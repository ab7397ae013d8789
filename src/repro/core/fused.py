"""Late materialization: fused scan→filter→aggregate plans (DESIGN.md §7).

The unfused path fully materializes every selected column of a row group
and then runs ``kernels/filter_agg.py`` as a separate launch — one extra
HBM round-trip of columns the predicate is about to throw away.  A
``FusedSpec`` attached to a Scanner/DecodePlanner splits the scan into:

  **stage A** (planner phases 1–2, unchanged machinery): decode the
  predicate/compare columns — plus any scanned column outside the spec —
  through the normal DecodePlan group path and evaluate their predicates
  host-side into a row mask;

  **stage B** (a new phase-3 work item): the *late* columns — aggregate
  operands and emit-only columns — are never materialized.  In aggregate
  mode their still-encoded page payloads ride into ONE
  ``kernels/fused_agg`` launch together with the stage-A mask (codes
  unpack, dictionary gather / PLAIN bitcast, residual predicates and the
  ``sum(left*right)`` reduce all happen in-kernel, one float32 partial
  per page).  Pages ruled out by the writer's per-page zone maps
  (``vmin``/``vmax`` in ``PageMeta.extra``) or by an all-false stage-A
  selection never enter the kernel arena at all — their canonical
  partial is exactly +0.0.  In selection mode the stage-A mask becomes a
  selection vector (ascending int64 row indices) and emit-only columns
  are materialized only when at least one row survived.

**Bit-identity contract.**  The canonical result of a predicated scan is
defined per page: the float32 partial ``kernels/fused_agg.page_partial``
computes over the page's padded (rows, 128) tiles, then
``float(np.sum(partials, dtype=np.float64))`` per row group,
then plan-order accumulation across row groups.  Reference execution
(``mode="reference"``, or any row group whose shape the fused plan
cannot take — cascade-coded operands, non-fusable aggregate inputs,
misaligned page layouts) materializes everything through the unfused
path and evaluates the SAME operations on the same page tiles, so fused
and unfused results diff exactly wherever both lower through one compiler,
which CI enforces on the CPU (tools/check_fused_identity.py).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.compression import Codec
from repro.core.encodings import Encoding
from repro.core.schema import PhysicalType

#: key under which a fused scan's per-row-group result is delivered in the
#: decoded-columns dict (late columns themselves are absent from it)
FUSED_KEY = "__fused__"

_NUMERIC_CAST = {
    PhysicalType.FLOAT: np.float32,
    PhysicalType.DOUBLE: np.float64,
    PhysicalType.INT32: np.int64,
    PhysicalType.INT64: np.int64,
}


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


# ---------------------------------------------------------------------------
# spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Interval:
    """Single-column predicate: optional lo/hi bounds and/or a value set.
    Bounds are compared in the column's decoded dtype (float32 columns
    compare against float32-cast constants — same bits as the unfused
    consumers)."""
    column: str
    lo: float | int | None = None
    hi: float | int | None = None
    lo_incl: bool = True
    hi_incl: bool = False
    in_set: tuple | None = None


@dataclasses.dataclass(frozen=True)
class Compare:
    """Cross-column predicate ``left < right`` (strict).  Both columns
    always decode in stage A."""
    left: str
    right: str


@dataclasses.dataclass(frozen=True)
class SumProduct:
    """Aggregate ``sum(left * right)`` over selected rows."""
    left: str
    right: str


@dataclasses.dataclass(frozen=True)
class FusedSpec:
    """Predicate + aggregate/emit spec a Scanner executes fused.

    Exactly one of aggregate mode (``agg`` set, ``emit`` empty — the
    per-RG result is a float partial) or selection mode (``agg`` None —
    the result is a selection vector plus gathered emit columns).
    ``mode="reference"`` executes unfused but computes the identical
    canonical result — the bit-identity twin CI diffs against.
    """
    predicates: tuple = ()
    compares: tuple = ()
    agg: SumProduct | None = None
    emit: tuple = ()
    mode: str = "fused"            # "fused" | "reference"

    def __post_init__(self):
        if self.mode not in ("fused", "reference"):
            raise ValueError(f"unknown fused mode {self.mode!r}")
        if self.agg is not None and self.emit:
            raise ValueError("aggregate and emit modes are exclusive")
        if self.agg is None and not (self.predicates or self.compares):
            raise ValueError("selection mode needs at least one predicate")

    def columns(self) -> list[str]:
        """Spec columns in canonical order (predicates, compares, agg,
        emit), deduplicated."""
        seen: dict[str, None] = {}
        for iv in self.predicates:
            seen.setdefault(iv.column)
        for cmp in self.compares:
            seen.setdefault(cmp.left)
            seen.setdefault(cmp.right)
        if self.agg is not None:
            seen.setdefault(self.agg.left)
            seen.setdefault(self.agg.right)
        for name in self.emit:
            seen.setdefault(name)
        return list(seen)

    def with_mode(self, mode: str) -> "FusedSpec":
        return dataclasses.replace(self, mode=mode)


# ---------------------------------------------------------------------------
# per-row-group fused plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OperandInfo:
    """One stage-B kernel operand (see kernels/fused_agg.py cfg format)."""
    name: str
    kind: str          # 'dict' | 'plain'
    width: int         # dict code bit width (0 for plain)
    vdtype: str        # 'float32' | 'int32'
    cfg: tuple         # static kernel config tuple


@dataclasses.dataclass
class FusedRGPlan:
    """How one row group executes under a FusedSpec.  ``ok=False`` means
    the shape is unsupported and the row group runs reference execution
    (full materialization, canonical compute) — correctness never depends
    on fusability."""
    ok: bool
    why: str
    n_pages: int
    page_counts: list[int]
    P: int                         # padded page lanes (pow2, >= 32)
    late: list[str]                # columns excluded from stage A
    operands: list[OperandInfo]    # aggregate-mode kernel operands
    zone_skip: frozenset           # pages provably all-false by zone maps

    @property
    def cfg(self) -> tuple:
        return tuple(op.cfg for op in self.operands)


class FusedRGResult:
    """Per-row-group result of a fused (or reference) execution, delivered
    as ``cols[FUSED_KEY]``.  Duck-types the two DecodeResult attributes the
    accounting layer reads (``on_device``, ``logical_bytes``)."""

    on_device = False

    __slots__ = ("partial", "partials", "selection", "gathered", "n_rows",
                 "n_selected", "pages_total", "pages_skipped",
                 "logical_bytes", "reference")

    def __init__(self, *, partial, partials, selection, gathered, n_rows,
                 n_selected, pages_total, pages_skipped, logical_bytes,
                 reference):
        self.partial = partial          # float | None (aggregate mode)
        self.partials = partials        # (n_pages,) float32 canonical
        self.selection = selection      # int64 row indices | None
        self.gathered = gathered        # {emit column: canonical ndarray}
        self.n_rows = n_rows
        self.n_selected = n_selected
        self.pages_total = pages_total
        self.pages_skipped = pages_skipped
        self.logical_bytes = logical_bytes
        self.reference = reference      # ran the unfused twin


def _iv_cfg(iv: Interval, vdtype: str, role: str = "",
            kind: str = "host", width: int = 0) -> tuple:
    return (kind, width, vdtype,
            iv.lo if iv is not None else None,
            iv.hi if iv is not None else None,
            iv.lo_incl if iv is not None else True,
            iv.hi_incl if iv is not None else False,
            tuple(iv.in_set) if iv is not None and iv.in_set is not None
            else None,
            role)


def _value_dtype(field) -> str | None:
    if field.physical == PhysicalType.FLOAT:
        return "float32"
    if field.physical == PhysicalType.INT32:
        return "int32"
    return None


def _operand_info(meta, rg, name: str, ivs: list, role: str
                  ) -> OperandInfo | None:
    """Kernel-fusable check for one column; None → it stays in stage A."""
    if len(ivs) > 1:
        return None                   # cfg carries at most one interval
    chunk = rg.column(name)
    field = meta.schema.field(name)
    if Codec(chunk.codec) not in (Codec.NONE, Codec.GZIP):
        return None                   # cascade payloads need device inflate
    vdtype = _value_dtype(field)
    if vdtype is None:
        return None                   # int64/double/bool/strings: stage A
    enc = Encoding(chunk.encoding)
    if enc == Encoding.RLE_DICTIONARY:
        widths = {pm.extra.get("bitwidth") for pm in chunk.pages}
        if len(widths) != 1:
            return None               # kernel width is static per launch
        width = widths.pop()
        if not isinstance(width, int) or width < 1 or width > 32:
            return None
        kind = "dict"
    elif enc == Encoding.PLAIN:
        kind, width = "plain", 0
    else:
        return None
    iv = ivs[0] if ivs else None
    return OperandInfo(name=name, kind=kind, width=int(width), vdtype=vdtype,
                       cfg=_iv_cfg(iv, vdtype, role, kind, int(width)))


def _interval_excludes(iv: Interval, cast, vmin, vmax) -> bool:
    """True when the page's [vmin, vmax] zone map proves the predicate
    false for every value on the page (conservative — equality keeps)."""
    if iv.lo is not None:
        lo = float(cast(iv.lo))
        if (vmax < lo) if iv.lo_incl else (vmax <= lo):
            return True
    if iv.hi is not None:
        hi = float(cast(iv.hi))
        if (vmin > hi) if iv.hi_incl else (vmin >= hi):
            return True
    if iv.in_set is not None:
        if all(float(cast(s)) < vmin or float(cast(s)) > vmax
               for s in iv.in_set):
            return True
    return False


def build_fused_rg_plan(planner, rg_index: int) -> FusedRGPlan:
    """Classify one row group under the planner's FusedSpec: stage-A vs
    late columns, kernel operand configs, zone-map page skips."""
    spec = planner.fused_spec
    meta = planner.meta
    rg = meta.row_groups[rg_index]
    cols = spec.columns()

    def bail(why: str) -> FusedRGPlan:
        return FusedRGPlan(ok=False, why=why, n_pages=0, page_counts=[],
                           P=32, late=[], operands=[],
                           zone_skip=frozenset())

    for c in cols:
        if c not in planner.columns:
            return bail(f"spec column {c} not in the scan selection")
    counts = [pm.n_values for pm in rg.column(cols[0]).pages]
    if not counts:
        return bail("row group has no pages")
    for c in cols[1:]:
        if [pm.n_values for pm in rg.column(c).pages] != counts:
            # the writer slices every column by the same rows_per_page, so
            # this only triggers on foreign/hand-built files
            return bail(f"page layout of {c} not row-aligned")
    P = max(32, _next_pow2(max(counts)))
    preds_by_col: dict[str, list[Interval]] = {}
    for iv in spec.predicates:
        preds_by_col.setdefault(iv.column, []).append(iv)
    compare_cols = {c for cmp in spec.compares
                    for c in (cmp.left, cmp.right)}

    late: list[str] = []
    operands: list[OperandInfo] = []
    if spec.agg is not None:
        for name in cols:
            role = ""
            if name == spec.agg.left and name == spec.agg.right:
                role = "both"
            elif name == spec.agg.left:
                role = "left"
            elif name == spec.agg.right:
                role = "right"
            if name in compare_cols:
                if role:
                    return bail(f"aggregate operand {name} is also a "
                                "compare column")
                continue                       # stage A
            ivs = preds_by_col.get(name, [])
            if not role and not ivs:
                continue                       # untouched by this spec
            info = _operand_info(meta, rg, name, ivs, role)
            if info is None:
                if role:
                    return bail(f"aggregate operand {name} is not "
                                "kernel-fusable here")
                continue                       # predicate stays in stage A
            late.append(name)
            operands.append(info)
    else:
        # selection mode: every predicate/compare column evaluates in
        # stage A; emit-only columns are late (materialized on demand)
        for name in spec.emit:
            if name in preds_by_col or name in compare_cols:
                continue
            field = meta.schema.field(name)
            if field.physical == PhysicalType.BYTE_ARRAY:
                return bail(f"string emit column {name} unsupported")
            late.append(name)

    zone_skip = set()
    for name, ivs in preds_by_col.items():
        field = meta.schema.field(name)
        cast = _NUMERIC_CAST.get(field.physical)
        if cast is None:
            continue
        for i, pm in enumerate(rg.column(name).pages):
            if i in zone_skip or "vmin" not in pm.extra:
                continue
            vmin, vmax = float(pm.extra["vmin"]), float(pm.extra["vmax"])
            if any(_interval_excludes(iv, cast, vmin, vmax) for iv in ivs):
                zone_skip.add(i)
    return FusedRGPlan(ok=True, why="", n_pages=len(counts),
                       page_counts=counts, P=P, late=late,
                       operands=operands, zone_skip=frozenset(zone_skip))


# ---------------------------------------------------------------------------
# execution (the planner's phase-3 work item)
# ---------------------------------------------------------------------------

def _payload_bytes(payloads, name: str, page_index: int) -> bytes:
    p = payloads[(name, page_index)]
    if isinstance(p, tuple):
        raw, lo, size = p
        return raw[lo:lo + size]
    return p


def _materialize(planner, ctx, name: str):
    """Assembled DecodeResult for a stage-A column (phase 3 runs before
    finish_execute, so grouped columns assemble here on first use;
    fallback/demoted columns are already in ctx.out)."""
    res = ctx.out.get(name)
    if res is not None:
        return res
    chunk = ctx.rg.column(name)
    field = planner.meta.schema.field(name)
    res = planner._assemble_column(chunk, field, ctx.per_col_parts[name],
                                   ctx.payloads)
    ctx.out[name] = res
    return res


def _page_rows(arr: np.ndarray, counts: list[int], P: int,
               dtype=None) -> np.ndarray:
    """(n_rows,) → (n_pages, P) padded page matrix (pad lanes zero —
    always masked out by the validity lanes of the mask matrix)."""
    out = np.zeros((len(counts), P), dtype=dtype or arr.dtype)
    off = 0
    for i, c in enumerate(counts):
        out[i, :c] = arr[off:off + c]
        off += c
    return out


def _stage_a_mask(planner, ctx, spec, fplan, reference: bool) -> np.ndarray:
    """Row mask from every predicate evaluated host-side: all of them
    under reference/selection execution, the non-late ones under fused
    aggregate execution (late predicates fold into the kernel).  Numpy
    compares on the decoded values — exact, so the mask bits match what
    the kernel would compute."""
    from repro.kernels.fused_agg import apply_predicates
    late = set() if reference else set(fplan.late)
    n_rows = sum(fplan.page_counts)
    mask = np.ones(n_rows, dtype=bool)
    vals_cache: dict[str, np.ndarray] = {}

    def vals(name):
        v = vals_cache.get(name)
        if v is None:
            v = np.asarray(_materialize(planner, ctx, name).array)
            vals_cache[name] = v
        return v

    for iv in spec.predicates:
        if iv.column in late:
            continue
        field = planner.meta.schema.field(iv.column)
        vdtype = _value_dtype(field) or "float32"
        mask = apply_predicates(mask, vals(iv.column),
                                _iv_cfg(iv, vdtype))
    for cmp in spec.compares:
        mask = mask & (vals(cmp.left) < vals(cmp.right))
    return mask


def _reduce_cfg(left_dtype: str, right_dtype: str) -> tuple:
    """Reference-twin cfg: two predicate-free operands in left/right roles
    (the full mask is precomputed host-side)."""
    return (("host", 0, left_dtype, None, None, True, False, None, "left"),
            ("host", 0, right_dtype, None, None, True, False, None, "right"))


def _host_decode_operand_page(planner, ctx, op: OperandInfo, rg,
                              page_index: int, count: int) -> np.ndarray:
    """Numpy twin of the in-kernel operand decode: identical values, so
    the host backend's fused partials match the pallas kernel's bits."""
    from repro.core import bitpack
    data = _payload_bytes(ctx.payloads, op.name, page_index)
    if op.kind == "dict":
        words = np.frombuffer(data, dtype=np.uint32, count=len(data) // 4)
        codes = bitpack.unpack(words, op.width,
                               (words.shape[0] // op.width) * 32)[:count]
        dic = planner._device_dictionary(rg, op.name, ctx.payloads).host
        codes = np.clip(codes.astype(np.int64), 0, dic.shape[0] - 1)
        return dic[codes]
    dt = np.float32 if op.vdtype == "float32" else np.int32
    return np.frombuffer(data, dtype=dt, count=count)


def _canonical_gather(values: np.ndarray, selection: np.ndarray
                      ) -> np.ndarray:
    """Gathered emit values in canonical dtype: integer columns widen to
    int64 (the device path narrows int64→int32, the host path keeps
    int64 — gathering through int64 makes both routes bit-identical)."""
    out = np.asarray(values)[selection]
    if out.dtype.kind in "iu":
        return out.astype(np.int64)
    return np.ascontiguousarray(out)


def _emit_dtype(field) -> np.dtype:
    if field.physical == PhysicalType.FLOAT:
        return np.dtype(np.float32)
    if field.physical == PhysicalType.DOUBLE:
        return np.dtype(np.float64)
    if field.physical == PhysicalType.BOOLEAN:
        return np.dtype(np.bool_)
    return np.dtype(np.int64)


def run_fused(planner, ctx) -> FusedRGResult:
    """The phase-3 work item: stage-A mask → fused kernel / selection
    gather (or the reference twin), producing the canonical per-RG
    result."""
    from repro.kernels.fused_agg import (fused_page_agg,
                                         reference_page_reduce)
    spec = planner.fused_spec
    fplan = ctx.fused_plan
    rg = ctx.rg
    if not fplan.ok:
        # rebuild page geometry from any spec column that exists; a spec
        # column missing from the scan selection is a caller error
        for c in spec.columns():
            if c not in planner.columns:
                raise ValueError(fplan.why)
        counts = [pm.n_values for pm in rg.column(spec.columns()[0]).pages]
        fplan = dataclasses.replace(
            fplan, n_pages=len(counts), page_counts=counts,
            P=max(32, _next_pow2(max(counts or [1]))), late=[],
            operands=[], zone_skip=frozenset())
    reference = (spec.mode == "reference") or not ctx.fused_plan.ok
    counts, P, n_pages = fplan.page_counts, fplan.P, fplan.n_pages
    n_rows = sum(counts)
    mask = _stage_a_mask(planner, ctx, spec, fplan, reference)
    mask_rows = _page_rows(mask.astype(np.uint8), counts, P)
    page_any = mask_rows.any(axis=1)

    if spec.agg is not None:
        partials = np.zeros(n_pages, dtype=np.float32)
        if reference:
            lname, rname = spec.agg.left, spec.agg.right
            lvals = np.asarray(_materialize(planner, ctx, lname).array)
            rvals = (lvals if rname == lname
                     else np.asarray(_materialize(planner, ctx,
                                                  rname).array))
            lrows = _page_rows(lvals, counts, P)
            rrows = lrows if rname == lname else _page_rows(rvals, counts, P)
            ldt = _value_dtype(planner.meta.schema.field(lname)) or "float32"
            rdt = _value_dtype(planner.meta.schema.field(rname)) or "float32"
            cfg = _reduce_cfg(ldt, rdt)
            for i in range(n_pages):
                partials[i] = np.float32(reference_page_reduce(
                    mask_rows[i:i + 1], lrows[i:i + 1], rrows[i:i + 1],
                    cfg=cfg))
            skipped = 0
        else:
            surv = [i for i in range(n_pages)
                    if i not in fplan.zone_skip and page_any[i]]
            skipped = n_pages - len(surv)
            if surv:
                if ctx.use_kernels:
                    arrays = []
                    for op in fplan.operands:
                        if op.kind == "dict":
                            wrow = (P // 32) * op.width
                            words = np.zeros((len(surv), wrow), np.uint32)
                            for r, i in enumerate(surv):
                                data = _payload_bytes(ctx.payloads,
                                                      op.name, i)
                                w = np.frombuffer(data, dtype=np.uint32,
                                                  count=len(data) // 4)
                                words[r, :min(w.shape[0], wrow)] = w[:wrow]
                            arrays.append(words)
                            arrays.append(planner._device_dictionary(
                                rg, op.name, ctx.payloads).device)
                        else:
                            words = np.zeros((len(surv), P), np.uint32)
                            for r, i in enumerate(surv):
                                data = _payload_bytes(ctx.payloads,
                                                      op.name, i)
                                w = np.frombuffer(data, dtype=np.uint32,
                                                  count=len(data) // 4)
                                words[r, :counts[i]] = w[:counts[i]]
                            arrays.append(words)
                    out = np.asarray(fused_page_agg(
                        mask_rows[surv], arrays, cfg=fplan.cfg))
                    partials[surv] = out
                else:
                    cfg = fplan.cfg
                    for i in surv:
                        rows = [_page_rows(
                            _host_decode_operand_page(planner, ctx, op, rg,
                                                      i, counts[i]),
                            [counts[i]], P)
                            for op in fplan.operands]
                        partials[i] = np.float32(reference_page_reduce(
                            mask_rows[i:i + 1], *rows, cfg=cfg))
        total = float(np.sum(partials, dtype=np.float64))
        return FusedRGResult(
            partial=total, partials=partials, selection=None, gathered={},
            n_rows=n_rows, n_selected=-1, pages_total=n_pages,
            pages_skipped=skipped, logical_bytes=int(partials.nbytes),
            reference=reference)

    # -- selection mode ----------------------------------------------------
    selection = np.flatnonzero(mask).astype(np.int64)
    n_selected = int(selection.shape[0])
    skipped = 0 if reference else int(n_pages - np.count_nonzero(page_any))
    gathered: dict[str, np.ndarray] = {}
    for name in spec.emit:
        field = planner.meta.schema.field(name)
        if n_selected == 0:
            gathered[name] = np.zeros(0, dtype=_emit_dtype(field))
            continue
        if not reference and name in fplan.late:
            # materialized on demand, host route (no extra kernel launch);
            # values are bit-identical to the device decode for the
            # canonical dtypes (_canonical_gather)
            from repro.kernels import ops
            chunk = ctx.rg.column(name)
            res = ops.decode_chunk(
                chunk, field, ctx.raws[name], use_kernels=False,
                payloads=planner._fallback_payloads(chunk, name, ctx.raws))
            values = np.asarray(res.array)
        else:
            values = np.asarray(_materialize(planner, ctx, name).array)
        gathered[name] = _canonical_gather(values, selection)
    logical = int(selection.nbytes
                  + sum(a.nbytes for a in gathered.values()))
    return FusedRGResult(
        partial=None, partials=None, selection=selection, gathered=gathered,
        n_rows=n_rows, n_selected=n_selected, pages_total=n_pages,
        pages_skipped=skipped, logical_bytes=logical, reference=reference)
