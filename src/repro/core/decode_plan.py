"""Row-group-level decode planning: cross-column batched decode (DESIGN.md §2.4).

The per-chunk decode path (kernels/ops.py::decode_chunk) issues one Pallas
call per column chunk — and per stride/width group inside it — so a
16-column row group pays ~16+ kernel launches.  Insight 1 of the paper says
GPU scan throughput comes from exposing *all* pages to the device at once;
this module takes that to its logical end at row-group granularity:

  1. a host-side **planning pass** walks every selected column chunk of a
     row group and groups all data pages — across columns — by
     ``(encoding, codec, bitwidth/stride class)``;
  2. each group's payloads are packed into one preallocated uint32 **arena**
     (contiguous page runs are copied with a single reshape copy, not one
     ``np.frombuffer`` per page);
  3. **one Pallas call per group** decodes pages from many columns at once
     (O(encoding groups) launches instead of O(columns × stride groups));
  4. decoded rows are scattered back into per-column ``DecodeResult``s that
     are bit-identical to the per-chunk reference path.

Plans depend only on the file footer + column selection, so they are cached
(module-level LRU) and repeated scans — the serving/query loop — skip
planning entirely.

The same plan also drives the *host* backend: group execution batches the
``bitpack.unpack`` / run-expansion work across every page of a group, which
collapses the per-page numpy call overhead that dominates host decode for
many-page files (see benchmarks/bench_scan_plan.py).

Class parameters (the padding buckets) are powers of two so ragged page
shapes across columns land in O(log size) groups; padded regions decode to
don't-care values past each page's true ``n_values`` and are sliced away.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import os
import threading
import time
from collections import OrderedDict
from collections.abc import Callable, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import bitpack
from repro.core import fused as fused_mod
from repro.core import trace
from repro.core.compression import (Codec, cascade_manifest,
                                    chunk_decompress_memo, decompress,
                                    verify_page)
from repro.core.encodings import (Encoding, build_delta_manifest,
                                  decode_plain_page)
from repro.core.metadata import ChunkMeta, FileMeta, PageMeta
from repro.core.schema import Field, PhysicalType
from repro.kernels import dict_decode, ops

_INT_TYPES = (PhysicalType.INT32, PhysicalType.INT64)

# A cross-column dictionary group ships one padded dictionary row per page
# (n_pages × d_max).  Beyond this arena size the duplication costs more
# than the saved launches, so the planner splits the group per column and
# each sub-group uses the shared-dictionary kernel instead.
_DICT_ARENA_CAP_BYTES = 16 * 1024 * 1024


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


_planner_token_counter = itertools.count()


# ---------------------------------------------------------------------------
# arena pool
# ---------------------------------------------------------------------------

class ArenaPool:
    """Reusable decode-arena buffers (DESIGN.md §2.4).

    ``take`` returns a ``(shape, dtype)`` ndarray view over a pooled byte
    buffer; ``give`` returns the buffer once the row group's kernels have
    consumed it, so consecutive row groups of the same file share arenas
    instead of paying a fresh ``np.zeros`` each (the PR-1 allocation).
    Reused buffers are **not** re-zeroed: arena words past each page's
    payload decode to don't-care values that the scatter stage slices away
    (``n_values``-exact), so zero-filling per row group is pure overhead.

    Thread-safe (the pipeline executor's decode workers share the planner);
    byte-capped — buffers beyond ``max_bytes`` are dropped on ``give``.
    """

    def __init__(self, max_bytes: int = 32 * 1024 * 1024):
        self.max_bytes = max_bytes
        self._free: dict[int, list[np.ndarray]] = {}
        self._lock = threading.Lock()
        self._pooled_bytes = 0
        self.allocs = 0
        self.reuses = 0

    def take(self, shape: tuple[int, ...], dtype
             ) -> tuple[np.ndarray, np.ndarray]:
        """Returns ``(view, buffer)``; pass ``buffer`` back to ``give``."""
        dt = np.dtype(dtype)
        need = int(np.prod(shape, dtype=np.int64)) * dt.itemsize
        cap = _next_pow2(need)
        buf = None
        with self._lock:
            stack = self._free.get(cap)
            if stack:
                buf = stack.pop()
                self._pooled_bytes -= cap
                self.reuses += 1
        if buf is None:
            buf = np.zeros(cap, dtype=np.uint8)
            self.allocs += 1
        return buf[:need].view(dt).reshape(shape), buf

    def give(self, buf: np.ndarray) -> None:
        cap = buf.shape[0]
        with self._lock:
            if self._pooled_bytes + cap <= self.max_bytes:
                self._free.setdefault(cap, []).append(buf)
                self._pooled_bytes += cap


# ---------------------------------------------------------------------------
# plan structures
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PageSlot:
    """One data page's place in a decode group (column + page index)."""
    column: str
    page_index: int
    n_values: int


@dataclasses.dataclass
class DecodeGroup:
    """Pages from any number of columns that decode in one batched call."""
    key: tuple                    # (encoding, codec, *class params)
    encoding: Encoding
    codec: Codec
    slots: list[PageSlot]

    @property
    def n_pages(self) -> int:
        return len(self.slots)


@dataclasses.dataclass
class CascadeGroup:
    """Device-cascade pages sharing one (value_width, count_width) class —
    one ``cascade_decode_pages`` launch.  Grouped at *plan* time from the
    widths the writer stamps into ``PageMeta.extra`` (``cascade_vw/cw``);
    ``key=None`` collects pages of older files without the stamp, which
    fall back to execute-time grouping by manifest widths."""
    key: tuple[int, int] | None
    slots: list[PageSlot]


@dataclasses.dataclass
class RowGroupPlan:
    rg_index: int
    groups: list[DecodeGroup]
    grouped_columns: list[str]    # decoded via the batched group path
    fallback_columns: list[str]   # decoded via the per-chunk reference path
    # decompress sub-plan: grouped columns whose pages inflate on the host
    # through the chunk memo vs. raw-view columns vs. device-cascade pages
    # (the latter pre-grouped by (vw, cw) — see CascadeGroup)
    memo_columns: list[str] = dataclasses.field(default_factory=list)
    raw_columns: list[str] = dataclasses.field(default_factory=list)
    cascade_groups: list[CascadeGroup] = dataclasses.field(
        default_factory=list)

    @property
    def n_groups(self) -> int:
        return len(self.groups)


# ---------------------------------------------------------------------------
# eligibility / group keys
#
# The key functions mirror the fallback conditions in ops.decode_chunk so
# the plan path takes the device (or batched-host) route exactly when the
# per-chunk reference path would — required for bit-identical results.
# ---------------------------------------------------------------------------

_DICT_DEVICE_DTYPE = {
    PhysicalType.INT32: "int32",
    PhysicalType.INT64: "int32",      # narrowed (stats-gated below)
    PhysicalType.FLOAT: "float32",
    PhysicalType.BOOLEAN: "uint8",
}


def _pallas_page_keys(chunk: ChunkMeta, field: Field) -> list[tuple] | None:
    """Per-page group keys for the device path, or None → per-chunk fallback."""
    enc = Encoding(chunk.encoding)
    codec = int(chunk.codec)
    if not chunk.pages:
        return None
    if enc == Encoding.RLE_DICTIONARY:
        dt = _DICT_DEVICE_DTYPE.get(field.physical)
        if dt is None:
            return None
        if (field.physical == PhysicalType.INT64
                and not ops._stats_fit_int32(chunk)):
            return None
        return [(int(enc), codec, pm.extra["bitwidth"], dt)
                for pm in chunk.pages]
    if enc == Encoding.DELTA_BINARY_PACKED:
        if not ops._stats_fit_int32(chunk):
            return None
        if max(pm.extra["n_blocks"] for pm in chunk.pages) == 0:
            return None
        return [(int(enc), codec, _next_pow2(max(pm.extra["n_blocks"], 1)))
                for pm in chunk.pages]
    if enc == Encoding.RLE:
        if (field.physical == PhysicalType.INT64
                and not ops._stats_fit_int32(chunk)):
            return None
        if any(pm.extra["n_runs"] > ops._RLE_MAX_RUNS for pm in chunk.pages):
            return None
        vdt = "int64" if field.physical == PhysicalType.INT64 else "int32"
        return [(int(enc), codec,
                 _next_pow2(-(-max(pm.n_values, 1) // 1024)) * 1024, vdt)
                for pm in chunk.pages]
    if enc == Encoding.BYTE_STREAM_SPLIT:
        if field.physical != PhysicalType.FLOAT:
            return None
        return [(int(enc), codec,
                 _next_pow2((pm.n_values + (-pm.n_values) % 4) // 4))
                for pm in chunk.pages]
    # PLAIN is a memcpy (no kernel launch to save); strings/float64 are
    # host-path encodings — the per-chunk reference handles all of them.
    return None


def _host_page_keys(chunk: ChunkMeta, field: Field) -> list[tuple] | None:
    """Group keys for the batched-host path (no padding classes needed —
    numpy handles ragged pages; keys only separate incompatible layouts)."""
    enc = Encoding(chunk.encoding)
    codec = int(chunk.codec)
    if not chunk.pages:
        return None
    if enc == Encoding.RLE_DICTIONARY:
        if field.physical == PhysicalType.BYTE_ARRAY:
            return None               # StringColumn dictionaries: reference
        return [(int(enc), codec, pm.extra["bitwidth"]) for pm in chunk.pages]
    if enc == Encoding.DELTA_BINARY_PACKED:
        if field.physical not in _INT_TYPES:
            return None
        return [(int(enc), codec) for pm in chunk.pages]
    if enc == Encoding.RLE:
        vdt = "int64" if field.physical == PhysicalType.INT64 else "int32"
        return [(int(enc), codec, vdt) for pm in chunk.pages]
    return None


# ---------------------------------------------------------------------------
# staged execution context
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ExecContext:
    """Shared state of one row group's staged decode (per-chunk dispatch).

    Built by ``DecodePlanner.begin_execute``; mutated by the decompress /
    decode work items; consumed by ``finish_execute``.  Tasks of one
    context may run concurrently on the ScanService's decode pool — each
    writes disjoint keys, see the concurrency contract in DecodePlanner.
    """
    rg_index: int
    plan: RowGroupPlan
    rg: object                       # RowGroupMeta
    raws: dict[str, bytes]
    use_kernels: bool
    per_col_parts: dict[str, dict]
    payloads: dict = dataclasses.field(default_factory=dict)
    demoted: list[str] = dataclasses.field(default_factory=list)
    out: dict[str, "ops.DecodeResult"] = dataclasses.field(
        default_factory=dict)
    leases: list[np.ndarray] = dataclasses.field(default_factory=list)
    # late-materialization state (core/fused.py): the per-RG fused plan
    # and the phase-3 result delivered under FUSED_KEY
    fused_plan: object = None
    fused_result: object = None


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

class DecodePlanner:
    """Builds + caches RowGroupPlans for one (file, column selection).

    ``backend`` is 'pallas' (batched device groups) or 'host' (batched numpy
    groups); both scatter back into per-column results bit-identical to the
    per-chunk path of the same backend.
    """

    def __init__(self, meta: FileMeta, columns: Sequence[str],
                 backend: str = "pallas",
                 cache_token: tuple | None = None,
                 fused_spec: "fused_mod.FusedSpec | None" = None):
        assert backend in ("pallas", "host")
        self.meta = meta
        self.columns = list(columns)
        self.backend = backend
        self.fused_spec = fused_spec
        self._fused_plans: dict[int, "fused_mod.FusedRGPlan"] = {}
        self._plans: dict[int, RowGroupPlan] = {}
        self.plans_built = 0
        self.plan_seconds = 0.0
        # identifies the file *contents* this planner decodes; keys the
        # cross-row-group dictionary cache and decompress memo so a
        # same-path rewrite can never serve stale entries
        self.cache_token = (cache_token if cache_token is not None
                            else ("planner", next(_planner_token_counter)))
        self._plan_lock = threading.Lock()
        self._arena_pool = ArenaPool()

    # -- planning ----------------------------------------------------------

    def plan_rg(self, rg_index: int) -> RowGroupPlan:
        plan = self._plans.get(rg_index)
        if plan is not None:
            return plan
        with self._plan_lock:     # decode workers may plan concurrently
            plan = self._plans.get(rg_index)
            if plan is not None:
                return plan
            t0 = time.perf_counter()
            key_fn = (_pallas_page_keys if self.backend == "pallas"
                      else _host_page_keys)
            rg = self.meta.row_groups[rg_index]
            # late materialization: under a fused-mode spec the late
            # columns never enter the stage-A plan at all — their pages
            # decode (or are skipped) inside the phase-3 fused item
            late: frozenset = frozenset()
            if (self.fused_spec is not None
                    and self.fused_spec.mode == "fused"):
                fp = self._fused_plan_locked(rg_index)
                if fp.ok:
                    late = frozenset(fp.late)
            groups: "OrderedDict[tuple, DecodeGroup]" = OrderedDict()
            grouped, fallback = [], []
            for name in self.columns:
                if name in late:
                    continue
                chunk = rg.column(name)
                field = self.meta.schema.field(name)
                keys = key_fn(chunk, field)
                if keys is None:
                    fallback.append(name)
                    continue
                grouped.append(name)
                for pi, (pm, key) in enumerate(zip(chunk.pages, keys)):
                    g = groups.get(key)
                    if g is None:
                        g = DecodeGroup(key=key, encoding=Encoding(key[0]),
                                        codec=Codec(key[1]), slots=[])
                        groups[key] = g
                    g.slots.append(PageSlot(name, pi, pm.n_values))
            final: list[DecodeGroup] = []
            for g in groups.values():
                final.extend(self._split_oversize_dict_group(g, rg))
            plan = RowGroupPlan(rg_index, final, grouped, fallback)
            self._plan_decompress_stage(plan, rg)
            self._plans[rg_index] = plan
            self.plans_built += 1
            self.plan_seconds += time.perf_counter() - t0
            return plan

    def fused_plan_rg(self, rg_index: int) -> "fused_mod.FusedRGPlan":
        fp = self._fused_plans.get(rg_index)
        if fp is not None:
            return fp
        with self._plan_lock:
            return self._fused_plan_locked(rg_index)

    def _fused_plan_locked(self, rg_index: int) -> "fused_mod.FusedRGPlan":
        fp = self._fused_plans.get(rg_index)
        if fp is None:
            fp = fused_mod.build_fused_rg_plan(self, rg_index)
            self._fused_plans[rg_index] = fp
        return fp

    def _plan_decompress_stage(self, plan: RowGroupPlan, rg) -> None:
        """Classify grouped columns for the decompress stage and group
        device-cascade pages by their footer-stamped (vw, cw) class, so
        execute never re-reads page headers to discover the grouping."""
        cas: "OrderedDict[tuple[int, int] | None, CascadeGroup]" = \
            OrderedDict()
        for name in plan.grouped_columns:
            chunk = rg.column(name)
            codec = Codec(chunk.codec)
            if codec == Codec.GZIP or (codec == Codec.CASCADE
                                       and self.backend != "pallas"):
                plan.memo_columns.append(name)
                continue
            plan.raw_columns.append(name)
            if codec == Codec.CASCADE:      # pallas: device decompress
                for pi, pm in enumerate(chunk.pages):
                    key = None
                    if "cascade_vw" in pm.extra:
                        key = (int(pm.extra["cascade_vw"]),
                               int(pm.extra["cascade_cw"]))
                    g = cas.get(key)
                    if g is None:
                        g = cas[key] = CascadeGroup(key=key, slots=[])
                    g.slots.append(PageSlot(name, pi, pm.n_values))
        plan.cascade_groups = list(cas.values())

    def _split_oversize_dict_group(self, group: DecodeGroup, rg
                                   ) -> list[DecodeGroup]:
        """Bound the per-page dictionary duplication of multi-column dict
        groups (see _DICT_ARENA_CAP_BYTES): oversize groups split per
        column, which the executor decodes with the shared-dict kernel."""
        if (self.backend != "pallas"
                or group.encoding != Encoding.RLE_DICTIONARY):
            return [group]
        cols = {s.column for s in group.slots}
        if len(cols) == 1:
            return [group]
        d_max = max(rg.column(c).dict_page.n_values for c in cols)
        if len(group.slots) * d_max * 4 <= _DICT_ARENA_CAP_BYTES:
            return [group]
        by_col: "OrderedDict[str, list[PageSlot]]" = OrderedDict()
        for s in group.slots:
            by_col.setdefault(s.column, []).append(s)
        return [DecodeGroup(key=group.key + (name,), encoding=group.encoding,
                            codec=group.codec, slots=slots)
                for name, slots in by_col.items()]

    # -- execution ---------------------------------------------------------
    #
    # Execution is *staged* so the ScanService (core/scheduler.py) can
    # dispatch every DecodePlan group of a row group as an independently
    # schedulable work item (per-chunk dispatch): ``begin_execute`` builds
    # the shared context, ``decompress_tasks`` returns the phase-1 items
    # (host inflate per memoizable column, raw views, one device launch per
    # cascade (vw, cw) class), ``decode_tasks`` — valid once phase 1 has
    # drained — returns the phase-2 items (one per DecodeGroup plus one per
    # fallback column), and ``finish_execute`` is the join barrier that
    # assembles columns, flushes the device, and returns pooled arenas.
    # ``execute`` runs the same stages serially, so the scheduled path is
    # bit-identical to the inline path by construction
    # (tests/test_scheduler.py pins it against the reference decoder too).
    #
    # Concurrency contract for tasks of ONE context: distinct tasks write
    # distinct ``payloads`` / ``per_col_parts`` keys (single dict stores,
    # atomic under the GIL); appends to ``leases`` and ``out`` go through
    # the same atomic operations; the planner-level caches (arena pool,
    # dictionary cache, decompress memo) are themselves thread-safe.

    def execute(self, rg_index: int, raws: dict[str, bytes]
                ) -> dict[str, ops.DecodeResult]:
        ctx = self.begin_execute(rg_index, raws)
        for task in self.decompress_tasks(ctx):
            task()
        for task in self.decode_tasks(ctx):
            task()
        for task in self.fused_tasks(ctx):
            task()
        return self.finish_execute(ctx)

    def begin_execute(self, rg_index: int, raws: dict[str, bytes]
                      ) -> "ExecContext":
        plan = self.plan_rg(rg_index)
        ctx = ExecContext(
            rg_index=rg_index, plan=plan,
            rg=self.meta.row_groups[rg_index], raws=raws,
            use_kernels=(self.backend == "pallas"),
            per_col_parts={name: {} for name in plan.grouped_columns})
        if self.fused_spec is not None:
            ctx.fused_plan = self.fused_plan_rg(rg_index)
        return ctx

    def decompress_tasks(self, ctx: "ExecContext") -> list[Callable[[], None]]:
        """Phase-1 work items: decompressed page payloads for every grouped
        column.  Host-decompressed chunks (gzip on either backend, cascade
        on the host backend) go through the chunk-level decompress memo —
        a scan that revisits the chunk reuses the inflated payloads instead
        of re-running one zlib call per page.  Device-cascade pages launch
        one kernel per plan-time (vw, cw) group."""
        tasks: list[Callable[[], None]] = []
        for name in ctx.plan.memo_columns:
            tasks.append(functools.partial(self._inflate_column_task,
                                           ctx, name))
        if ctx.plan.raw_columns:
            tasks.append(functools.partial(self._raw_views_task, ctx))
        for group in ctx.plan.cascade_groups:
            tasks.append(functools.partial(self._cascade_group_task,
                                           ctx, group))
        if (ctx.fused_plan is not None and ctx.fused_plan.ok
                and self.fused_spec.mode == "fused"):
            # fused-mode aggregate operands: stage their still-encoded
            # page payloads now, CRC-verified — the ChecksumError-before-
            # kernel gate for the fused path (tools/chaos_check.py)
            for op in ctx.fused_plan.operands:
                tasks.append(functools.partial(self._fused_payload_task,
                                               ctx, op.name))
        return tasks

    def _fused_payload_task(self, ctx: "ExecContext", name: str) -> None:
        """Verified page payloads for one late fused operand (its column
        is outside the stage-A plan, so neither the memo nor the raw-view
        task covers it).  Operand eligibility restricts the codec to
        NONE/GZIP (core/fused.py)."""
        chunk = ctx.rg.column(name)
        codec = Codec(chunk.codec)
        if codec == Codec.GZIP:
            self._inflate_column_task(ctx, name)
            return
        raw = ctx.raws[name]
        off0, _ = chunk.byte_range
        if chunk.dict_page is not None:
            dp = chunk.dict_page
            data = raw[dp.offset - off0:dp.offset - off0 + dp.stored_size]
            verify_page(data, dp, where=f"{name} dict@{dp.offset}")
            ctx.payloads[(name, "dict")] = decompress(
                data, codec, dp.uncompressed_size)
        for pi, pm in enumerate(chunk.pages):
            lo = pm.offset - off0
            verify_page(raw[lo:lo + pm.stored_size], pm,
                        where=f"{name} page@{pm.offset}")
            ctx.payloads[(name, pi)] = (raw, lo, pm.stored_size)

    def _inflate_column_task(self, ctx: "ExecContext", name: str) -> None:
        chunk = ctx.rg.column(name)
        memo = chunk_decompress_memo()
        memo_key = self._memo_key(chunk, name)
        entry = memo.get(memo_key)
        if entry is None:
            entry = memo.put(memo_key,
                             self._inflate_chunk_entry(chunk, ctx.raws[name]))
        for k, v in entry.items():
            ctx.payloads[(name, k)] = v

    def _raw_views_task(self, ctx: "ExecContext") -> None:
        """Raw-view tuples for uncompressed pages (enables the single-copy
        arena fill) + host dict-page decompress for every non-memo column.
        Cheap — one item covers all such columns."""
        for name in ctx.plan.raw_columns:
            chunk = ctx.rg.column(name)
            raw = ctx.raws[name]
            off0, _ = chunk.byte_range
            codec = Codec(chunk.codec)
            if chunk.dict_page is not None:
                dp = chunk.dict_page
                data = raw[dp.offset - off0:dp.offset - off0
                           + dp.stored_size]
                verify_page(data, dp, where=f"{name} dict@{dp.offset}")
                ctx.payloads[(name, "dict")] = decompress(
                    data, codec, dp.uncompressed_size)
            if codec == Codec.NONE:
                for pi, pm in enumerate(chunk.pages):
                    lo = pm.offset - off0
                    verify_page(raw[lo:lo + pm.stored_size], pm,
                                where=f"{name} page@{pm.offset}")
                    ctx.payloads[(name, pi)] = (raw, lo, pm.stored_size)

    def _cascade_group_task(self, ctx: "ExecContext",
                            group: CascadeGroup) -> None:
        """One device decompress launch for one (vw, cw) class (or the
        execute-time-grouped leftovers of width-unstamped files)."""
        pages = []
        for s in group.slots:
            chunk = ctx.rg.column(s.column)
            pm = chunk.pages[s.page_index]
            off0, _ = chunk.byte_range
            lo = pm.offset - off0
            data = ctx.raws[s.column][lo:lo + pm.stored_size]
            verify_page(data, pm,
                        where=f"{s.column} page@{pm.offset}")
            pages.append((pm, data))
        if group.key is not None:
            datas = ops.cascade_decompress_pages_grouped(pages)
            for s, data in zip(group.slots, datas):
                ctx.payloads[(s.column, s.page_index)] = data
        else:
            dec = ops.cascade_decompress_device(pages)
            for s, (_, data) in zip(group.slots, dec):
                ctx.payloads[(s.column, s.page_index)] = data

    def decode_tasks(self, ctx: "ExecContext") -> list[Callable[[], None]]:
        """Phase-2 work items (valid once every decompress task drained):
        one per DecodeGroup plus one per fallback/demoted column.  The
        wide-delta demotion scan runs here, serially, so every group task
        sees the final demoted set (mirrors the chunk-granular reference
        fallback)."""
        plan = ctx.plan
        if ctx.use_kernels:
            for group in plan.groups:
                if group.encoding != Encoding.DELTA_BINARY_PACKED:
                    continue
                slots = [s for s in group.slots
                         if s.column not in ctx.demoted]
                _, newly = self._demote_wide_delta(ctx.rg, slots,
                                                   ctx.payloads)
                ctx.demoted.extend(newly)
        tasks: list[Callable[[], None]] = []
        for group in plan.groups:
            tasks.append(functools.partial(self._group_task, ctx, group))
        for name in list(plan.fallback_columns) + list(ctx.demoted):
            tasks.append(functools.partial(self._fallback_task, ctx, name))
        return tasks

    def _group_task(self, ctx: "ExecContext", group: DecodeGroup) -> None:
        slots = [s for s in group.slots if s.column not in ctx.demoted]
        if not slots:
            return
        exec_group = (self._execute_group_pallas if ctx.use_kernels
                      else self._execute_group_host)
        exec_group(group, slots, ctx.rg, ctx.payloads, ctx.per_col_parts,
                   ctx.leases)

    def _fallback_task(self, ctx: "ExecContext", name: str) -> None:
        chunk = ctx.rg.column(name)
        field = self.meta.schema.field(name)
        ctx.out[name] = ops.decode_chunk(
            chunk, field, ctx.raws[name], use_kernels=ctx.use_kernels,
            payloads=self._fallback_payloads(chunk, name, ctx.raws))

    def fused_tasks(self, ctx: "ExecContext") -> list[Callable[[], None]]:
        """Phase-3 work item (valid once every decode task drained): the
        fused stage-B of a predicated scan — stage-A mask, zone/selection
        page skips, ONE fused kernel launch (or the reference twin).
        Empty for planners without a FusedSpec, so the scheduler's phase
        accounting is untouched on the unfused path."""
        if ctx.fused_plan is None:
            return []
        return [functools.partial(self._fused_task, ctx)]

    def _fused_task(self, ctx: "ExecContext") -> None:
        ctx.fused_result = fused_mod.run_fused(self, ctx)

    def finish_execute(self, ctx: "ExecContext"
                       ) -> dict[str, ops.DecodeResult]:
        """Join barrier: scatter group outputs back into per-column results,
        flush the device, return pooled arenas."""
        for name in ctx.plan.grouped_columns:
            if name in ctx.demoted or name in ctx.out:
                continue      # phase 3 may have assembled stage-A columns
            chunk = ctx.rg.column(name)
            field = self.meta.schema.field(name)
            ctx.out[name] = self._assemble_column(
                chunk, field, ctx.per_col_parts[name], ctx.payloads)
        if ctx.leases:
            # flush before returning arenas: a pooled buffer may be aliased
            # by in-flight device computation until results materialize
            tr = trace.active()
            t0 = time.perf_counter() if tr is not None else 0.0
            for res in ctx.out.values():
                if res.on_device:
                    res.array.block_until_ready()
            if tr is not None:
                tr.complete("device_wait", "device", t0,
                            time.perf_counter(), site="finalize")
            for buf in ctx.leases:
                self._arena_pool.give(buf)
        if ctx.fused_result is not None:
            # late columns were never materialized — deliver the stage-A
            # columns that exist plus the fused result under FUSED_KEY
            out = {name: ctx.out[name] for name in self.columns
                   if name in ctx.out}
            out[fused_mod.FUSED_KEY] = ctx.fused_result
            return out
        return {name: ctx.out[name] for name in self.columns}

    # -- fault recovery ------------------------------------------------------

    def evict_rg(self, rg_index: int) -> int:
        """Drop every shared-cache entry this planner could have populated
        for ``rg_index`` (decompress memo + dictionary cache); returns the
        eviction count.  The ScanService calls this before retrying a row
        group whose decode failed — and for every delivered row group of a
        permanently failed scan — so bytes derived from a bad read can
        never be served to a later scan (checksum verification makes
        poisoning impossible when ON; eviction keeps the invariant even
        with verification off or for non-checksum failures)."""
        rg = self.meta.row_groups[rg_index]
        n = 0
        memo = chunk_decompress_memo()
        for name in self.columns:
            chunk = rg.column(name)
            key = self._memo_key(chunk, name)
            if key is not None and memo.pop(key) is not None:
                n += 1
            if chunk.dict_page is not None:
                dp_off = chunk.dict_page.offset
                n += dict_decode.dict_cache_evict(
                    lambda k, o=dp_off, nm=name: (k[0] == self.cache_token
                                                  and k[1] == nm
                                                  and k[2] == o))
        return n

    def evict_file(self) -> int:
        """Drop every shared-cache entry keyed by this planner's file
        token (all row groups, all columns)."""
        token = self.cache_token
        memo = chunk_decompress_memo()
        n = memo.pop_matching(lambda k: k and k[0] == token)
        n += dict_decode.dict_cache_evict(lambda k: k and k[0] == token)
        return n

    # -- stages ------------------------------------------------------------

    def _memo_key(self, chunk, name: str) -> tuple | None:
        """Memo key for host-decompressed chunks (gzip on either backend,
        cascade on the host backend); None → not memoizable."""
        codec = Codec(chunk.codec)
        if codec == Codec.GZIP or (codec == Codec.CASCADE
                                   and self.backend != "pallas"):
            return (self.cache_token, name, chunk.byte_range)
        return None

    @staticmethod
    def _inflate_chunk_entry(chunk, raw) -> dict[object, object]:
        """Decompress every page of one chunk into the memo entry format:
        {page_index: payload, "dict": dictionary payload} — the shape both
        the grouped decompress stage and ops.decode_chunk consume.

        Every page's stored bytes are CRC-verified *here*, before the
        entry is built — the caller inserts the result into the shared
        decompress memo, so this is the cache-poisoning gate: corrupt
        bytes raise ChecksumError and nothing reaches the memo."""
        codec = Codec(chunk.codec)
        off0, _ = chunk.byte_range
        entry: dict[object, object] = {}
        if chunk.dict_page is not None:
            dp = chunk.dict_page
            data = raw[dp.offset - off0:dp.offset - off0 + dp.stored_size]
            verify_page(data, dp, where=f"{chunk.name} dict@{dp.offset}")
            entry["dict"] = decompress(data, codec, dp.uncompressed_size)
        for pi, pm in enumerate(chunk.pages):
            lo = pm.offset - off0
            data = raw[lo:lo + pm.stored_size]
            verify_page(data, pm, where=f"{chunk.name} page@{pm.offset}")
            entry[pi] = decompress(data, codec, pm.uncompressed_size)
        return entry

    def _fallback_payloads(self, chunk, name: str, raws
                           ) -> dict | None:
        """Pre-inflated page payloads for a fallback column, served from
        (and feeding) the chunk decompress memo — strings/float64 gzip
        chunks are exactly the host-decompress bottleneck the memo is
        for.  None → decode_chunk decompresses itself (NONE codec,
        device-cascade)."""
        memo_key = self._memo_key(chunk, name)
        if memo_key is None:
            return None
        memo = chunk_decompress_memo()
        hit = memo.get(memo_key)
        if hit is not None:
            return hit
        return memo.put(memo_key,
                        self._inflate_chunk_entry(chunk, raws[name]))

    def _demote_wide_delta(self, rg, slots: list[PageSlot], payloads
                           ) -> tuple[list[PageSlot], list[str]]:
        """Chunks whose min_delta exceeds int32 take the per-chunk path
        (mirrors the reference fallback, which is chunk-granular)."""
        bad: list[str] = []
        for s in slots:
            if s.column in bad:
                continue
            pm = rg.column(s.column).pages[s.page_index]
            man = self._manifest(rg, s, payloads)
            if abs(int(man["min_delta"].min(initial=0))) > ops._INT32_SAFE:
                bad.append(s.column)
        return [s for s in slots if s.column not in bad], bad

    def _payload_bytes(self, payloads, slot: PageSlot) -> bytes:
        p = payloads[(slot.column, slot.page_index)]
        if isinstance(p, tuple):
            raw, lo, size = p
            return raw[lo:lo + size]
        return p

    def _manifest(self, rg, slot: PageSlot, payloads) -> dict:
        key = (slot.column, slot.page_index, "man")
        man = payloads.get(key)
        if man is None:
            pm = rg.column(slot.column).pages[slot.page_index]
            man = build_delta_manifest(self._payload_bytes(payloads, slot),
                                       pm.n_values, pm.extra)
            payloads[key] = man
        return man

    # -- arena packing -----------------------------------------------------

    def _fill_arena(self, arena: np.ndarray, slots: Sequence[PageSlot],
                    payloads) -> None:
        """Pack page payload words into the preallocated uint32 arena.

        Uncompressed pages still sitting in the fetched row-group buffer are
        copied per *contiguous same-width run* (one reshape copy per run —
        for the common uniform-page chunk this is one copy per column, not
        one per page); materialized payloads copy row-by-row.
        """
        w = arena.shape[1]
        i, n = 0, len(slots)
        while i < n:
            p = payloads[(slots[i].column, slots[i].page_index)]
            if isinstance(p, tuple) and p[2] == w * 4:
                raw, lo, _ = p
                j = i + 1
                while j < n:
                    q = payloads[(slots[j].column, slots[j].page_index)]
                    if not (isinstance(q, tuple) and q[0] is raw
                            and q[2] == w * 4
                            and q[1] == lo + (j - i) * w * 4):
                        break
                    j += 1
                k = j - i
                arena[i:i + k] = np.frombuffer(
                    raw, dtype=np.uint32, count=k * w,
                    offset=lo).reshape(k, w)
                i = j
            else:
                data = self._payload_bytes(payloads, slots[i])
                words = np.frombuffer(data, dtype=np.uint32,
                                      count=len(data) // 4)
                arena[i, :words.shape[0]] = words
                i += 1

    # -- pallas group execution -------------------------------------------

    def _execute_group_pallas(self, group: DecodeGroup,
                              slots: list[PageSlot], rg, payloads,
                              per_col_parts, leases) -> None:
        """Pack the group's inputs on the host, ``stage`` them to the
        device, launch its kernel."""
        tr = trace.active()
        stage = functools.partial(
            _stage, tr, time.perf_counter() if tr is not None else 0.0)
        enc = group.encoding
        if enc == Encoding.RLE_DICTIONARY:
            batch = self._dict_group_pallas(group, slots, rg, payloads,
                                            leases, stage)
        elif enc == Encoding.DELTA_BINARY_PACKED:
            batch = self._delta_group_pallas(group, slots, rg, payloads,
                                             stage)
        elif enc == Encoding.RLE:
            batch = self._rle_group_pallas(group, slots, rg, payloads,
                                           stage)
        else:
            batch = self._bss_group_pallas(group, slots, rg, payloads,
                                           leases, stage)
        self._scatter_batch(batch, slots, per_col_parts)

    @staticmethod
    def _scatter_batch(batch, slots: list[PageSlot], per_col_parts) -> None:
        """Slice group output rows back to columns.  Consecutive pages of
        one column compact in a single segment (the uniform-page fast path
        of ops._compact), keyed by their page range for ordered reassembly."""
        i, n = 0, len(slots)
        while i < n:
            col, p0 = slots[i].column, slots[i].page_index
            j = i + 1
            while (j < n and slots[j].column == col
                   and slots[j].page_index == p0 + (j - i)):
                j += 1
            counts = [s.n_values for s in slots[i:j]]
            per_col_parts[col][(p0, slots[j - 1].page_index)] = \
                ops._compact(batch[i:j], counts)
            i = j

    def _dict_group_pallas(self, group, slots, rg, payloads, leases, stage):
        width = group.key[2]
        w_arena = max(
            -(-rg.column(s.column).pages[s.page_index].uncompressed_size
              // 4) for s in slots)
        arena, buf = self._arena_pool.take(
            (len(slots), max(w_arena, 1)), np.uint32)
        leases.append(buf)
        self._fill_arena(arena, slots, payloads)
        dicts: dict[str, dict_decode.CachedDictionary] = {}
        for s in slots:
            if s.column not in dicts:
                dicts[s.column] = self._device_dictionary(rg, s.column,
                                                          payloads)
        if len(dicts) == 1:   # single-column group: no dict duplication
            return ops.decode_dict_group_shared(
                *stage(arena, next(iter(dicts.values()))), width)
        d_max = max(d.host.shape[0] for d in dicts.values())
        dtype = next(iter(dicts.values())).host.dtype
        dict_arena, dbuf = self._arena_pool.take((len(slots), d_max), dtype)
        leases.append(dbuf)
        for row, s in enumerate(slots):
            d = dicts[s.column].host
            dict_arena[row, :d.shape[0]] = d
        return ops.decode_dict_group(*stage(arena, dict_arena), width)

    def _device_dictionary(self, rg, name: str, payloads
                           ) -> dict_decode.CachedDictionary:
        """Decoded dictionary for one column chunk, served from the
        cross-row-group cache (kernels/dict_decode.py) keyed by
        (file token, column, dict-page offset) — repeated scans skip both
        the host PLAIN-decode and the host→device staging."""
        chunk = rg.column(name)
        dp = chunk.dict_page
        # "device" variant: stored narrowed (int64→int32, bool→uint8);
        # distinct from the "host" variant of _host_dictionary
        key = (self.cache_token, name, dp.offset, "device")
        entry = dict_decode.dict_cache_get(key)
        if entry is not None:
            return entry
        field = self.meta.schema.field(name)
        dictionary = decode_plain_page(payloads[(name, "dict")], dp.n_values,
                                       field, dp.extra)
        if field.physical == PhysicalType.INT64:
            dictionary = dictionary.astype(np.int32)
        elif field.physical == PhysicalType.BOOLEAN:
            dictionary = dictionary.astype(np.uint8)
        return dict_decode.dict_cache_put(
            key, np.ascontiguousarray(dictionary))

    def _delta_group_pallas(self, group, slots, rg, payloads, stage):
        n_blocks = group.key[2]
        mans = [self._manifest(rg, s, payloads) for s in slots]
        pls = [self._payload_bytes(payloads, s) for s in slots]
        arrays = stage(*ops.delta_group_arrays(mans, pls, n_blocks))
        return ops.decode_delta_group(*arrays, n_blocks=n_blocks)

    def _rle_group_pallas(self, group, slots, rg, payloads, stage):
        n_out, vdt_name = group.key[2], group.key[3]
        vdt = np.dtype(vdt_name)
        runs = []
        for s in slots:
            pm = rg.column(s.column).pages[s.page_index]
            p = self._payload_bytes(payloads, s)
            r = pm.extra["n_runs"]
            runs.append((
                np.frombuffer(p, dtype=vdt, count=r).astype(np.int32),
                np.frombuffer(p, dtype=np.int32, count=r,
                              offset=r * vdt.itemsize)))
        vals, counts = stage(*ops.rle_group_arrays(runs))
        return ops.decode_rle_group(vals, counts, n_out=n_out)

    def _bss_group_pallas(self, group, slots, rg, payloads, leases, stage):
        stride = group.key[2]
        arena, buf = self._arena_pool.take((len(slots), 4 * stride),
                                           np.uint32)
        leases.append(buf)
        for row, s in enumerate(slots):
            pm = rg.column(s.column).pages[s.page_index]
            n = pm.n_values
            s_words = (n + (-n) % 4) // 4
            words = np.frombuffer(self._payload_bytes(payloads, s),
                                  dtype=np.uint32, count=4 * s_words)
            if s_words == stride:
                arena[row, :4 * stride] = words
            else:
                for plane in range(4):
                    arena[row, plane * stride:plane * stride + s_words] = \
                        words[plane * s_words:(plane + 1) * s_words]
        return ops.decode_bss_group(*stage(arena), stride)

    # -- host group execution ---------------------------------------------

    def _execute_group_host(self, group: DecodeGroup, slots: list[PageSlot],
                            rg, payloads, per_col_parts, leases) -> None:
        del leases  # host groups build exact-size numpy slabs, no arenas
        enc = group.encoding
        if enc == Encoding.RLE_DICTIONARY:
            self._dict_group_host(group, slots, rg, payloads, per_col_parts)
        elif enc == Encoding.DELTA_BINARY_PACKED:
            self._delta_group_host(slots, rg, payloads, per_col_parts)
        else:
            self._rle_group_host(group, slots, rg, payloads, per_col_parts)

    def _dict_group_host(self, group, slots, rg, payloads, per_col_parts):
        """One bitpack.unpack across every page of the group (all columns),
        then one dictionary gather per column — the per-page unpack overhead
        is what dominates host decode of many-page files."""
        width = group.key[2]
        words, g_offs, g_total = [], [], 0
        for s in slots:
            p = self._payload_bytes(payloads, s)
            w = np.frombuffer(p, dtype=np.uint32, count=len(p) // 4)
            words.append(w)
            g_offs.append(g_total)
            g_total += w.shape[0] // width
        slab = words[0] if len(words) == 1 else np.concatenate(words)
        codes = bitpack.unpack(slab, width, g_total * 32,
                               out_dtype=np.int64)
        for (s, goff) in zip(slots, g_offs):
            per_col_parts[s.column][(s.page_index, s.page_index)] = \
                codes[goff * 32:goff * 32 + s.n_values]

    def _delta_group_host(self, slots, rg, payloads, per_col_parts):
        """Manifest pass per page, then one gather+unpack per distinct
        miniblock width across the whole group; per-page cumsum assembles
        values (bit-identical to encodings.decode_delta_page)."""
        from repro.core.encodings import BLOCK, MB_GROUPS, MB_VALUES
        mans = [self._manifest(rg, s, payloads) for s in slots]
        base, total = [], 0
        for m in mans:
            base.append(total)
            total += m["words"].shape[0]
        slab = np.concatenate([m["words"] for m in mans]) if mans else \
            np.zeros(0, np.uint32)
        page_of, mb_widths, mb_offs = [], [], []
        for i, m in enumerate(mans):
            n_mb = m["n_blocks"] * 4
            page_of.append(np.full(n_mb, i, dtype=np.int64))
            mb_widths.append(m["mb_width"][:n_mb])
            mb_offs.append(m["mb_off"][:n_mb].astype(np.int64) + base[i])
        page_of = np.concatenate(page_of) if page_of else np.zeros(0, np.int64)
        mb_widths = np.concatenate(mb_widths) if mb_widths else \
            np.zeros(0, np.int64)
        mb_offs = np.concatenate(mb_offs) if mb_offs else np.zeros(0, np.int64)
        rel = np.zeros((max(page_of.shape[0], 1), MB_VALUES), dtype=np.uint64)
        for w in np.unique(mb_widths) if mb_widths.shape[0] else []:
            w = int(w)
            sel = np.flatnonzero(mb_widths == w)
            idx = mb_offs[sel][:, None] + np.arange(MB_GROUPS * w)[None, :]
            vals = bitpack.unpack(slab[idx].reshape(-1), w,
                                  sel.shape[0] * MB_VALUES)
            rel[sel] = vals.reshape(sel.shape[0], MB_VALUES)
        mb_of_page = np.concatenate([[0], np.cumsum(
            [m["n_blocks"] * 4 for m in mans])]).astype(np.int64)
        for i, (s, m) in enumerate(zip(slots, mans)):
            field = self.meta.schema.field(s.column)
            n = s.n_values
            n_blocks = m["n_blocks"]
            deltas = rel[mb_of_page[i]:mb_of_page[i + 1]].reshape(-1)[
                :n_blocks * BLOCK].astype(np.int64)
            deltas += np.repeat(m["min_delta"][:n_blocks], BLOCK)
            out = np.empty(n, dtype=np.int64)
            if n:
                out[0] = m["first_value"]
                if n > 1:
                    np.cumsum(deltas[:n - 1], out=out[1:])
                    out[1:] += m["first_value"]
            per_col_parts[s.column][(s.page_index, s.page_index)] = \
                out.astype(field.numpy_dtype)

    def _rle_group_host(self, group, slots, rg, payloads, per_col_parts):
        vdt = np.dtype(group.key[2])
        for s in slots:
            pm = rg.column(s.column).pages[s.page_index]
            field = self.meta.schema.field(s.column)
            p = self._payload_bytes(payloads, s)
            r = pm.extra["n_runs"]
            if r == 0:
                dt = (np.bool_ if field.physical == PhysicalType.BOOLEAN
                      else field.numpy_dtype)
                per_col_parts[s.column][(s.page_index, s.page_index)] = \
                    np.zeros(0, dtype=dt)
                continue
            vals = np.frombuffer(p, dtype=vdt, count=r)
            counts = np.frombuffer(p, dtype=np.int32, count=r,
                                   offset=r * vdt.itemsize)
            out = np.repeat(vals, counts)
            if field.physical == PhysicalType.BOOLEAN:
                out = out.astype(np.bool_)
            else:
                out = out.astype(field.numpy_dtype)
            per_col_parts[s.column][(s.page_index, s.page_index)] = out

    # -- scatter -----------------------------------------------------------

    def _assemble_column(self, chunk: ChunkMeta, field: Field,
                         parts: dict[tuple, object],
                         payloads) -> ops.DecodeResult:
        import jax.numpy as jnp
        ordered = [parts[k] for k in sorted(parts)]  # keys: page ranges
        on_device = self.backend == "pallas"
        if on_device:
            arr = ordered[0] if len(ordered) == 1 else jnp.concatenate(ordered)
            if (Encoding(chunk.encoding) == Encoding.RLE
                    and field.physical == PhysicalType.BOOLEAN):
                arr = arr.astype(jnp.uint8)
            if field.physical == PhysicalType.INT64:
                ops.count_int64_narrowed()
            logical = int(arr.dtype.itemsize) * chunk.n_values
        else:
            arr = ordered[0] if len(ordered) == 1 else np.concatenate(ordered)
            if Encoding(chunk.encoding) == Encoding.RLE_DICTIONARY:
                arr = self._host_dictionary(chunk, field, payloads)[arr]
            logical = int(np.dtype(field.numpy_dtype or np.int64).itemsize
                          * chunk.n_values)
        return ops.DecodeResult(
            array=arr, on_device=on_device, n_values=chunk.n_values,
            encoding=int(chunk.encoding), codec=int(chunk.codec),
            stored_bytes=chunk.stored_bytes, logical_bytes=int(logical))

    def _host_dictionary(self, chunk: ChunkMeta, field: Field, payloads):
        dp = chunk.dict_page
        key = (self.cache_token, chunk.name, dp.offset, "host")
        entry = dict_decode.dict_cache_get(key)
        if entry is None:
            raw = payloads[(chunk.name, "dict")]
            entry = dict_decode.dict_cache_put(
                key, decode_plain_page(raw, dp.n_values, field, dp.extra))
        return entry.host


# ---------------------------------------------------------------------------
# planner cache (per file footer + column selection + backend)
# ---------------------------------------------------------------------------

_PLANNER_CACHE: "OrderedDict[tuple, DecodePlanner]" = OrderedDict()
_PLANNER_CACHE_MAX = 64


def _stage(tr, t0: float, *packed) -> tuple:
    """Host→device staging of one group's packed inputs: arrays by
    transfer, cached dictionaries through the cache (staged once).  With
    the recorder on, the packing since ``t0`` is a ``pack`` span and the
    transfer a ``stage`` span, each with the bytes it covers."""
    if tr is None:
        return tuple(_on_device(a) for a in packed)
    t1 = time.perf_counter()
    tr.complete("pack", "decode", t0, t1,
                bytes=sum(a.nbytes for a in packed))
    moved = sum(a.nbytes for a in packed
                if not getattr(a, "on_device", False))
    out = tuple(_on_device(a) for a in packed)
    tr.complete("stage", "decode", t1, time.perf_counter(), bytes=moved)
    return out


def _on_device(a):
    if isinstance(a, dict_decode.CachedDictionary):
        return a.device
    return jnp.asarray(a)


def planner_for(path: str, meta: FileMeta, columns: Sequence[str],
                backend: str,
                fused_spec: "fused_mod.FusedSpec | None" = None
                ) -> DecodePlanner:
    # st_size + st_mtime_ns catch same-path rewrites whose footers would
    # otherwise collide (same rows / row groups / stored bytes) — a stale
    # plan would decode with the old file's page offsets.
    try:
        st = os.stat(path)
        stamp = (st.st_size, st.st_mtime_ns)
    except OSError:
        stamp = ()
    key = (path, tuple(columns), backend, meta.num_rows,
           len(meta.row_groups), meta.stored_bytes, stamp, fused_spec)
    planner = _PLANNER_CACHE.get(key)
    if planner is not None:
        _PLANNER_CACHE.move_to_end(key)
        return planner
    # cache_token omits the column selection: scanners over different
    # column subsets of one file share dictionary/decompress cache entries
    planner = DecodePlanner(meta, columns, backend,
                            cache_token=(path, stamp, meta.stored_bytes),
                            fused_spec=fused_spec)
    _PLANNER_CACHE[key] = planner
    while len(_PLANNER_CACHE) > _PLANNER_CACHE_MAX:
        _PLANNER_CACHE.popitem(last=False)
    return planner


def clear_planner_cache() -> None:
    _PLANNER_CACHE.clear()
