"""Query operators over scans: TPC-H Q6 (filter+agg) and Q12 (join).

These are the paper's §4 query-level validation workloads.  Both consume
row groups streamed by the overlap executor, so file-level configuration
gains translate to query runtime exactly as in Fig. 5.

Both also accept a **Dataset** (repro.dataset) in place of a Scanner:
the scan is then planned over the manifest (partition + file-level
zone-map pruning with the same stats contract the row-group pruner
uses) and executed as sharded fragment scans through the shared
ScanService — the "data-lake" path where file pruning and cooperative
multi-scan scheduling compound with the paper's single-file config
gains.  Per-fragment partial results reduce in plan order, so pruned
and unpruned runs are bit-identical.

Dates are int32 days since 1992-01-01 (DATE logical type).
"""

from __future__ import annotations

import dataclasses
import decimal
import functools
import math
import operator
import os
import threading
import time
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace as trace_mod
from repro.core.fused import (FUSED_KEY, Compare, FusedSpec, Interval,
                              SumProduct)
from repro.core.overlap import RunReport, run_blocking, run_overlapped
from repro.core.scan import Scanner
from repro.core.schema import LogicalType
from repro.kernels.filter_agg import TILE, filter_agg_q6

D_1994_01_01 = 731
D_1995_01_01 = 1096

Q6_COLUMNS = ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]
Q12_LINEITEM_COLUMNS = ["l_orderkey", "l_shipmode", "l_shipdate",
                        "l_commitdate", "l_receiptdate"]
Q12_ORDERS_COLUMNS = ["o_orderkey", "o_orderpriority"]


def _on_device(x):
    """A decoded column as the operand of a jitted reduce or probe.  A
    ``jax.Array`` is used where it is, with no copy and no wait: the call
    that reads it orders itself after the kernel that made it, and JAX
    moves an uncommitted array to the device the call runs on.  A host
    array (numpy backend, host-fallback decode) is uploaded once; with
    the recorder on, that upload is a ``to_device`` span with ``bytes``.
    Nothing here writes into or donates the array, so a result-window
    entry is read in place."""
    if isinstance(x, jax.Array):
        return x
    tr = trace_mod.active()
    if tr is None:
        return jnp.asarray(x)
    host = np.asarray(x)
    t0 = time.perf_counter()
    out = jnp.asarray(host)
    tr.complete("to_device", "consume", t0, time.perf_counter(),
                bytes=host.nbytes)
    return out


def _to_host(convert, x, site: str):
    """``convert(x)`` for a small device result; with the recorder on, the
    call is a ``device_wait`` span naming the site, as its copy is a few
    bytes and the rest is the wait."""
    tr = trace_mod.active()
    if tr is None:
        return convert(x)
    t0 = time.perf_counter()
    out = convert(x)
    tr.complete("device_wait", "device", t0, time.perf_counter(), site=site)
    return out


def _is_dataset(source) -> bool:
    """Duck-typed Dataset check (no repro.dataset import on the scan-only
    path): a manifest-backed source exposes fragments + partitioning."""
    return hasattr(source, "fragments") and hasattr(source, "partitioning")


def _resolve_fused(fused: "bool | str | None") -> "bool | str":
    """``fused=`` resolution shared by q6/q12: None defers to the
    ``REPRO_FUSED`` env (the CI matrix leg), "reference" selects the
    unfused bit-identity twin (full materialization, canonical reduce)."""
    if fused is None:
        return os.environ.get("REPRO_FUSED", "0") == "1"
    return fused


# ---------------------------------------------------------------------------
# Q6 — SELECT sum(l_extendedprice*l_discount) WHERE shipdate in FY1994
#       AND discount BETWEEN 0.05 AND 0.07 AND quantity < 24
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=())
def _q6_jnp(ship, disc, qty, price):
    mask = ((ship >= D_1994_01_01) & (ship < D_1995_01_01)
            & (disc >= jnp.float32(0.05)) & (disc <= jnp.float32(0.07))
            & (qty < jnp.float32(24.0)))
    return jnp.sum(jnp.where(mask, price * disc, jnp.float32(0)))


def q6_rg_stats_predicate(name: str, stats: dict) -> bool:
    """Zone-map pruning: skip row groups whose shipdate range misses FY94."""
    if name == "l_shipdate":
        return stats["min"] < D_1995_01_01 and stats["max"] >= D_1994_01_01
    return True


def q6_fused_spec(mode: str = "fused") -> FusedSpec:
    """Q6 as a FusedSpec: shipdate interval stays in stage A (DELTA-coded
    → not kernel-fusable), discount/quantity intervals and the
    price×discount aggregate fuse into one stage-B launch per row group
    (constants cast to float32 in-kernel — same bits as ``_q6_jnp``)."""
    return FusedSpec(
        predicates=(Interval("l_shipdate", lo=D_1994_01_01,
                             hi=D_1995_01_01),
                    Interval("l_discount", lo=0.05, hi=0.07, hi_incl=True),
                    Interval("l_quantity", hi=24.0)),
        agg=SumProduct("l_extendedprice", "l_discount"),
        mode=mode)


_legacy_fused_consumes = 0
_legacy_lock = threading.Lock()


def legacy_fused_consume_count() -> int:
    """Row groups a fused Q6 served through the unfused consume (process
    total) — nonzero means fused was asked for and did not happen."""
    return _legacy_fused_consumes


def _q6_consume_fused(use_kernel: bool):
    """Sums the canonical per-RG fused partials in plan order.  Falls back
    to the legacy consume when a row group arrives without a fused result
    (use_plan=False scanners, instance-patched decode paths), and counts
    each such row group."""
    legacy = _q6_consume(use_kernel)

    def consume(acc, rg_index, cols):
        global _legacy_fused_consumes
        res = cols.get(FUSED_KEY)
        if res is None:
            with _legacy_lock:
                _legacy_fused_consumes += 1
            return legacy(acc, rg_index, cols)
        return res.partial if acc is None else acc + res.partial

    return consume


def _q6_consume(use_kernel: bool):
    def consume(acc, rg_index, cols):
        ship = _on_device(cols["l_shipdate"].array).astype(jnp.int32)
        disc = _on_device(cols["l_discount"].array).astype(jnp.float32)
        qty = _on_device(cols["l_quantity"].array).astype(jnp.float32)
        price = _on_device(cols["l_extendedprice"].array).astype(jnp.float32)
        if use_kernel:
            n = ship.shape[0]
            pad = (-n) % TILE
            if pad:
                ship = jnp.pad(ship, (0, pad),
                               constant_values=np.iinfo(np.int32).max)
                disc = jnp.pad(disc, (0, pad))
                qty = jnp.pad(qty, (0, pad))
                price = jnp.pad(price, (0, pad))
            part = filter_agg_q6(ship, qty, disc, price,
                                 lo=D_1994_01_01, hi=D_1995_01_01,
                                 dlo=0.05, dhi=0.07, qmax=24.0)
        else:
            part = _q6_jnp(ship, disc, qty, price)
        part = _to_host(float, part, "q6_partial")
        return part if acc is None else acc + part

    return consume


# ---------------------------------------------------------------------------
# Q6 over DECIMAL operands: exact, in scaled integers.  The decode narrows
# an INT64 chunk whose stats fit to int32 on the device; predicate, where
# and price × discount stay int32, and the sum is exact past 2**31 by
# 16-bit limbs summed per block of rows, combined on the host in Python
# ints.  No float lies between the decoded cents and the answer.
# ---------------------------------------------------------------------------

#: rows per block sum: 2**14 limbs of 16 bits sum below 2**30
EXACT_BLOCK = 1 << 14
_INT32 = np.iinfo(np.int32)


@dataclasses.dataclass(frozen=True)
class DecimalQ6:
    """Q6's constants at the operands' DECIMAL scales: discount in
    [``disc_lo``, ``disc_hi``] and quantity below ``qty_max``, all in
    stored units; the answer is in units of 10**-``scale``."""
    disc_lo: int
    disc_hi: int
    qty_max: int
    scale: int

    def answer(self, units: int) -> decimal.Decimal:
        return decimal.Decimal(f"{units}E-{self.scale}")


def q6_decimal(schema) -> DecimalQ6 | None:
    """The exact plan of Q6 when the schema gives its discount, quantity
    and price as DECIMAL; None when none of them is (the float path)."""
    fields = [schema.field(c) for c in
              ("l_discount", "l_quantity", "l_extendedprice")]
    dec = [f.logical == LogicalType.DECIMAL for f in fields]
    if not any(dec):
        return None
    if not all(dec):
        raise ValueError("Q6 needs l_discount, l_quantity and "
                         "l_extendedprice all DECIMAL or none of them")
    sd, sq, sp = (f.decimal_scale for f in fields)
    return DecimalQ6(disc_lo=math.ceil(Fraction(5, 100) * 10**sd),
                     disc_hi=math.floor(Fraction(7, 100) * 10**sd),
                     qty_max=24 * 10**sq, scale=sd + sp)


def _check_products_fit(dec: DecimalQ6, price_stats, where: str) -> None:
    """A qualifying row's price × discount is at most max|price| ×
    max(|disc_lo|, |disc_hi|); raise unless the chunk stats prove that
    below 2**31, so no int32 product overflows (a row the predicate drops
    may wrap: ``where`` discards it)."""
    if not price_stats or not isinstance(price_stats.get("max"), int):
        raise ValueError(f"l_extendedprice has no integer min/max stats in "
                         f"{where}: an exact Q6 cannot bound its products")
    bound = (max(abs(price_stats["min"]), abs(price_stats["max"]))
             * max(abs(dec.disc_lo), abs(dec.disc_hi)))
    if bound > _INT32.max:
        raise ValueError(f"l_extendedprice × l_discount reaches {bound} in "
                         f"{where}, past int32: an exact Q6 would overflow")


def _exact_operand(res, name: str):
    """A decoded DECIMAL/DATE column as an int32 device array: a device
    column is already int32 (narrowed by the decode); a host int64 column
    is narrowed here, or refused when a value does not fit."""
    x = res.array
    if isinstance(x, jax.Array):
        return x
    host = np.asarray(x)
    if host.dtype != np.int32:
        if host.size and (host.min() < _INT32.min or host.max() > _INT32.max):
            raise ValueError(f"{name} holds values past int32: an exact Q6 "
                             "cannot take them")
        host = host.astype(np.int32)
    return _on_device(host)


@functools.partial(jax.jit,
                   static_argnames=("disc_lo", "disc_hi", "qty_max"))
def _q6_exact_blocks(ship, disc, qty, price, *, disc_lo, disc_hi, qty_max):
    """(2, n_blocks) int32: per block of ``EXACT_BLOCK`` rows, the sums of
    the low and the high 16 bits of each qualifying price × discount."""
    mask = ((ship >= D_1994_01_01) & (ship < D_1995_01_01)
            & (disc >= disc_lo) & (disc <= disc_hi) & (qty < qty_max))
    prod = jnp.where(mask, price * disc, 0)
    prod = jnp.pad(prod, (0, (-prod.shape[0]) % EXACT_BLOCK))
    prod = prod.reshape(-1, EXACT_BLOCK)
    return jnp.stack([jnp.sum(prod & 0xFFFF, axis=1),
                      jnp.sum(prod >> 16, axis=1)])


def _q6_exact_consume(dec: DecimalQ6):
    """Each row group's exact partial, a Python int in units of
    10**-``dec.scale``: the block sums come to the host and combine
    there.  With the recorder on, the whole reduce is an ``exact_sum``
    span with ``rows`` and ``blocks``."""

    def consume(acc, rg_index, cols):
        t0 = time.perf_counter()
        ship, disc, qty, price = (_exact_operand(cols[c], c)
                                  for c in Q6_COLUMNS)
        blocks = _q6_exact_blocks(ship, disc, qty, price,
                                  disc_lo=dec.disc_lo, disc_hi=dec.disc_hi,
                                  qty_max=dec.qty_max)
        sums = _to_host(np.asarray, blocks, "q6_blocks").astype(np.int64)
        part = int(sums[0].sum()) + (int(sums[1].sum()) << 16)
        tr = trace_mod.active()
        if tr is not None:
            tr.complete("exact_sum", "consume", t0, time.perf_counter(),
                        rows=int(ship.shape[0]), blocks=int(sums.shape[1]))
        return part if acc is None else acc + part

    return consume


def _schema_of(source):
    """The file schema of a scanner, or of a dataset's first fragment
    (None for a dataset with no fragments)."""
    if not _is_dataset(source):
        return source.meta.schema
    if not source.fragments:
        return None
    from repro.core.reader import read_footer
    return read_footer(source.fragment_path(source.fragments[0])).schema


def q6(scanner: Scanner, overlapped: bool = True, use_kernel: bool = False,
       prune: bool = True, prepare_plan: bool = False, depth: int = 2,
       decode_workers: int | None = None, service=None,
       window: int = 4, open_opts: dict | None = None,
       fused: "bool | str | None" = None, devices=None,
       trace=None, tenant: str | None = None,
       result_cache=None) -> tuple[float, RunReport]:
    """Run Q6 over the scanner's stream — or over a whole **Dataset**
    (file-level pruning + sharded fragment scans; returns a
    ``DatasetRunReport``).  ``prepare_plan`` pre-builds the row-group
    decode plans before timing starts (the serving-loop case — plans are
    cached per file footer + column selection, so repeated queries always
    hit).  ``depth``/``decode_workers`` shape the pipelined executor
    (overlap.py); ``service`` selects a specific ScanService instead of
    the shared one; all three are ignored for blocking runs.
    ``window``/``open_opts`` apply to dataset runs only (fragment
    concurrency bound; ``Dataset.open_fragment`` storage options);
    dataset runs are always sharded (``overlapped=False`` raises) and
    ``prepare_plan`` is a no-op for them (per-fragment decode plans are
    cached on first scan).  ``fused`` selects late materialization
    (``True``/``"reference"``; ``None`` defers to ``REPRO_FUSED``):
    the decode plan stages predicate columns first and runs the
    filter+aggregate inside the scan (core/fused.py).  ``devices``
    (dataset runs only) routes fragments through the multi-device
    executor (``run_distributed_scan``): None keeps the windowed
    single-service path; an int or device list shards fragments across
    devices with the deterministic tree reduce — bit-identical across
    device counts.  ``trace`` enables the flight recorder for this run
    (core/trace.py, DESIGN.md §10): True records, a path string records
    and exports Chrome trace JSON.  ``tenant`` attributes the scan(s) to
    a ScanService tenant (weighted fair scheduling + admission,
    DESIGN.md §11); ``result_cache`` (dataset runs only) is a
    FragmentResultCache — repeated identical Q6 runs answer unchanged
    fragments from cached partials, invalidated on manifest swap.

    Where the schema gives discount, quantity and price as DECIMAL the
    answer is an exact ``decimal.Decimal`` (``q6_decimal``): the row
    groups run the scaled-integer consume whatever ``fused`` says (no
    FusedSpec is attached: the fused aggregate is float32), and
    ``use_kernel=True`` raises.  Otherwise it is a float, as before."""
    fused = _resolve_fused(fused)
    schema = _schema_of(scanner)
    dec = q6_decimal(schema) if schema is not None else None
    if dec is None:
        spec = q6_fused_spec("reference" if fused == "reference"
                             else "fused") if fused else None
        consume = (_q6_consume_fused(use_kernel) if spec is not None
                   else _q6_consume(use_kernel))
    else:
        if use_kernel:
            raise ValueError("use_kernel=True sums in float32 "
                             "(kernels/filter_agg.py); Q6 over DECIMAL "
                             "operands is served exactly without it")
        reg = trace_mod.registry()
        reg.counter_inc("decimal_exact_queries")
        if (fused or (open_opts or {}).get("fused_spec") is not None
                or getattr(scanner, "fused_spec", None) is not None):
            reg.counter_inc("fused_declined_decimal")
        spec, consume = None, _q6_exact_consume(dec)

    def answer(acc):
        return (acc or 0.0) if dec is None else dec.answer(acc or 0)

    if _is_dataset(scanner):
        if not overlapped:
            raise ValueError("dataset runs are always sharded/overlapped; "
                             "open a fragment Scanner for a blocking run")
        from repro.dataset.executor import run_dataset_scan
        from repro.dataset.planner import plan_dataset_scan
        plan = plan_dataset_scan(
            scanner, columns=list(Q6_COLUMNS),
            predicate_stats=q6_rg_stats_predicate if prune else None)
        if spec is not None:
            open_opts = dict(open_opts or {}, fused_spec=spec)
        if dec is not None:
            open_opts = {k: v for k, v in (open_opts or {}).items()
                         if k != "fused_spec"}
            for frag in plan.fragments:
                _check_products_fit(
                    dec, frag.column_stats.get("l_extendedprice"),
                    frag.path)
        if devices is not None:
            from repro.dataset.executor import run_distributed_scan
            acc, report = run_distributed_scan(
                plan, consume, lambda a, b: a + b,
                devices=devices, depth=depth,
                decode_workers=decode_workers, open_opts=open_opts,
                trace=trace)
            return answer(acc), report
        fp = (f"q6:exact:p{int(prune)}" if dec is not None else
              f"q6:{'fused' if spec is not None else 'unfused'}:"
              f"{'ref' if fused == 'reference' else 'opt'}:"
              f"k{int(use_kernel)}:p{int(prune)}")
        acc, report = run_dataset_scan(
            plan, consume, lambda a, b: a + b,
            window=window, depth=depth, decode_workers=decode_workers,
            service=service, open_opts=open_opts, trace=trace,
            tenant=tenant, result_cache=result_cache, fingerprint=fp)
        return answer(acc), report
    if spec is not None and scanner.planner is not None \
            and scanner.fused_spec != spec:
        scanner.enable_fused(spec)
    if dec is not None:
        if scanner.fused_spec is not None:
            scanner.enable_fused(None)
        for i in scanner.plan(q6_rg_stats_predicate if prune else None):
            _check_products_fit(
                dec, scanner.meta.row_groups[i].column(
                    "l_extendedprice").stats,
                f"{scanner.path} row group {i}")
    if prepare_plan:
        scanner.prepare_plans(
            predicate_stats=q6_rg_stats_predicate if prune else None)
    if overlapped:
        runner = functools.partial(run_overlapped, depth=depth,
                                   decode_workers=decode_workers,
                                   service=service, tenant=tenant)
    else:
        runner = run_blocking
    acc, report = runner(scanner, consume,
                         predicate_stats=(q6_rg_stats_predicate
                                          if prune else None),
                         trace=trace)
    return answer(acc), report


def q6_reference(tables: dict[str, np.ndarray]) -> float:
    """Numpy oracle over raw columns."""
    ship, disc = tables["l_shipdate"], tables["l_discount"]
    qty, price = tables["l_quantity"], tables["l_extendedprice"]
    m = ((ship >= D_1994_01_01) & (ship < D_1995_01_01)
         & (disc >= np.float32(0.05)) & (disc <= np.float32(0.07))
         & (qty < 24))
    return float(np.sum(price[m].astype(np.float64)
                        * disc[m].astype(np.float64)))


def q6_decimal_reference(tables: dict[str, np.ndarray]) -> decimal.Decimal:
    """Numpy oracle over DECIMAL(15,2) cents (int64): discount 5 to 7
    cents, quantity under 2,400 cents; the products summed as Python
    ints, the answer exact at scale 4."""
    ship, disc = tables["l_shipdate"], tables["l_discount"]
    qty, price = tables["l_quantity"], tables["l_extendedprice"]
    m = ((ship >= D_1994_01_01) & (ship < D_1995_01_01)
         & (disc >= 5) & (disc <= 7) & (qty < 2400))
    units = sum(map(operator.mul, price[m].tolist(), disc[m].tolist()))
    return decimal.Decimal(units) / 10**4


# ---------------------------------------------------------------------------
# Q12 — lineitem ⋈ orders on orderkey; counts per shipmode split by
#        order priority (urgent/high vs other); FY1994 receipt dates
# ---------------------------------------------------------------------------

SHIPMODE_MAIL = 2
SHIPMODE_SHIP = 4


@jax.jit
def _q12_probe(skeys, sprio, okey, mode, ship, commit, receipt):
    mask = (((mode == SHIPMODE_MAIL) | (mode == SHIPMODE_SHIP))
            & (commit < receipt) & (ship < commit)
            & (receipt >= D_1994_01_01) & (receipt < D_1995_01_01))
    pos = jnp.clip(jnp.searchsorted(skeys, okey), 0, skeys.shape[0] - 1)
    hit = skeys[pos] == okey
    prio = sprio[pos]
    urgent = (prio <= 1) & hit & mask        # 1-URGENT / 2-HIGH
    other = (prio > 1) & hit & mask
    out = []
    for m in (SHIPMODE_MAIL, SHIPMODE_SHIP):
        sel = mode == m
        out.append(jnp.sum((urgent & sel).astype(jnp.int32)))
        out.append(jnp.sum((other & sel).astype(jnp.int32)))
    return jnp.stack(out)


def q12_fused_spec(mode: str = "fused") -> FusedSpec:
    """Q12's probe side as a selection-mode FusedSpec: every predicate and
    compare column evaluates in stage A, and the emit-only ``l_orderkey``
    is materialized late — only for row groups where any row survives the
    receipt-window + shipmode + date-ordering filter."""
    return FusedSpec(
        predicates=(Interval("l_receiptdate", lo=D_1994_01_01,
                             hi=D_1995_01_01),
                    Interval("l_shipmode",
                             in_set=(SHIPMODE_MAIL, SHIPMODE_SHIP))),
        compares=(Compare("l_commitdate", "l_receiptdate"),
                  Compare("l_shipdate", "l_commitdate")),
        emit=("l_orderkey", "l_shipmode"),
        mode=mode)


@jax.jit
def _q12_probe_selected(skeys, sprio, okey, mode):
    """Join probe over pre-selected rows (the fused path's selection
    vector already applied).  Padding rows carry okey=-1 (no order key
    matches) and mode=0 (neither shipmode), so they count nothing."""
    pos = jnp.clip(jnp.searchsorted(skeys, okey), 0, skeys.shape[0] - 1)
    hit = skeys[pos] == okey
    prio = sprio[pos]
    urgent = (prio <= 1) & hit
    other = (prio > 1) & hit
    out = []
    for m in (SHIPMODE_MAIL, SHIPMODE_SHIP):
        sel = mode == m
        out.append(jnp.sum((urgent & sel).astype(jnp.int32)))
        out.append(jnp.sum((other & sel).astype(jnp.int32)))
    return jnp.stack(out)


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def _source_digest(src) -> "str | None":
    """Content identity of a q12 side for result-cache fingerprints: a
    dataset's (root, generation), a file scanner's planner cache token
    (path + size + mtime); None → unknown, never cache against it."""
    if _is_dataset(src):
        return f"ds:{src.root}:g{src.generation}"
    tok = getattr(getattr(src, "planner", None), "cache_token", None)
    return None if tok is None else f"file:{tok}"


def q12(lineitem_scanner: Scanner, orders_scanner: Scanner,
        overlapped: bool = True, prepare_plan: bool = False,
        depth: int = 2, decode_workers: int | None = None,
        service=None, window: int = 4, open_opts: dict | None = None,
        fused: "bool | str | None" = None, devices=None,
        trace=None, tenant: str | None = None,
        result_cache=None) -> tuple[dict[str, int], RunReport, RunReport]:
    """Q12 over scanners — or over Datasets (either side independently):
    the build side streams every orders fragment, the probe side shards
    lineitem fragments through the ScanService, and per-fragment counts
    reduce in plan order.  Dataset sides are always sharded
    (``overlapped=False`` raises) and skip ``prepare_plan``.  ``fused``
    (``True``/``"reference"``/``None``→``REPRO_FUSED``) runs the probe
    side with late materialization: ``l_orderkey`` only materializes for
    row groups with surviving rows (core/fused.py).  ``devices`` routes
    dataset sides through ``run_distributed_scan`` (multi-device
    sharding + deterministic tree reduce).  ``trace`` records both the
    build and probe scans in one flight-recorder session (DESIGN.md
    §10); a path string also exports Chrome trace JSON on return.
    ``tenant``/``result_cache`` are the serving hooks (DESIGN.md §11):
    tenant attribution on every scan, and fragment-partial caching on
    dataset sides — the probe side's fingerprint carries the orders
    side's content identity, so a build-table change invalidates it."""
    if trace:
        with trace_mod.request(trace):
            return q12(lineitem_scanner, orders_scanner,
                       overlapped=overlapped, prepare_plan=prepare_plan,
                       depth=depth, decode_workers=decode_workers,
                       service=service, window=window,
                       open_opts=open_opts, fused=fused, devices=devices,
                       tenant=tenant, result_cache=result_cache)
    if not overlapped and (_is_dataset(lineitem_scanner)
                           or _is_dataset(orders_scanner)):
        raise ValueError("dataset runs are always sharded/overlapped; "
                         "open fragment Scanners for a blocking run")
    fused = _resolve_fused(fused)
    lspec = q12_fused_spec("reference" if fused == "reference"
                           else "fused") if fused else None
    if lspec is not None and not _is_dataset(lineitem_scanner) \
            and lineitem_scanner.planner is not None \
            and lineitem_scanner.fused_spec != lspec:
        lineitem_scanner.enable_fused(lspec)
    if prepare_plan and not _is_dataset(lineitem_scanner):
        lineitem_scanner.prepare_plans()
    if prepare_plan and not _is_dataset(orders_scanner):
        orders_scanner.prepare_plans()
    # Build side: stream orders, then sort once on device.
    def build_consume(acc, rg_index, cols):
        k = _on_device(cols["o_orderkey"].array).astype(jnp.int32)
        p = _on_device(cols["o_orderpriority"].array).astype(jnp.int32)
        return (k, p) if acc is None else (jnp.concatenate([acc[0], k]),
                                           jnp.concatenate([acc[1], p]))

    if overlapped:
        runner = functools.partial(run_overlapped, depth=depth,
                                   decode_workers=decode_workers,
                                   service=service, tenant=tenant)
    else:
        runner = run_blocking

    if _is_dataset(orders_scanner):
        from repro.dataset.executor import run_dataset_scan
        from repro.dataset.planner import plan_dataset_scan
        oplan = plan_dataset_scan(orders_scanner,
                                  columns=list(Q12_ORDERS_COLUMNS))
        build_combine = (lambda a, b: (jnp.concatenate([a[0], b[0]]),
                                       jnp.concatenate([a[1], b[1]])))
        if devices is not None:
            # concatenation is exactly associative, so the tree pairing
            # yields the same build table the left fold would
            from repro.dataset.executor import run_distributed_scan
            (keys, prio), build_report = run_distributed_scan(
                oplan, build_consume, build_combine,
                devices=devices, depth=depth,
                decode_workers=decode_workers, open_opts=open_opts)
        else:
            (keys, prio), build_report = run_dataset_scan(
                oplan, build_consume, build_combine,
                window=window, depth=depth, decode_workers=decode_workers,
                service=service, open_opts=open_opts, tenant=tenant,
                result_cache=result_cache, fingerprint="q12:build")
    else:
        (keys, prio), build_report = runner(orders_scanner, build_consume)
    order = jnp.argsort(keys)
    skeys, sprio = keys[order], prio[order]

    def probe_consume(acc, rg_index, cols):
        fres = cols.get(FUSED_KEY) if lspec is not None else None
        if fres is not None:
            # fused path: the selection already applied every predicate —
            # probe only the surviving (okey, shipmode) pairs, padded to a
            # pow2 (okey=-1 / mode=0 rows count nothing)
            okey = fres.gathered["l_orderkey"]
            shipmode = fres.gathered["l_shipmode"]
            n = int(okey.shape[0])
            if n == 0:
                part = jnp.zeros(4, jnp.int32)
            else:
                cap = max(32, _next_pow2(n))
                ok = np.full(cap, -1, dtype=np.int64)
                ok[:n] = okey
                md = np.zeros(cap, dtype=np.int64)
                md[:n] = shipmode
                part = _q12_probe_selected(
                    skeys, sprio, jnp.asarray(ok.astype(np.int32)),
                    jnp.asarray(md.astype(np.int32)))
            return part if acc is None else acc + part
        part = _q12_probe(
            skeys, sprio,
            _on_device(cols["l_orderkey"].array).astype(jnp.int32),
            _on_device(cols["l_shipmode"].array).astype(jnp.int32),
            _on_device(cols["l_shipdate"].array).astype(jnp.int32),
            _on_device(cols["l_commitdate"].array).astype(jnp.int32),
            _on_device(cols["l_receiptdate"].array).astype(jnp.int32))
        return part if acc is None else acc + part

    if _is_dataset(lineitem_scanner):
        from repro.dataset.executor import run_dataset_scan
        from repro.dataset.planner import plan_dataset_scan
        lplan = plan_dataset_scan(lineitem_scanner,
                                  columns=list(Q12_LINEITEM_COLUMNS))
        l_open_opts = open_opts
        if lspec is not None:
            l_open_opts = dict(open_opts or {}, fused_spec=lspec)
        if devices is not None:
            from repro.dataset.executor import run_distributed_scan
            counts, probe_report = run_distributed_scan(
                lplan, probe_consume, lambda a, b: a + b,
                devices=devices, depth=depth,
                decode_workers=decode_workers, open_opts=l_open_opts)
        else:
            # the probe partial depends on the build table, so its
            # fingerprint carries the orders side's content identity —
            # an orders change invalidates probe entries even when the
            # lineitem dataset is untouched
            odig = _source_digest(orders_scanner)
            lfp = (None if odig is None else
                   f"q12:probe:{'fused' if lspec else 'unfused'}:{odig}")
            counts, probe_report = run_dataset_scan(
                lplan, probe_consume, lambda a, b: a + b,
                window=window, depth=depth, decode_workers=decode_workers,
                service=service, open_opts=l_open_opts, tenant=tenant,
                result_cache=result_cache, fingerprint=lfp)
    else:
        counts, probe_report = runner(lineitem_scanner, probe_consume)
    counts = _to_host(np.asarray, counts, "q12_counts")
    result = {
        "MAIL_high": int(counts[0]), "MAIL_low": int(counts[1]),
        "SHIP_high": int(counts[2]), "SHIP_low": int(counts[3]),
    }
    return result, build_report, probe_report


def q12_reference(line: dict[str, np.ndarray],
                  orders: dict[str, np.ndarray]) -> dict[str, int]:
    ok = orders["o_orderkey"].astype(np.int64)
    op = orders["o_orderpriority"]
    pr = dict(zip(ok.tolist(), op.tolist()))
    mode = line["l_shipmode"]
    mask = (np.isin(mode, [SHIPMODE_MAIL, SHIPMODE_SHIP])
            & (line["l_commitdate"] < line["l_receiptdate"])
            & (line["l_shipdate"] < line["l_commitdate"])
            & (line["l_receiptdate"] >= D_1994_01_01)
            & (line["l_receiptdate"] < D_1995_01_01))
    out = {"MAIL_high": 0, "MAIL_low": 0, "SHIP_high": 0, "SHIP_low": 0}
    names = {SHIPMODE_MAIL: "MAIL", SHIPMODE_SHIP: "SHIP"}
    for i in np.flatnonzero(mask):
        p = pr[int(line["l_orderkey"][i])]
        key = names[int(mode[i])] + ("_high" if p <= 1 else "_low")
        out[key] += 1
    return out
