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
import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fused import (FUSED_KEY, Compare, FusedSpec, Interval,
                              SumProduct)
from repro.core.overlap import RunReport, run_blocking, run_overlapped
from repro.core.scan import Scanner
from repro.kernels.filter_agg import TILE, filter_agg_q6

D_1994_01_01 = 731
D_1995_01_01 = 1096

Q6_COLUMNS = ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]
Q12_LINEITEM_COLUMNS = ["l_orderkey", "l_shipmode", "l_shipdate",
                        "l_commitdate", "l_receiptdate"]
Q12_ORDERS_COLUMNS = ["o_orderkey", "o_orderpriority"]


def _dev(x):
    return jnp.asarray(np.asarray(x))


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
        ship = _dev(cols["l_shipdate"].array).astype(jnp.int32)
        disc = _dev(cols["l_discount"].array).astype(jnp.float32)
        qty = _dev(cols["l_quantity"].array).astype(jnp.float32)
        price = _dev(cols["l_extendedprice"].array).astype(jnp.float32)
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
        part = float(part)
        return part if acc is None else acc + part

    return consume


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
    fragments from cached partials, invalidated on manifest swap."""
    fused = _resolve_fused(fused)
    spec = q6_fused_spec("reference" if fused == "reference"
                         else "fused") if fused else None
    consume = (_q6_consume_fused(use_kernel) if spec is not None
               else _q6_consume(use_kernel))
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
        if devices is not None:
            from repro.dataset.executor import run_distributed_scan
            acc, report = run_distributed_scan(
                plan, consume, lambda a, b: a + b,
                devices=devices, depth=depth,
                decode_workers=decode_workers, open_opts=open_opts,
                trace=trace)
            return (acc or 0.0), report
        fp = (f"q6:{'fused' if spec is not None else 'unfused'}:"
              f"{'ref' if fused == 'reference' else 'opt'}:"
              f"k{int(use_kernel)}:p{int(prune)}")
        acc, report = run_dataset_scan(
            plan, consume, lambda a, b: a + b,
            window=window, depth=depth, decode_workers=decode_workers,
            service=service, open_opts=open_opts, trace=trace,
            tenant=tenant, result_cache=result_cache, fingerprint=fp)
        return (acc or 0.0), report
    if spec is not None and scanner.planner is not None \
            and scanner.fused_spec != spec:
        scanner.enable_fused(spec)
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
    return (acc or 0.0), report


def q6_reference(tables: dict[str, np.ndarray]) -> float:
    """Numpy oracle over raw columns."""
    ship, disc = tables["l_shipdate"], tables["l_discount"]
    qty, price = tables["l_quantity"], tables["l_extendedprice"]
    m = ((ship >= D_1994_01_01) & (ship < D_1995_01_01)
         & (disc >= np.float32(0.05)) & (disc <= np.float32(0.07))
         & (qty < 24))
    return float(np.sum(price[m].astype(np.float64)
                        * disc[m].astype(np.float64)))


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
        from repro.core import trace as trace_mod
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
        k = _dev(cols["o_orderkey"].array).astype(jnp.int32)
        p = _dev(cols["o_orderpriority"].array).astype(jnp.int32)
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
            _dev(cols["l_orderkey"].array).astype(jnp.int32),
            _dev(cols["l_shipmode"].array).astype(jnp.int32),
            _dev(cols["l_shipdate"].array).astype(jnp.int32),
            _dev(cols["l_commitdate"].array).astype(jnp.int32),
            _dev(cols["l_receiptdate"].array).astype(jnp.int32))
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
    counts = np.asarray(counts)
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
