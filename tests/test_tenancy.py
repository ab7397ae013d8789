"""Multi-tenant front end (DESIGN.md §11): weighted fair scheduling,
admission control, the delivered-result window, the fragment result
cache, and the session API.

The fairness contract is pinned by a **deterministic scheduler
simulation**: a synthetic clock + event heap drives the *real*
``ScanService`` state machine (``_next_fetch_locked`` /
``_next_item_locked`` / ``_run_item``) single-threaded with scripted
fetch/decode durations, so dispatch-share ratios and starvation bounds
are exact properties of the scheduler — never timing flakes.

The acceptance contract:

  * a weight-4 tenant receives ~4x the row-group dispatches of a
    weight-1 tenant under saturation (within 15%), and the weight-1
    tenant never starves (bounded gap between its dispatches)
  * randomized weights / arrival orders keep shares proportional and
    delivery bit-identical to the sequential plan order (property
    tests, real hypothesis or the deterministic fallback shim)
  * over-limit submits reject with a typed error or queue until a slot
    frees, per the tenant's ``on_limit``
  * a late-arriving identical scan is served from the delivered-result
    window with strictly fewer io_requests, bit-identically; clearing
    the window restores the cold fetch count exactly
  * fragment-result-cache entries die with the manifest generation
    (swap/compaction) and survive a crash mid-compaction
"""

import heapq
import itertools
import threading
import time

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # deterministic fallback shim (hypothesis not installed)
    from _hypothesis_fallback import given, settings, st

from repro.core import scheduler as sched
from repro.core import trace
from repro.core.config import ACCELERATOR_OPTIMIZED
from repro.core.query import Q6_COLUMNS, q6
from repro.core.scan import open_scanner
from repro.core.scheduler import (AdmissionRejected, ScanService, Tenant,
                                  clear_delivered_windows)
from repro.core.table import Table
from repro.data import tpch
from repro.dataset.catalog import Dataset, write_dataset
from repro.dataset.executor import run_dataset_scan
from repro.dataset.planner import plan_dataset_scan
from repro.dataset.result_cache import (MISS, FragmentResultCache,
                                        clear_all_result_caches)
from repro.serve.engine import QueryFrontEnd

CFG = ACCELERATOR_OPTIMIZED.replace(rows_per_rg=1_500,
                                    target_pages_per_chunk=2)


# ---------------------------------------------------------------------------
# deterministic scheduler simulation
# ---------------------------------------------------------------------------

class _StubScanner:
    """Minimal scanner for the sim: ``plan`` → n row groups, instant
    fetch/decode (the sim's scripted durations model the time).  No
    ``planner`` attribute → ``share_key`` is None, so cooperative
    sharing and the delivered-result window never trigger — fairness is
    measured on real dispatches only."""

    def __init__(self, n_rgs: int):
        self.n_rgs = n_rgs

    def plan(self, predicate_stats=None, row_groups=None):
        return list(range(self.n_rgs))

    def fetch_rg(self, rg):
        return ("raw", rg), 0.0

    def decode_rg(self, rg, raws):
        return {"rg": rg}, 0.0


class _NoThreadService(ScanService):
    """A ScanService that never spawns threads: the sim driver IS the
    fetch pool and the decode pool."""

    def _ensure_threads_locked(self):
        pass

    def _spawn_to_target_locked(self):
        pass


class _Sim:
    """Single-threaded deterministic executor of the ScanService state
    machine.  One fetch slot and ``slots`` decode slots; every fetch
    takes ``fetch_dt`` synthetic seconds and every decode item
    ``dec_dt``; completions pop off an event heap in (time, insertion)
    order, so two runs of the same script are identical.

    The driver replicates ``_fetch_loop``'s post-fetch registration
    (build the _RgJob, queue its "open" item) and drains each handle
    only when its next in-order seq is already delivered — no
    condition-variable waits, no real time anywhere."""

    def __init__(self, svc: _NoThreadService, fetch_dt: float = 0.05,
                 dec_dt: float = 1.0, slots: int = 3):
        self.svc = svc
        self.fetch_dt = fetch_dt
        self.dec_dt = dec_dt
        self.slots = slots
        self.clock = 0.0
        self.heap: list[tuple] = []
        self._ctr = itertools.count()
        self.fetch_busy = False
        self.busy = 0
        self.handles: list[tuple] = []
        self.delivered: dict[str, list[int]] = {}
        #: (synthetic time, tenant name) per row-group "open" dispatch
        self.dispatch_log: list[tuple[float, str]] = []

    def submit(self, n_rgs: int, tenant: str | None, label: str,
               depth: int = 8):
        h = self.svc.submit(_StubScanner(n_rgs), tenant=tenant,
                            label=label, depth=depth)
        self.handles.append((h, label))
        self.delivered[label] = []
        return h

    def _push(self, dt: float, kind: str, payload):
        heapq.heappush(self.heap,
                       (self.clock + dt, next(self._ctr), kind, payload))

    def _try_fetch(self):
        while not self.fetch_busy:
            got = self.svc._next_fetch_locked()
            if got is None:
                return
            scan, seq, subscribed, _is_retry = got
            if subscribed:
                continue
            self.fetch_busy = True
            self._push(self.fetch_dt, "fetch", (scan, seq))

    def _fetch_done(self, scan, seq):
        self.fetch_busy = False
        if scan.dead:
            return
        raws, io_dt = scan.scanner.fetch_rg(scan.plan[seq])
        rgjob = sched._RgJob(scan, seq, scan.plan[seq], raws, io_dt, None)
        scan.ready.append(("open", rgjob, None))

    def _try_dispatch(self):
        while self.busy < self.slots:
            got = self.svc._next_item_locked(None)
            if got is None:
                return
            scan, item = got
            self.busy += 1
            if item[0] == "open":
                name = (scan.tenant.name if scan.tenant is not None
                        else "-")
                self.dispatch_log.append((self.clock, name))
            self._push(self.dec_dt, "item", (scan, item))

    def _item_done(self, scan, item):
        self.busy -= 1
        self.svc._run_item(scan, item)

    def _drain(self):
        for h, label in self.handles:
            scan = h._scan
            while not scan.finished:
                if h._next_seq >= len(scan.plan):
                    try:
                        next(h)
                    except StopIteration:
                        pass
                    break
                if h._next_seq in scan.done:
                    rg = next(h)[0]
                    self.delivered[label].append(rg)
                else:
                    break

    def _step(self):
        self._drain()
        self._try_fetch()
        self._try_dispatch()
        self._drain()

    def run(self, stop_after_dispatches: int | None = None,
            max_events: int = 500_000):
        self._step()
        n = 0
        while self.heap:
            n += 1
            assert n < max_events, "sim did not converge"
            t, _, kind, payload = heapq.heappop(self.heap)
            self.clock = t
            if kind == "fetch":
                self._fetch_done(*payload)
            else:
                self._item_done(*payload)
            self._step()
            if (stop_after_dispatches is not None
                    and len(self.dispatch_log) >= stop_after_dispatches):
                return


def _shares(log, first_n=None):
    counts: dict[str, int] = {}
    for _, name in (log if first_n is None else log[:first_n]):
        counts[name] = counts.get(name, 0) + 1
    return counts


def _max_gap(log, name):
    """Largest number of consecutive dispatches NOT won by ``name``."""
    gap = worst = 0
    for _, n in log:
        if n == name:
            worst = max(worst, gap)
            gap = 0
        else:
            gap += 1
    return worst


def test_sim_two_tenants_4_to_1_within_15pct():
    svc = _NoThreadService(workers=1, adaptive=False)
    svc.register_tenant("gold", weight=4)
    svc.register_tenant("bronze", weight=1)
    sim = _Sim(svc)
    sim.submit(200, "gold", "g0")
    sim.submit(200, "bronze", "b0")
    sim.run(stop_after_dispatches=150)
    counts = _shares(sim.dispatch_log, 150)
    ratio = counts["gold"] / counts["bronze"]
    assert 4 * 0.85 <= ratio <= 4 * 1.15, counts
    # starvation-freedom: bronze keeps landing dispatches throughout —
    # stride bounds the gap near sum(weights); 12 is generous
    assert _max_gap(sim.dispatch_log[:150], "bronze") <= 12
    # run to completion: every row group of both scans delivers in plan
    # order (bit-identical to a sequential run of each scan)
    sim.run()
    assert sim.delivered["g0"] == list(range(200))
    assert sim.delivered["b0"] == list(range(200))
    assert svc.tenant("gold").dispatches == 200
    assert svc.tenant("bronze").dispatches == 200
    assert svc.active_scans == 0


def test_sim_multi_scan_tenants_share_by_weight_not_scan_count():
    # bronze runs TWO scans, gold one: shares follow tenant weights, not
    # per-scan round-robin (2 scans must not double bronze's share)
    svc = _NoThreadService(workers=1, adaptive=False)
    svc.register_tenant("gold", weight=3)
    svc.register_tenant("bronze", weight=1)
    sim = _Sim(svc)
    sim.submit(200, "gold", "g0")
    sim.submit(150, "bronze", "b0")
    sim.submit(150, "bronze", "b1")
    sim.run(stop_after_dispatches=160)
    counts = _shares(sim.dispatch_log, 160)
    ratio = counts["gold"] / counts["bronze"]
    assert 3 * 0.8 <= ratio <= 3 * 1.2, counts
    sim.run()
    assert sim.delivered["b0"] == list(range(150))
    assert sim.delivered["b1"] == list(range(150))


def test_sim_idle_tenant_rejoins_without_burst():
    # bronze registered up front but submits late: its virtual time
    # re-syncs to the active minimum on admission, so banked idleness
    # never becomes a catch-up burst over gold
    svc = _NoThreadService(workers=1, adaptive=False)
    svc.register_tenant("gold", weight=4)
    svc.register_tenant("bronze", weight=1)
    sim = _Sim(svc)
    sim.submit(400, "gold", "g0")
    sim.run(stop_after_dispatches=80)       # gold runs alone for a while
    before = len(sim.dispatch_log)
    sim.submit(200, "bronze", "b0")
    sim.run(stop_after_dispatches=before + 60)
    window = sim.dispatch_log[before:before + 60]
    bronze_share = sum(1 for _, n in window if n == "bronze") / len(window)
    # fair share is 1/5 = 0.2; a burst would spike well above it
    assert bronze_share <= 0.35, bronze_share
    assert bronze_share > 0.0
    for h, _ in sim.handles:
        h.cancel()
    svc.shutdown()


def test_sim_untenanted_scans_ride_as_shared_weight1_tenant():
    svc = _NoThreadService(workers=1, adaptive=False)
    svc.register_tenant("gold", weight=2)
    sim = _Sim(svc)
    sim.submit(150, "gold", "g0")
    sim.submit(150, None, "u0")             # untenanted sibling
    sim.run(stop_after_dispatches=120)
    counts = _shares(sim.dispatch_log, 120)
    ratio = counts["gold"] / counts["-"]
    assert 2 * 0.8 <= ratio <= 2 * 1.2, counts
    sim.run()
    assert sim.delivered["u0"] == list(range(150))


@settings(max_examples=8)
@given(st.lists(st.integers(min_value=1, max_value=8),
                min_size=2, max_size=4),
       st.integers(min_value=0, max_value=10_000))
def test_property_shares_track_weights_any_arrival_order(weights,
                                                         order_seed):
    svc = _NoThreadService(workers=1, adaptive=False)
    names = [f"t{i}" for i in range(len(weights))]
    for name, w in zip(names, weights):
        svc.register_tenant(name, weight=w)
    order = list(range(len(weights)))
    np.random.default_rng(order_seed).shuffle(order)
    sim = _Sim(svc)
    n_rgs = 220
    for i in order:                          # randomized arrival order
        sim.submit(n_rgs, names[i], f"s{i}")
    total_w = sum(weights)
    n_obs = 200
    sim.run(stop_after_dispatches=n_obs)
    counts = _shares(sim.dispatch_log, n_obs)
    for name, w in zip(names, weights):
        got = counts.get(name, 0)
        expect = n_obs * w / total_w
        assert abs(got - expect) <= max(4, 0.25 * expect), \
            (weights, order, counts)
        # starvation-freedom under arbitrary weights
        assert got > 0
    assert _max_gap(sim.dispatch_log[:n_obs], names[weights.index(
        min(weights))]) <= 4 * total_w + 8
    # bit-identical to sequential: every scan's delivery IS its plan order
    sim.run()
    for i in range(len(weights)):
        assert sim.delivered[f"s{i}"] == list(range(n_rgs))


# ---------------------------------------------------------------------------
# admission control (real service)
# ---------------------------------------------------------------------------

def test_admission_reject_and_release():
    svc = ScanService(workers=1, adaptive=False)
    try:
        svc.register_tenant("bronze", weight=1, max_active=1,
                            on_limit="reject")
        reg = trace.registry()
        rejects0 = reg.snapshot()["counters"].get(
            "scheduler.admission_rejects", 0)
        h1 = svc.submit(_StubScanner(64), tenant="bronze", depth=1)
        with pytest.raises(AdmissionRejected):
            svc.submit(_StubScanner(4), tenant="bronze")
        assert (reg.snapshot()["counters"]["scheduler.admission_rejects"]
                == rejects0 + 1)
        assert svc.tenant("bronze").active == 1
        h1.cancel()
        assert svc.tenant("bronze").active == 0
        h2 = svc.submit(_StubScanner(4), tenant="bronze")  # slot freed
        for _ in h2:
            pass
    finally:
        svc.shutdown()


def test_admission_queue_blocks_until_slot_frees():
    svc = ScanService(workers=1, adaptive=False)
    try:
        svc.register_tenant("q", weight=1, max_active=1, on_limit="queue")
        h1 = svc.submit(_StubScanner(64), tenant="q", depth=1)
        admitted = []

        def second():
            h2 = svc.submit(_StubScanner(4), tenant="q")
            admitted.append(h2)

        t = threading.Thread(target=second, daemon=True)
        t.start()
        time.sleep(0.3)
        assert t.is_alive() and not admitted     # still queued
        h1.cancel()                              # frees the slot
        t.join(timeout=5.0)
        assert admitted, "queued submit was never admitted"
        for _ in admitted[0]:
            pass
        assert svc.tenant("q").active == 0
    finally:
        svc.shutdown()


def test_admission_queue_wait_is_a_queued_span():
    svc = ScanService(workers=1, adaptive=False)
    tr = trace.enable()
    try:
        svc.register_tenant("q", weight=1, max_active=1, on_limit="queue")
        h1 = svc.submit(_StubScanner(64), tenant="q", depth=1)
        t = threading.Thread(
            target=lambda: list(svc.submit(_StubScanner(4), tenant="q")),
            daemon=True)
        t.start()
        time.sleep(0.3)
        h1.cancel()
        t.join(timeout=5.0)
        (span,) = [e for e in tr.events() if e.name == "queued"]
        assert span.cat == "frontend" and span.args == {"tenant": "q"}
        assert span.dur >= 0.25
    finally:
        svc.shutdown()
        trace.reset()


def test_admission_unknown_tenant_auto_registers_weight1():
    svc = ScanService(workers=1, adaptive=False)
    try:
        h = svc.submit(_StubScanner(4), tenant="newcomer")
        ten = svc.tenant("newcomer")
        assert (ten.weight, ten.max_active) == (1, None)
        for _ in h:
            pass
        assert ten.dispatches == 4
    finally:
        svc.shutdown()


def test_tenant_validation():
    with pytest.raises(ValueError):
        Tenant("bad", weight=0)
    with pytest.raises(ValueError):
        Tenant("bad", on_limit="drop")
    svc = ScanService(workers=1)
    try:
        svc.register_tenant("a", weight=2)
        svc.register_tenant("a", weight=5)       # re-configure in place
        assert svc.tenant("a").weight == 5
    finally:
        svc.shutdown()


def test_slo_miss_boosts_pool_policy():
    svc = ScanService(workers=1, adaptive=True, resize_every=1,
                      max_workers=4)
    try:
        svc.register_tenant("slo", weight=1, slo_s=1e-9)  # always missed
        h1 = svc.submit(_StubScanner(4), tenant="slo")
        for _ in h1:                              # records a latency ≫ slo
            pass
        h2 = svc.submit(_StubScanner(8), tenant="slo")
        for _ in h2:                              # resizes see the miss
            pass
        snap = trace.registry().snapshot()["counters"]
        assert snap.get("scheduler.slo_boosts", 0) >= 1
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# delivered-result window (real service, real files)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_tpch(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch_tenancy")
    cfg = ACCELERATOR_OPTIMIZED.replace(rows_per_rg=4_000,
                                        target_pages_per_chunk=8)
    return tpch.write_tpch(str(d), sf=0.004, config=cfg, seed=77)


def _q6_scanner(metas):
    return open_scanner(metas["lineitem_path"], columns=list(Q6_COLUMNS),
                        decode_backend="host")


def test_window_serves_repeat_scan_with_fewer_io_requests(small_tpch):
    svc = ScanService(workers=2, window_bytes=64 << 20)
    try:
        a1, r1 = q6(_q6_scanner(small_tpch), prune=False, service=svc,
                    tenant="gold", decode_workers=2)
        a2, r2 = q6(_q6_scanner(small_tpch), prune=False, service=svc,
                    tenant="gold", decode_workers=2)
        assert a2 == a1                              # bit-identical
        assert r2.metrics.n_io_requests < r1.metrics.n_io_requests
        assert r2.metrics.n_io_requests == 0         # fully window-served
        assert svc.window_hits > 0
        assert svc.window_entries > 0
        # cold-ladder contract: clearing the window restores the exact
        # cold fetch count (and stays bit-identical)
        clear_delivered_windows()
        assert svc.window_entries == 0
        a3, r3 = q6(_q6_scanner(small_tpch), prune=False, service=svc,
                    tenant="gold", decode_workers=2)
        assert a3 == a1
        assert r3.metrics.n_io_requests == r1.metrics.n_io_requests
    finally:
        svc.shutdown()


def test_window_off_by_default_keeps_cold_io_counts(small_tpch):
    svc = ScanService(workers=2)                     # window_bytes=0
    try:
        _, r1 = q6(_q6_scanner(small_tpch), prune=False, service=svc,
                   decode_workers=2)
        _, r2 = q6(_q6_scanner(small_tpch), prune=False, service=svc,
                   decode_workers=2)
        assert r2.metrics.n_io_requests == r1.metrics.n_io_requests
        assert svc.window_hits == 0
    finally:
        svc.shutdown()


def test_concurrent_tenants_bit_identical_to_sequential(small_tpch):
    a_ref, _ = q6(_q6_scanner(small_tpch), prune=False, decode_workers=1)
    svc = ScanService(workers=2, window_bytes=0)
    try:
        svc.register_tenant("gold", weight=4)
        svc.register_tenant("bronze", weight=1)
        out: dict[str, float] = {}

        def run(tenant):
            acc, _ = q6(_q6_scanner(small_tpch), prune=False, service=svc,
                        tenant=tenant, decode_workers=2)
            out[tenant] = acc

        ts = [threading.Thread(target=run, args=(t,), daemon=True)
              for t in ("gold", "bronze", "gold", "bronze")]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert out["gold"] == a_ref and out["bronze"] == a_ref
        assert svc.tenant("gold").dispatches >= 0  # charged via fair path
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# fragment result cache
# ---------------------------------------------------------------------------

def _table(n=9_000, seed=0):
    rng = np.random.default_rng(seed)
    return Table({"k": rng.integers(0, 50, n).astype(np.int64),
                  "v": rng.normal(size=n).astype(np.float32)})


def _mk_dataset(tmp_path, n=9_000):
    return write_dataset(_table(n), str(tmp_path / "ds"), CFG,
                         partition_by="k", how="range", fragments=4)


def _sum_consume(acc, rg, cols):
    s = float(np.asarray(cols["v"].array[:cols["v"].n_values]).sum())
    return (acc or 0.0) + s


def _ds_scan(ds, **kw):
    plan = plan_dataset_scan(ds, columns=["v"])
    kw.setdefault("combine", lambda a, b: a + b)
    return run_dataset_scan(plan, _sum_consume, **kw)


def test_result_cache_repeat_scan_hits_all_fragments(tmp_path):
    ds = _mk_dataset(tmp_path)
    cache = FragmentResultCache()
    acc1, rep1 = _ds_scan(ds, result_cache=cache, fingerprint="sum:v")
    assert rep1.result_cache_hits == 0
    assert len(cache) == len(ds.fragments)
    acc2, rep2 = _ds_scan(ds, result_cache=cache, fingerprint="sum:v")
    assert acc2 == acc1                              # bit-identical
    assert rep2.result_cache_hits == len(ds.fragments)
    assert rep2.n_io_requests == 0                   # nothing refetched
    assert cache.hits == len(ds.fragments)
    # a different predicate fingerprint never aliases
    acc3, rep3 = _ds_scan(ds, result_cache=cache, fingerprint="sum:v2")
    assert rep3.result_cache_hits == 0 and acc3 == acc1
    assert "result_cache_hits=4" in rep2.summary()


def test_result_cache_invalidated_on_manifest_swap(tmp_path):
    ds = _mk_dataset(tmp_path)
    cache = FragmentResultCache()
    acc1, _ = _ds_scan(ds, result_cache=cache, fingerprint="sum:v")
    assert len(cache) == 4
    ds.generation += 1                               # manifest swap
    ds.save()
    assert len(cache) == 0 and cache.invalidated == 4
    acc2, rep2 = _ds_scan(Dataset.load(ds.root), result_cache=cache,
                          fingerprint="sum:v")
    assert rep2.result_cache_hits == 0 and acc2 == acc1


def test_result_cache_invalidated_by_compaction(tmp_path):
    import repro.dataset.compact as compact_mod
    ds = _mk_dataset(tmp_path)
    cache = FragmentResultCache()
    acc1, _ = _ds_scan(ds, result_cache=cache, fingerprint="sum:v")
    gen0 = ds.generation
    compacted, _rep = compact_mod.compact_dataset(ds)
    if compacted.generation == gen0:
        pytest.skip("compaction plan was empty")
    # stale-generation entries died with the swap; the compacted layout
    # recomputes and stays bit-identical
    assert all(k[1] == compacted.generation for k in cache._entries)
    acc2, rep2 = _ds_scan(compacted, result_cache=cache,
                          fingerprint="sum:v")
    assert acc2 == pytest.approx(acc1, rel=1e-6)
    assert rep2.result_cache_hits == 0 or acc2 == acc1


def test_result_cache_survives_crash_mid_compaction(tmp_path):
    import repro.dataset.compact as compact_mod
    ds = _mk_dataset(tmp_path)
    cache = FragmentResultCache()
    acc1, _ = _ds_scan(ds, result_cache=cache, fingerprint="sum:v")
    assert len(cache) == 4
    real_writer = compact_mod.TabFileWriter

    class CrashingWriter(real_writer):
        def __init__(self, *a, **kw):
            raise RuntimeError("injected crash mid-compaction")

    compact_mod.TabFileWriter = CrashingWriter
    try:
        with pytest.raises(RuntimeError, match="mid-compaction"):
            compact_mod.compact_dataset(Dataset.load(ds.root))
    finally:
        compact_mod.TabFileWriter = real_writer
    # the manifest never swapped: every cached result is still valid
    assert len(cache) == 4 and cache.invalidated == 0
    survivor = Dataset.open(ds.root)
    acc2, rep2 = _ds_scan(survivor, result_cache=cache,
                          fingerprint="sum:v")
    assert acc2 == acc1
    assert rep2.result_cache_hits == 4


def test_result_cache_lru_cap_and_clear(tmp_path):
    cache = FragmentResultCache(max_entries=2)
    cache.put("/r", 1, "f0", "p", 10.0)
    cache.put("/r", 1, "f1", "p", 11.0)
    cache.put("/r", 1, "f2", "p", 12.0)
    assert len(cache) == 2 and cache.evictions == 1
    assert cache.get("/r", 1, "f0", "p") is MISS     # LRU-evicted
    assert cache.get("/r", 1, "f2", "p") == 12.0
    clear_all_result_caches()
    assert len(cache) == 0


def test_q6_dataset_routes_through_result_cache(tmp_path):
    line, _orders = tpch.generate_tables(sf=0.004, seed=77)
    cfg = ACCELERATOR_OPTIMIZED.replace(rows_per_rg=4_000,
                                        target_pages_per_chunk=8)
    ds = write_dataset(line, str(tmp_path / "li_ds"), cfg,
                       partition_by="l_shipdate", how="range", fragments=3)
    cache = FragmentResultCache()
    a1, r1 = q6(ds, result_cache=cache, tenant="gold")
    a2, r2 = q6(ds, result_cache=cache, tenant="gold")
    assert a2 == a1
    assert r2.result_cache_hits > 0
    assert len(cache) > 0


# ---------------------------------------------------------------------------
# session API (serve/engine.py)
# ---------------------------------------------------------------------------

def test_frontend_submit_poll_result_round_trip(small_tpch):
    a_ref, _ = q6(_q6_scanner(small_tpch), prune=False, decode_workers=1)
    with QueryFrontEnd(workers=2) as fe:
        fe.register_tenant("gold", weight=4)
        fe.register_tenant("bronze", weight=1)
        t1 = fe.submit("gold", "q6", _q6_scanner(small_tpch), prune=False,
                       decode_workers=2)
        t2 = fe.submit("bronze", "q6", _q6_scanner(small_tpch),
                       prune=False, decode_workers=2)
        acc1, reports1 = fe.result(t1, timeout=60)
        acc2, _ = fe.result(t2, timeout=60)
        assert acc1 == a_ref and acc2 == a_ref
        assert len(reports1) == 1
        st1 = fe.poll(t1)
        assert st1["state"] == "done" and st1["tenant"] == "gold"
        assert st1["wall_s"] >= 0.0
        assert {t["id"] for t in fe.tickets("gold")} == {t1}
        # the repeat arrived after the first finished: the front end's
        # delivered-result window served it (strictly fewer io_requests)
        assert reports1[0].metrics.n_io_requests >= 0
        assert fe.service.window_hits > 0 or fe.service.shared_rgs > 0


def test_frontend_started_at_stamp_and_spans(small_tpch):
    tr = trace.enable()
    try:
        with QueryFrontEnd(workers=1) as fe:
            tid = fe.submit("gold", "q6", _q6_scanner(small_tpch),
                            prune=False)
            fe.result(tid, timeout=60)
            ticket = fe._tickets[tid]
        assert (0 < ticket.submitted_at <= ticket.started_at
                <= ticket.finished_at)
        (queued,) = [e for e in tr.events() if e.name == "queued"]
        assert queued.cat == "frontend"
        assert queued.args == {"ticket": tid, "tenant": "gold"}
        # the queue wait runs from submit to the run's start, on the
        # ticket's stamps
        assert tr.epoch + queued.ts == pytest.approx(ticket.submitted_at,
                                                     abs=1e-9)
        assert tr.epoch + queued.ts + queued.dur == pytest.approx(
            ticket.started_at, abs=1e-9)
    finally:
        trace.reset()


def test_frontend_rejected_ticket(small_tpch):
    with QueryFrontEnd(workers=1) as fe:
        fe.register_tenant("full", weight=1, max_active=0,
                           on_limit="reject")
        tid = fe.submit("full", "q6", _q6_scanner(small_tpch),
                        prune=False)
        with pytest.raises(AdmissionRejected):
            fe.result(tid, timeout=30)
        assert fe.poll(tid)["state"] == "rejected"
        assert "AdmissionRejected" in fe.poll(tid)["error"]


def test_frontend_cancel_discards_result(small_tpch):
    with QueryFrontEnd(workers=1) as fe:
        tid = fe.submit("gold", "q6", _q6_scanner(small_tpch),
                        prune=False)
        if fe.cancel(tid):
            assert fe.poll(tid)["state"] == "cancelled"
            with pytest.raises(RuntimeError):
                fe.result(tid, timeout=30)
        else:                      # query already finished — still done
            assert fe.poll(tid)["state"] == "done"


def test_frontend_rejects_unknown_query(small_tpch):
    with QueryFrontEnd(workers=1) as fe:
        with pytest.raises(ValueError):
            fe.submit("gold", "q99", None)
        with pytest.raises(KeyError):
            fe.poll("t999")
