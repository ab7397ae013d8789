"""Flight recorder + metrics registry (core/trace.py, DESIGN.md §10):
off-by-default, bounded buffers, span well-formedness under concurrent
scans, reconciliation of traced spans against ScanMetrics, bit-identity
with tracing on vs off on the fused and unfused paths, backend-aware
retry-policy defaults, and tools/trace_report.py's bucket attribution."""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import trace
from repro.core.config import ACCELERATOR_OPTIMIZED
from repro.core.overlap import run_blocking, run_overlapped
from repro.core.query import Q6_COLUMNS, q6
from repro.core.scan import Scanner, open_scanner
from repro.core.storage import (DEFAULT_RETRY_POLICY, NO_RETRY,
                                OBJECT_RETRY_POLICY, ObjectStoreStorage,
                                SimulatedStorage, backend_retry_policy)
from repro.core.table import Table
from repro.core.writer import write_table

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import trace_report  # noqa: E402

CFG = ACCELERATOR_OPTIMIZED.replace(rows_per_rg=1_500,
                                    target_pages_per_chunk=2)


@pytest.fixture(autouse=True)
def _clean_trace_state():
    """Every test starts and ends with the recorder off and the env
    unresolved — tracing state is process-global."""
    trace.reset()
    yield
    trace.reset()


def _table(n=9_000, seed=0):
    rng = np.random.default_rng(seed)
    return Table({"k": rng.integers(0, 50, n).astype(np.int64),
                  "v": rng.normal(size=n).astype(np.float32)})


@pytest.fixture()
def tab_file(tmp_path):
    path = str(tmp_path / "t.tab")
    write_table(_table(), path, CFG)
    return path


def _sum_consume(acc, rg, cols):
    s = float(np.asarray(cols["v"].array[:cols["v"].n_values]).sum())
    return (acc or 0.0) + s


# -- enablement --------------------------------------------------------------

def test_off_by_default(tab_file, monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    trace.reset()
    assert trace.active() is None
    sc = open_scanner(tab_file, columns=["v"], decode_backend="host")
    _, rep = run_overlapped(sc, _sum_consume, decode_workers=2)
    assert trace.active() is None
    assert rep.metrics.trace_events == 0
    assert rep.metrics.registry_snapshot == {}


def test_env_var_enables(monkeypatch):
    monkeypatch.setenv("REPRO_TRACE", "1")
    trace.reset()
    assert trace.active() is not None
    monkeypatch.setenv("REPRO_TRACE", "0")
    trace.reset()
    assert trace.active() is None


def test_enable_disable_idempotent():
    tr = trace.enable()
    assert trace.enable() is tr          # idempotent
    assert trace.active() is tr
    trace.disable()
    assert trace.active() is None
    tr.complete("late", "io", 0.0, 1.0)  # held reference stays usable
    assert tr.event_count() == 1


def test_request_context_enables_and_exports(tab_file, tmp_path):
    out = str(tmp_path / "run.json")
    sc = open_scanner(tab_file, columns=["v"], decode_backend="host")
    _, rep = run_overlapped(sc, _sum_consume, decode_workers=2,
                            trace=out)
    assert trace.active() is None        # last request turned it off
    assert rep.metrics.trace_events > 0
    doc = trace_report.load_trace(out)
    assert trace_report.validate_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"fetch", "consume", "scan"} <= names


def test_request_none_is_noop(tab_file):
    sc = open_scanner(tab_file, columns=["v"], decode_backend="host")
    _, rep = run_overlapped(sc, _sum_consume, decode_workers=2,
                            trace=None)
    assert trace.active() is None
    assert rep.metrics.trace_events == 0


# -- bounded buffers ---------------------------------------------------------

def test_global_cap_bounds_and_counts_drops():
    tr = trace.Tracer(cap=32)
    for i in range(100):
        tr.instant("e", "io", i=i)
    assert tr.event_count() == 32
    assert tr.dropped == 68
    assert tr.to_chrome()["otherData"]["dropped"] == 68


def test_per_scan_cap_protects_other_scans():
    tr = trace.Tracer(cap=64)            # scan_cap = 32
    for _ in range(50):
        tr.instant("e", "io", scan="chatty")
    assert tr.dropped_by_scan["chatty"] == 50 - tr.scan_cap
    tr.instant("e", "io", scan="quiet")  # still admitted
    by_scan = [e.args.get("scan") for e in tr.events()]
    assert by_scan.count("chatty") == tr.scan_cap
    assert by_scan.count("quiet") == 1


def test_clear_resets_buffer_and_drops():
    tr = trace.Tracer(cap=16)
    for _ in range(40):
        tr.instant("e", "io", scan="s")
    tr.clear()
    assert tr.event_count() == 0
    assert tr.dropped == 0
    tr.instant("e", "io", scan="s")
    assert tr.event_count() == 1


# -- metrics registry --------------------------------------------------------

def test_registry_counters_gauges_histograms():
    reg = trace.MetricsRegistry()
    reg.counter_inc("a")
    reg.counter_inc("a", 4)
    reg.gauge_set("g", 7)
    for v in (1.0, 3.0, 2.0):
        reg.observe("h", v)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 5
    assert snap["gauges"]["g"] == 7
    h = snap["histograms"]["h"]
    assert (h["count"], h["sum"], h["min"], h["max"]) == (3, 6.0, 1.0, 3.0)
    assert h["mean"] == pytest.approx(2.0)
    reg.clear()
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_registry_snapshot_lands_in_scan_metrics(tab_file):
    trace.enable()
    sc = open_scanner(tab_file, columns=["v"], decode_backend="host")
    _, rep = run_overlapped(sc, _sum_consume, decode_workers=2)
    snap = rep.metrics.registry_snapshot
    assert "scheduler.fetch_wall_s" in snap["histograms"]
    assert snap["histograms"]["scheduler.fetch_wall_s"]["count"] \
        == rep.metrics.n_row_groups


# -- reconciliation: traced spans vs ScanMetrics -----------------------------

def _spans(tr, name):
    return [e for e in tr.events() if e.name == name and e.ph == "X"]


def test_reconciliation_service_path(tab_file):
    tr = trace.enable()
    sc = open_scanner(tab_file, columns=["v"], decode_backend="host")
    _, rep = run_overlapped(sc, _sum_consume, decode_workers=2)
    m = rep.metrics
    # the fetch span carries the same io_dt float appended to io_per_rg
    fetched = sorted(e.args["io_dt"] for e in _spans(tr, "fetch"))
    assert fetched == sorted(m.io_per_rg)
    # decode items' durations ARE the chunk_times floats -> per-RG sums
    # reconcile with decode_per_rg (fp accumulation order may differ)
    per_rg: dict[int, float] = {}
    for e in tr.events():
        if e.cat == "decode" and e.ph == "X":
            per_rg[e.args["rg"]] = per_rg.get(e.args["rg"], 0.0) + e.dur
    assert len(per_rg) == m.n_row_groups
    for dec, rg in zip(m.decode_per_rg, sorted(per_rg)):
        assert per_rg[rg] == pytest.approx(dec, rel=1e-9, abs=1e-12)
    # consume spans share their stamps with consume_seconds exactly
    assert sum(e.dur for e in _spans(tr, "consume")) \
        == pytest.approx(m.consume_seconds, rel=1e-9)
    # the whole-run span IS the measured wall
    (scan_span,) = _spans(tr, "scan")
    assert scan_span.dur == pytest.approx(rep.measured_wall, rel=1e-9)
    assert scan_span.args["mode"] == "overlapped"
    assert m.trace_events == tr.event_count()


def test_reconciliation_blocking_path(tab_file):
    tr = trace.enable()
    sc = open_scanner(tab_file, columns=["v"], decode_backend="host")
    _, rep = run_blocking(sc, _sum_consume)
    m = rep.metrics
    assert sorted(e.args["io_dt"] for e in _spans(tr, "fetch")) \
        == sorted(m.io_per_rg)
    # decode_rg spans bracket scanner.decode_rg: their sum is the decode
    # stage wall (host-measured), within accumulation tolerance
    assert sum(e.dur for e in _spans(tr, "decode_rg")) \
        == pytest.approx(m.decode_wall_seconds, rel=1e-9)
    (scan_span,) = _spans(tr, "scan")
    assert scan_span.args["mode"] == "blocking"
    assert scan_span.dur == pytest.approx(rep.measured_wall, rel=1e-9)


def test_reconciliation_inline_path(tab_file):
    tr = trace.enable()
    sc = open_scanner(tab_file, columns=["v"], decode_backend="host")
    _, rep = run_overlapped(sc, _sum_consume, decode_workers=0)
    (scan_span,) = _spans(tr, "scan")
    assert scan_span.args["mode"] == "overlapped-inline"
    assert scan_span.dur == pytest.approx(rep.measured_wall, rel=1e-9)
    assert sum(e.dur for e in _spans(tr, "decode_rg")) \
        == pytest.approx(rep.metrics.decode_wall_seconds, rel=1e-9)


# -- well-formedness under concurrency ---------------------------------------

def test_spans_well_formed_under_concurrent_scans(tmp_path):
    paths = []
    for k in range(3):
        p = str(tmp_path / f"t{k}.tab")
        write_table(_table(seed=k), p, CFG)
        paths.append(p)
    tr = trace.enable()
    errors: list[BaseException] = []

    def one(p):
        try:
            sc = open_scanner(p, columns=["v"], decode_backend="host")
            run_overlapped(sc, _sum_consume, decode_workers=2)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(p,)) for p in paths]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    events = tr.events()
    assert all(e.ts >= 0 and e.dur >= 0 for e in events)
    assert all(e.ph in ("X", "i") for e in events)
    # one balanced whole-run span per scan, each attributing its file
    scans = [e for e in events if e.name == "scan"]
    assert sorted(e.args["scan"] for e in scans) == sorted(paths)
    # the export round-trips through the validator cleanly
    doc = tr.to_chrome()
    assert trace_report.validate_trace(doc) == []


def test_chrome_event_format():
    tr = trace.Tracer()
    tr.complete("s", "io", tr.epoch + 0.001, tr.epoch + 0.003, rg=1)
    tr.instant("i", "fault")
    doc = tr.to_chrome()
    span, inst = doc["traceEvents"]
    assert span["ph"] == "X"
    assert span["dur"] == pytest.approx(2_000.0)   # µs
    assert span["ts"] == pytest.approx(1_000.0)
    assert span["args"] == {"rg": 1}
    assert inst["ph"] == "i" and inst["s"] == "t"
    assert doc["displayTimeUnit"] == "ms"
    assert "registry" in doc["otherData"]


# -- bit-identity: tracing must not change results ---------------------------

@pytest.mark.parametrize("fused", [False, True])
def test_bit_identity_tracing_on_off(tmp_path_factory, fused):
    d = tmp_path_factory.mktemp("trace_q6")
    from repro.data import tpch
    tpch.write_tpch(str(d), sf=0.002, config=CFG, seed=5)
    path = str(d / "lineitem.tab")

    def run():
        sc = open_scanner(path, columns=Q6_COLUMNS,
                          decode_backend="host")
        return q6(sc, overlapped=True, decode_workers=2, fused=fused)

    res_off, rep_off = run()
    tr = trace.enable()
    res_on, rep_on = run()
    trace.disable()
    assert np.float64(res_on).tobytes() == np.float64(res_off).tobytes()
    assert rep_on.metrics.n_io_requests == rep_off.metrics.n_io_requests
    assert rep_on.metrics.trace_events > 0
    assert rep_off.metrics.trace_events == 0
    if fused:
        # the fused stage records its phase-3 items under the recorder
        names = {e.name for e in tr.events()}
        assert "fused" in names or "decode" in names


# -- backend-aware retry-policy defaults (satellite: object-store) -----------

def test_backend_retry_policy_profiles():
    assert backend_retry_policy("object") is OBJECT_RETRY_POLICY
    assert backend_retry_policy("real") is DEFAULT_RETRY_POLICY
    assert backend_retry_policy("sim") is DEFAULT_RETRY_POLICY
    assert OBJECT_RETRY_POLICY.name == "object"
    assert DEFAULT_RETRY_POLICY.name == "nvme"
    assert NO_RETRY.name == "none"
    # object-store profile: more attempts, longer backoff, wider budget
    assert OBJECT_RETRY_POLICY.attempts > DEFAULT_RETRY_POLICY.attempts
    assert OBJECT_RETRY_POLICY.base_delay > DEFAULT_RETRY_POLICY.base_delay
    assert OBJECT_RETRY_POLICY.timeout > (DEFAULT_RETRY_POLICY.timeout
                                          or 0.0)


def test_scanner_defaults_retry_policy_by_backend(tab_file):
    sc_nvme = Scanner(tab_file, columns=["v"],
                      storage=SimulatedStorage(tab_file))
    assert sc_nvme.retry.name == "nvme"
    sc_obj = Scanner(tab_file, columns=["v"],
                     storage=ObjectStoreStorage(tab_file))
    assert sc_obj.retry.name == "object"
    assert sc_obj.retry.attempts == OBJECT_RETRY_POLICY.attempts
    explicit = Scanner(tab_file, columns=["v"],
                       storage=ObjectStoreStorage(tab_file),
                       retry=NO_RETRY)
    assert explicit.retry.name == "none"


def test_retry_policy_name_lands_in_metrics(tab_file):
    sc = open_scanner(tab_file, columns=["v"], decode_backend="host")
    _, rep = run_overlapped(sc, _sum_consume, decode_workers=2)
    assert rep.metrics.retry_policy == "nvme"
    sc2 = open_scanner(tab_file, columns=["v"], decode_backend="host")
    _, rep2 = run_blocking(sc2, _sum_consume)
    assert rep2.metrics.retry_policy == "nvme"


# -- trace_report ------------------------------------------------------------

def _synthetic_doc():
    """scan 0-100ms; fetch 0-20; decode 10-50; consume 50-80 →
    fetch 10ms, decode 40ms, consume 30ms, stall 20ms."""
    tr = trace.Tracer()
    e = tr.epoch
    tr.complete("scan", "scan", e, e + 0.100, scan="s")
    tr.complete("fetch", "io", e, e + 0.020, scan="s", rg=0, io_dt=0.02)
    tr.complete("decode", "decode", e + 0.010, e + 0.050, scan="s", rg=0)
    tr.complete("consume", "consume", e + 0.050, e + 0.080, scan="s",
                rg=0, logical_bytes=1_000_000)
    return tr.to_chrome()


def test_trace_report_bucket_attribution_partitions_wall():
    rep = trace_report.build_report(_synthetic_doc())
    b = rep["buckets_us"]
    assert rep["wall_us"] == pytest.approx(100_000.0, rel=1e-6)
    assert b["fetch"] == pytest.approx(10_000.0, rel=1e-6)
    assert b["decode"] == pytest.approx(40_000.0, rel=1e-6)
    assert b["consume"] == pytest.approx(30_000.0, rel=1e-6)
    assert b["stall"] == pytest.approx(20_000.0, rel=1e-6)
    assert sum(b.values()) == pytest.approx(rep["wall_us"], rel=1e-9)
    assert rep["bottleneck"] == "decode"


def test_trace_report_critical_path_and_bandwidth():
    rep = trace_report.build_report(_synthetic_doc())
    longest = rep["critical_path"]["longest"]
    assert longest["rg"] == 0
    assert longest["total"] == pytest.approx(20_000 + 40_000 + 30_000,
                                             rel=1e-6)
    bw = rep["bandwidth"]
    assert bw["logical_bytes"] == 1_000_000
    assert bw["effective_bw_mbps"] == pytest.approx(10.0, rel=1e-3)


def test_trace_report_validator_rejects_malformed():
    assert trace_report.validate_trace({"traceEvents": "nope"})
    bad_dur = {"traceEvents": [
        {"name": "x", "ph": "X", "ts": 0, "dur": -5, "pid": 1, "tid": 1}],
        "displayTimeUnit": "ms"}
    assert any("dur" in e for e in trace_report.validate_trace(bad_dur))
    unbalanced = {"traceEvents": [
        {"name": "b", "ph": "B", "ts": 0, "pid": 1, "tid": 1}],
        "displayTimeUnit": "ms"}
    assert any("unclosed" in e
               for e in trace_report.validate_trace(unbalanced))
    bad_ph = {"traceEvents": [
        {"name": "x", "ph": "Q", "ts": 0, "pid": 1, "tid": 1}],
        "displayTimeUnit": "ms"}
    assert any("ph" in e for e in trace_report.validate_trace(bad_ph))


def test_trace_report_on_real_export(tab_file, tmp_path):
    out = str(tmp_path / "real.json")
    tr = trace.enable()
    sc = open_scanner(tab_file, columns=["v"], decode_backend="host")
    _, rep = run_overlapped(sc, _sum_consume, decode_workers=2)
    tr.export(out)
    trace.disable()
    doc = trace_report.load_trace(out)
    assert trace_report.validate_trace(doc) == []
    r = trace_report.build_report(doc)
    assert r["wall_us"] == pytest.approx(rep.measured_wall * 1e6,
                                         rel=0.10)
    assert r["bottleneck"] in ("fetch", "decompress", "decode",
                               "consume", "stall")
    assert sum(r["buckets_us"].values()) \
        == pytest.approx(r["wall_us"], rel=1e-6)
    assert r["dropped"] == 0
    with open(out, encoding="utf-8") as f:
        assert json.load(f)["displayTimeUnit"] == "ms"


# -- multi-tenant attribution (DESIGN.md §11) --------------------------------

def _tenant_doc():
    """gold fetches 0-20ms and decodes 20-60ms (one window hit); the
    shared ``-`` tenant consumes 60-90ms."""
    tr = trace.Tracer()
    e = tr.epoch
    tr.complete("scan", "scan", e, e + 0.100, scan="s", tenant="gold")
    tr.complete("fetch", "io", e, e + 0.020, scan="s", rg=0,
                io_dt=0.02, tenant="gold")
    tr.complete("decode", "decode", e + 0.020, e + 0.060, scan="s",
                rg=0, tenant="gold")
    tr.instant("window_hit", "io", scan="s", rg=1, tenant="gold")
    tr.complete("consume", "consume", e + 0.060, e + 0.090, scan="s",
                rg=0, logical_bytes=1)
    return tr.to_chrome()


def test_trace_report_per_tenant_breakdown():
    rep = trace_report.build_report(_tenant_doc())
    per = rep["per_tenant"]
    assert set(per) == {"gold", "-"}
    gold = per["gold"]
    assert gold["fetch"] == pytest.approx(20_000.0, rel=1e-6)
    assert gold["decode"] == pytest.approx(40_000.0, rel=1e-6)
    assert gold["busy_us"] == pytest.approx(60_000.0, rel=1e-6)
    assert gold["spans"] == 2          # the structural scan span is not
    assert gold["window_hits"] == 1    # a bucketed work span
    shared = per["-"]
    assert shared["consume"] == pytest.approx(30_000.0, rel=1e-6)
    assert shared["busy_us"] == pytest.approx(30_000.0, rel=1e-6)
    assert shared["window_hits"] == 0
    text = trace_report.format_report(rep)
    assert "tenant gold" in text
    assert "1 window hits" in text


def test_trace_report_per_tenant_absent_without_tenants():
    rep = trace_report.build_report(_synthetic_doc())
    # untagged runs collapse onto the shared tenant and the human
    # report omits the breakdown entirely
    assert set(rep["per_tenant"]) <= {"-"}
    assert "tenant" not in trace_report.format_report(rep)


def test_tenant_tagged_spans_and_depth_gauge_live(tab_file):
    from repro.core.scheduler import ScanService
    tr = trace.enable()
    svc = ScanService(workers=2)
    svc.register_tenant("gold", weight=4, max_active=2)
    try:
        sc = open_scanner(tab_file, columns=["v"], decode_backend="host")
        _, rep = run_overlapped(sc, _sum_consume, decode_workers=2,
                                service=svc, tenant="gold")
    finally:
        svc.shutdown()
    fetches = _spans(tr, "fetch")
    assert fetches and all(e.args.get("tenant") == "gold"
                           for e in fetches)
    (scan_span,) = _spans(tr, "scan")
    assert scan_span.args["tenant"] == "gold"
    # the queue-depth gauge exists and reads 0 once the scan released
    # its admission slot
    gauges = trace.registry().snapshot()["gauges"]
    assert gauges.get("scheduler.tenant_depth.gold") == 0
    per = trace_report.build_report(tr.to_chrome())["per_tenant"]
    assert per["gold"]["spans"] > 0
    assert per["gold"]["busy_us"] > 0
    assert rep.metrics.trace_events > 0


def test_result_cache_hit_instant_and_counter(tmp_path):
    from repro.dataset.result_cache import MISS, FragmentResultCache
    tr = trace.enable()
    before = trace.registry().snapshot()["counters"]
    cache = FragmentResultCache()
    cache.put("root", 0, "f0", "fp", 1.5)
    assert cache.get("root", 0, "f0", "fp") == 1.5
    assert cache.get("root", 0, "f1", "fp") is MISS
    after = trace.registry().snapshot()["counters"]
    assert after.get("result_cache.hits", 0) \
        - before.get("result_cache.hits", 0) == 1
    assert after.get("result_cache.misses", 0) \
        - before.get("result_cache.misses", 0) == 1
    hits = [e for e in tr.events() if e.name == "result_cache_hit"]
    assert len(hits) == 1 and hits[0].args["fragment"] == "f0"


# -- dataset layer -----------------------------------------------------------

def test_dataset_scan_trace_kwarg(tmp_path):
    from repro.dataset import plan_dataset_scan, write_dataset
    from repro.dataset.executor import run_dataset_scan
    line = _table(n=6_000, seed=3)
    ds = write_dataset(line, str(tmp_path / "ds"), CFG,
                       partition_by="k", how="range", fragments=2)
    plan = plan_dataset_scan(ds, columns=["v"])
    out = str(tmp_path / "ds.json")
    _, rep = run_dataset_scan(
        plan, _sum_consume, lambda a, b: a + b, window=2,
        open_opts={"decode_backend": "host"}, trace=out)
    assert trace.active() is None
    assert rep.trace_events > 0
    assert rep.registry_snapshot
    doc = trace_report.load_trace(out)
    assert trace_report.validate_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"fragment", "dataset_scan"} <= names


# -- the served path: front end, staging, round trips, device waits ----------

@pytest.fixture(scope="module")
def q6_file(tmp_path_factory):
    from repro.data import tpch
    d = tmp_path_factory.mktemp("trace_q6")
    cfg = ACCELERATOR_OPTIMIZED.replace(rows_per_rg=4_000,
                                        target_pages_per_chunk=4)
    return tpch.write_tpch(str(d), sf=0.002, config=cfg,
                           seed=5)["lineitem_path"]


def _served_q6(path):
    """One Q6 through the front end on a pallas scanner, with every
    ``block_until_ready`` counted by the function that called it."""
    from jax._src.array import ArrayImpl

    from repro.serve.engine import QueryFrontEnd
    callers = []
    real = ArrayImpl.block_until_ready

    def counted(self):
        callers.append(sys._getframe(1).f_code.co_name)
        return real(self)

    sc = open_scanner(path, columns=list(Q6_COLUMNS),
                      decode_backend="pallas")
    ArrayImpl.block_until_ready = counted
    try:
        with QueryFrontEnd(workers=1, window_bytes=0) as fe:
            acc, _ = fe.result(fe.submit("t0", "q6", sc, prune=False),
                               timeout=300)
    finally:
        ArrayImpl.block_until_ready = real
    return acc, callers


def test_served_q6_off_records_nothing_and_adds_no_device_wait(q6_file):
    acc_off, callers = _served_q6(q6_file)
    assert trace.active() is None and trace.followed() is None
    # decode's flush is the only wait with the recorder off
    flushes = ("finish_execute", "finalize")
    assert callers and set(callers) <= set(flushes)
    n_off = [callers.count(f) for f in flushes]

    tr = trace.enable()
    acc_on, callers = _served_q6(q6_file)
    assert acc_on == acc_off
    # the recorder adds no block of its own
    assert [callers.count(f) for f in flushes] == n_off
    assert set(callers) <= set(flushes)
    waits = [e.args["site"] for e in _spans(tr, "device_wait")]
    assert set(waits) == {"finalize", "q6_partial"}
    names = {e.name for e in tr.events()}
    assert {"queued", "pack", "stage", "consume"} <= names
    assert all(e.args["bytes"] > 0 for e in _spans(tr, "pack"))
    # the consume reads the decoded columns where they are: no copy to
    # the host and back, and no upload
    assert not _spans(tr, "to_host") and not _spans(tr, "to_device")


@pytest.mark.parametrize("kind", ["ready", "not_ready", "host"])
def test_on_device_moves_only_host_columns(kind, monkeypatch):
    import jax
    import jax.numpy as jnp
    from jax._src.array import ArrayImpl

    from repro.core.query import _on_device
    want = np.arange(1024, dtype=np.int32)
    if kind == "host":
        x = want.copy()
    else:
        x = jnp.asarray(want)
        x.block_until_ready()

        def no_wait(self):
            raise AssertionError("a device column was waited for")
        monkeypatch.setattr(ArrayImpl, "block_until_ready", no_wait)
        if kind == "not_ready":
            monkeypatch.setattr(ArrayImpl, "is_ready", lambda self: False)
    assert trace.active() is None
    off = _on_device(x)
    tr = trace.enable()
    on = _on_device(x)
    for out in (off, on):
        assert isinstance(out, jax.Array)
        assert np.array_equal(np.asarray(out), want)
    spans = [e for e in tr.events() if e.ph == "X"]
    if kind == "host":
        # uploaded once per call, and only the call with the recorder on
        # records it
        assert [e.name for e in spans] == ["to_device"]
        assert spans[0].args["bytes"] == x.nbytes == 4096
    else:
        # a device column comes back in place: no copy, no span, no wait
        assert off is x and on is x
        assert spans == []


def test_front_end_follows_the_profiler(q6_file, tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        _served_q6(q6_file)
    finally:
        jax.profiler.stop_trace()
    # the front end's shutdown turned it off; it is handed over once
    assert trace.active() is None
    tr = trace.followed()
    assert tr is not None and tr.cap == trace.PROFILER_CAP
    assert trace.followed() is None
    assert {e.name for e in tr.events()} >= {"queued", "stage"}
    # no profiler session: the next served query records nothing
    _served_q6(q6_file)
    assert trace.active() is None and trace.followed() is None


def test_followed_recorder_honours_the_cap_and_is_let_go(monkeypatch,
                                                         tmp_path):
    import jax
    monkeypatch.setenv("REPRO_TRACE_CAP", "4096")
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr = trace.follow_profiler()
        assert tr is not None and tr.cap == 4096
        assert trace.follow_profiler() is tr and trace.active() is tr
    finally:
        jax.profiler.stop_trace()
    # the session ended and nothing took the recorder: the next query's
    # check turns it off and lets it go
    assert trace.follow_profiler() is None
    assert trace.active() is None and trace.followed() is None
    # the same for one a front end's shutdown already turned off
    jax.profiler.start_trace(str(tmp_path / "again"))
    try:
        assert trace.follow_profiler() is not None
        trace.stop_following()
    finally:
        jax.profiler.stop_trace()
    assert trace.active() is None
    assert trace.follow_profiler() is None and trace.followed() is None


def test_trace_report_buckets_the_new_spans():
    for name in ("pack", "stage", "device_wait", "to_host", "to_device"):
        assert name in trace_report.BUCKET_OF
    assert "queued" not in trace_report.BUCKET_OF   # front-end framing


def test_stage_counts_only_the_bytes_it_moves():
    from repro.core.decode_plan import _stage
    from repro.kernels.dict_decode import CachedDictionary
    arena = np.arange(256, dtype=np.uint32).reshape(2, 128)
    d = CachedDictionary(np.arange(16, dtype=np.float32))
    out = _stage(None, 0.0, arena, d)        # recorder off: staged alone
    assert np.array_equal(np.asarray(out[0]), arena)
    assert out[1] is d.device and d.on_device
    tr = trace.enable()
    fresh = CachedDictionary(np.arange(8, dtype=np.int32))
    _stage(tr, time.perf_counter(), arena, d, fresh)
    pack, stage = _spans(tr, "pack"), _spans(tr, "stage")
    assert [p.args["bytes"] for p in pack] == [1024 + 64 + 32]
    # the cached dictionary already on the device moves nothing
    assert [s.args["bytes"] for s in stage] == [1024 + 32]
    assert pack[0].ts + pack[0].dur <= stage[0].ts + 1e-9


# -- the exact DECIMAL reduce --------------------------------------------------

@pytest.mark.parametrize("fused", [False, True])
def test_decimal_q6_spans_each_scanned_row_group_and_counts(tmp_path,
                                                           fused):
    """Q6 over INT64 DECIMAL cents, traced: one ``exact_sum`` span with
    ``rows`` and ``blocks`` per row group the zone maps keep, the exact
    query counted, each INT64 chunk counted as narrowed on the device,
    and a fused request counted as declined."""
    from repro.core.query import EXACT_BLOCK
    from repro.core.schema import Field, LogicalType, PhysicalType, Schema

    def dec(name):
        return Field(name, PhysicalType.INT64, LogicalType.DECIMAL, 2)

    n = 4_500
    rng = np.random.default_rng(16)
    tbl = Table({
        # sorted dates: the first row group ends before 1994 and is pruned
        "l_shipdate": np.sort(rng.integers(300, 1500, n)).astype(np.int32),
        "l_discount": rng.integers(0, 11, n).astype(np.int64),
        "l_quantity": rng.integers(1, 51, n).astype(np.int64) * 100,
        "l_extendedprice": rng.integers(90_000, 10_494_951,
                                        n).astype(np.int64)},
        Schema([Field("l_shipdate", PhysicalType.INT32, LogicalType.DATE),
                dec("l_discount"), dec("l_quantity"),
                dec("l_extendedprice")]))
    path = str(tmp_path / "dec.tab")
    meta = write_table(tbl, path, CFG)
    kept = [rg for rg in meta.row_groups
            if rg.column("l_shipdate").stats["max"] >= 731
            and rg.column("l_shipdate").stats["min"] < 1096]
    assert 0 < len(kept) < len(meta.row_groups)
    sc = open_scanner(path, columns=list(Q6_COLUMNS),
                      decode_backend="pallas")
    tr = trace.enable()
    q6(sc, fused=fused)
    spans = _spans(tr, "exact_sum")
    assert len(spans) == len(kept)
    assert all(e.cat == "consume" for e in spans)
    assert sorted(e.args["rows"] for e in spans) == \
        sorted(rg.n_rows for rg in kept)
    assert all(e.args["blocks"] == -(-e.args["rows"] // EXACT_BLOCK)
               for e in spans)
    counters = trace.registry().snapshot()["counters"]
    assert counters["decimal_exact_queries"] == 1
    assert counters["int64_narrowed_chunks"] == 3 * len(kept)
    assert counters.get("fused_declined_decimal", 0) == int(fused)
