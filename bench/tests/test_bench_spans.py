"""The recorder's spans beside the device trace (``bench/span_reduce.py``)
and the per-layer readers that sum them."""

from __future__ import annotations

import gzip
import json
import os
import time
import types

import pytest
from bench import span_reduce, trace_reduce
from bench.tests.helpers import cpu_ok, last_json

from repro.core import trace

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000
READERS = {"queue_wait_ms_per_query": {"queued"},
           "stage_ms_per_query": {"stage"},
           "host_roundtrip_ms_per_query": {"to_host", "to_device"},
           "device_wait_ms_per_query": {"device_wait"}}


@pytest.fixture(autouse=True)
def _clean_trace_state():
    trace.reset()
    yield
    trace.reset()


def test_recorder_span_maps_onto_the_profiler_clock(tmp_path):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    tr = trace.Tracer(cap=64)                  # anchors as it is created
    time.sleep(0.05)
    with jax.profiler.TraceAnnotation("probe"):
        with tr.span("probe_span", "test"):
            time.sleep(0.03)
    time.sleep(0.05)
    trace.clock_anchor()
    jax.profiler.stop_trace()
    anchors = span_reduce.anchors(str(tmp_path))
    assert len(anchors) >= 2
    clock = span_reduce.Clock(anchors)
    assert clock.residual_ns < 1 * MS
    cut = trace_reduce.extract(str(tmp_path))
    (ann,) = [h for h in cut["host"] if h[0] == "probe"]
    (span,) = span_reduce.spans_of(tr)
    mapped = clock.span(span)
    assert abs(mapped.start - ann[1]) < 1 * MS
    assert abs(mapped.end - (ann[1] + ann[2])) < 1 * MS


def test_clock_fits_a_drifting_offset_clock():
    # the profiler runs 50 ppm fast and 3 s behind perf_counter
    pcs = [10**12 + k * 10**9 for k in range(5)]
    pairs = [[pc, round((pc - 3 * 10**9) * (1 + 5e-5))] for pc in pcs]
    clock = span_reduce.Clock(pairs)
    assert clock.slope == pytest.approx(1 + 5e-5, rel=1e-9)
    assert clock.residual_ns < 2
    assert clock.ns(1002.5) == pytest.approx((1002.5e9 - 3e9) * (1 + 5e-5),
                                             abs=2)


def _cut():
    """100 ms window; one chip busy 10-40, 50-60 ms: idle 0-10, 40-50,
    60-100 ms."""
    return {"window": [0, 100 * MS],
            "devices": {"/device:TPU:0": [["%a = x", 10 * MS, 30 * MS],
                                          ["%b = y", 50 * MS, 10 * MS]]},
            "host": [["bench_query q6 c0", 0, 100 * MS],
                     ["DelinearizeUsingTranspose", 62 * MS, 20 * MS]],
            "anchors": [[7 * 10**12, 0], [7 * 10**12 + 100 * MS, 100 * MS]]}


def _at(ms):     # perf_counter seconds that the cut's anchors put at ms
    return 7000.0 + ms / 1e3


def _span(name, a, b):
    return span_reduce.Span(name, _at(a), _at(b))


def test_idle_by_stage_charges_the_innermost_leaf_span():
    spans = [_span("scan", 0, 100),         # framing: never charged
             _span("decode", 0, 45), _span("pack", 5, 8),
             _span("to_host", 60, 90)]
    clock = span_reduce.Clock(_cut()["anchors"])
    assert clock.slope == pytest.approx(1.0) and clock.residual_ns < 1
    idle = span_reduce.idle_by_stage(_cut(), spans, clock)
    assert idle == pytest.approx({"to_host": 0.030, "decode": 0.012,
                                  "none": 0.015, "pack": 0.003})
    assert list(idle) == ["to_host", "none", "decode", "pack"]


def test_gaps_keep_their_order_and_gain_a_stage_name():
    clock = span_reduce.Clock(_cut()["anchors"])
    named = span_reduce.label_gaps(_cut(), [_span("to_host", 60, 90)],
                                   clock)
    plain = trace_reduce.reduce(_cut())["idle_gaps"]
    assert [d for _, d in named] == [d for _, d in plain]
    assert named[0] == ["to_host / DelinearizeUsingTranspose",
                        pytest.approx(0.040)]
    assert [n for n, _ in named[1:]] == [n for n, _ in plain[1:]]


def test_recorded_v5e_cut_keeps_its_gaps_and_labels():
    """With no program span, the recorded ``q12.sf1_gpu_aware`` cut's
    gaps come out as ``trace_reduce.reduce`` names them."""
    with gzip.open(os.path.join(DATA, "q12_v5e_cut.json.gz"), "rt") as f:
        cut = json.load(f)["cut"]
    named = span_reduce.label_gaps(cut, [], None)
    assert named == trace_reduce.reduce(cut)["idle_gaps"]


def test_span_coverage_per_query():
    records = [types.SimpleNamespace(submitted=_at(0), done=_at(40)),
               types.SimpleNamespace(submitted=_at(50), done=_at(100))]
    spans = [_span("scan", 0, 100), _span("queued", 0, 1),
             _span("decode", 2, 30), _span("consume", 20, 40),
             _span("to_host", 50, 75)]
    assert span_reduce.span_coverage(records, spans) == pytest.approx(
        [0.975, 0.5])


def _fake_recorder(events, dropped=0):
    return types.SimpleNamespace(
        epoch=100.0, dropped=dropped, cap=1 << 20,
        events=lambda: [types.SimpleNamespace(name=n, ph=ph, ts=ts, dur=d)
                        for n, ph, ts, d in events])


def _run(*windows):
    return types.SimpleNamespace(records=[
        types.SimpleNamespace(submitted=100.0 + a, done=100.0 + b)
        for a, b in windows])


def test_readers_sum_the_window_spans_per_query(monkeypatch):
    from bench import run
    events = [("queued", "X", 1.0, 0.001), ("queued", "X", 3.0, 0.003),
              ("stage", "X", 1.5, 0.010), ("to_host", "X", 1.6, 0.100),
              ("to_device", "X", 1.8, 0.050),
              ("device_wait", "X", 2.0, 0.200),
              ("device_wait", "X", 9.0, 5.0),     # after the window
              ("kernel_launch", "i", 1.7, 0.0)]
    monkeypatch.setattr(span_reduce, "recorder",
                        lambda: _fake_recorder(events))
    r = _run((1.0, 2.5), (3.0, 4.0))
    got = {m: run.load_reader(m)(r) for m in READERS}
    assert got == pytest.approx({"queue_wait_ms_per_query": 2.0,
                                 "stage_ms_per_query": 5.0,
                                 "host_roundtrip_ms_per_query": 75.0,
                                 "device_wait_ms_per_query": 100.0})
    assert all(run.load_reader(m)(_run()) is None for m in READERS)


def test_readers_fail_a_run_whose_recorder_dropped_events(monkeypatch):
    from bench import run
    monkeypatch.setattr(span_reduce, "recorder",
                        lambda: _fake_recorder([], dropped=3))
    for m in READERS:
        with pytest.raises(RuntimeError, match="dropped 3"):
            run.load_reader(m)(_run((0.0, 1.0)))


def test_readers_read_nothing_from_a_program_without_the_recorder(
        monkeypatch):
    from bench import run
    monkeypatch.delattr(trace, "followed")
    for m in READERS:
        assert run.load_reader(m)(_run((0.0, 1.0))) is None


def test_readers_of_one_run_share_one_hand_over(monkeypatch):
    from bench import run
    handed = [_fake_recorder([("stage", "X", 1.5, 0.010),
                              ("queued", "X", 1.0, 0.002)])]
    monkeypatch.setattr(span_reduce, "recorder",
                        lambda: handed.pop() if handed else None)
    r = _run((1.0, 2.5))
    got = {m: run.load_reader(m)(r) for m in READERS}
    assert got == pytest.approx({"queue_wait_ms_per_query": 2.0,
                                 "stage_ms_per_query": 10.0,
                                 "host_roundtrip_ms_per_query": 0.0,
                                 "device_wait_ms_per_query": 0.0})
    # another run finds the recorder taken
    assert run.load_reader("stage_ms_per_query")(_run((1.0, 2.5))) is None


def test_traced_cell_reads_the_spans_and_lets_the_recorder_go(
        bench_run, capsys, monkeypatch):
    monkeypatch.setattr(bench_run, "peaks_for", lambda kind: {})
    rc = bench_run.main(["--workload", "q6.sf10_gpu_aware", "--seed",
                         str(2**31 + 777), "--seconds", "1", "--trace",
                         "1"], require=cpu_ok)
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    last = last_json(out)
    assert last["correct"] is True
    assert set(READERS) <= set(last["metrics"])
    assert last["metrics"]["stage_ms_per_query"]["value"] > 0
    # the served pallas path consumes decoded columns where they are: no
    # column goes to the host and back
    assert last["metrics"]["host_roundtrip_ms_per_query"]["value"] == 0
    # the readers took the recorder; the program keeps none
    assert trace.active() is None and trace.followed() is None
