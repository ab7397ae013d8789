"""The trace reduction on a hand-made cut and on a small recorded one."""

from __future__ import annotations

import gzip
import json
import os

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_hand_made_cut():
    ms = 1_000_000
    cut = {
        "window": [0, 100 * ms],
        "devices": {
            "/device:TPU:0": [
                ["%loop.1 = while(...)", 10 * ms, 30 * ms],
                ["%body.2 = fusion(...)", 12 * ms, 5 * ms],   # nested
                ["%k.3 = custom-call(...)", 50 * ms, 10 * ms],
                ["%k.3 = custom-call(...)", 95 * ms, 10 * ms],  # clipped
                ["%early = copy(...)", -20 * ms, 10 * ms],     # outside
            ],
            "/device:TPU:1": [["%k.3 = custom-call(...)", 0, 20 * ms]],
        },
        "host": [["bench_query q6 c0", 0, 100 * ms],
                 ["DeviceToHost", 65 * ms, 25 * ms]],
    }
    r = trace_reduce.reduce(cut)
    assert r["window_s"] == pytest.approx(0.1)
    # chip 0: 30 + 10 + 5 ms busy, chip 1: 20 ms; averaged over 2 chips
    assert r["busy_s"] == pytest.approx(0.0325)
    assert r["op_s"] == pytest.approx(0.0325)
    assert r["device_ops"][0] == ["k.3", pytest.approx(0.035)]
    assert r["device_ops"][1] == ["loop.1", pytest.approx(0.030)]
    assert "body.2" not in dict(r["device_ops"])
    # longest gap: chip 1's 20..100 ms, under the query only
    assert r["idle_gaps"][0] == ["bench_query q6 c0", pytest.approx(0.080)]
    # chip 0's 60..95 ms gap: the host was copying to the host
    assert ["DeviceToHost", pytest.approx(0.035)] in r["idle_gaps"]


def test_nothing_to_read():
    assert trace_reduce.reduce({"window": None, "devices": {}}) is None
    assert trace_reduce.reduce({"window": [0, 10], "devices": {
        "/device:TPU:0": [["%a = b", 20, 5]]}, "host": []}) is None


def test_recorded_cut_from_a_v5e():
    """A slice of a traced ``q12.sf1_gpu_aware`` run on one TPU v5e, with
    the values an independent sweep gave when it was cut."""
    with gzip.open(os.path.join(DATA, "q12_v5e_cut.json.gz"), "rt") as f:
        rec = json.load(f)
    r = trace_reduce.reduce(rec["cut"])
    for key, want in rec["expected"].items():
        assert r[key] == pytest.approx(want, rel=1e-12), key
    assert 0 < r["busy_s"] < r["window_s"]


def test_extract_on_a_host_trace(tmp_path):
    """A CPU trace has the window annotation and no TPU plane: the
    reduction reads nothing."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    cut = trace_reduce.extract(str(tmp_path))
    assert cut["window"] is not None and cut["devices"] == {}
    assert trace_reduce.reduce(cut) is None
