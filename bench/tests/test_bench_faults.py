"""The run's comparison catches a broken timed path: each fault is
planted in the program underneath a tiny CPU run, and ``correct`` has to
come out false.  The cells have no training state and run on one chip,
so the faults that apply are an answer altered where it is produced and
half of the row groups left out of the scan."""

from __future__ import annotations

import jax.numpy as jnp
import pytest
from bench.tests.helpers import cpu_ok, last_json


def _alter_q6(monkeypatch):
    import repro.core.query as query
    real = query._q6_jnp
    monkeypatch.setattr(query, "_q6_jnp",
                        lambda *a: real(*a) * jnp.float32(1.001))


def _alter_q12(monkeypatch):
    import repro.core.query as query
    real = query._q12_probe
    monkeypatch.setattr(query, "_q12_probe", lambda *a: real(*a) + jnp.array(
        [1, 0, 0, 0], jnp.int32))


def _drop_half(monkeypatch):
    import repro.core.query as query
    real = query.run_overlapped

    def run_overlapped(scanner, consume, *a, **kw):
        def every_other(acc, rg_index, cols):
            return acc if rg_index % 2 else consume(acc, rg_index, cols)
        return real(scanner, every_other, *a, **kw)

    monkeypatch.setattr(query, "run_overlapped", run_overlapped)


@pytest.mark.parametrize("cell,fault", [
    ("q6.sf10_gpu_aware", _alter_q6),
    ("q6.sf10_gpu_aware", _drop_half),
    ("q12.sf1_gpu_aware", _alter_q12),
    ("q12.sf1_gpu_aware", _drop_half),
])
def test_fault_makes_the_run_incorrect(bench_run, capsys, monkeypatch,
                                       cell, fault):
    fault(monkeypatch)
    rc = bench_run.main(["--workload", cell, "--seed", "4242",
                         "--seconds", "0.5", "--trace", "0"],
                        require=cpu_ok)
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    last = last_json(out)
    assert last["correct"] is False
    (name, check), = last["checks"].items()
    assert check["value"] > check["limit"]


def test_an_answer_that_never_comes_fails_the_run(bench_run, capsys,
                                                   monkeypatch):
    """A query still unanswered a grace period after the window closed
    counts as failed, and the run is not correct."""
    import itertools
    import time

    import repro.core.query as query
    real = query._q6_jnp
    calls = itertools.count()

    def stuck(*a):        # the warm-up's three row groups pass
        if next(calls) >= 3:
            time.sleep(1.0)
        return real(*a)

    monkeypatch.setattr(query, "_q6_jnp", stuck)
    monkeypatch.setattr(bench_run, "LATE_S", 0.2)
    rc = bench_run.main(["--workload", "q6.sf10_gpu_aware", "--seed", "77",
                         "--seconds", "0.1", "--trace", "0"],
                        require=cpu_ok)
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    last = last_json(out)
    assert last["correct"] is False and last["failed"] >= 1
