"""The control, the reference in the precision below the
configuration's, fails every cell's limit, and the reference itself
passes it (at a size a test run holds; ``bench/control.py`` reads the
control at each cell's own size)."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest
from bench.tests.helpers import ROOT

from bench import queries, tpch_gen

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def tables(sf, seed):
    chunks = list(tpch_gen.tpch_chunks(sf, seed))
    return {t: {c: np.concatenate([ch[t][c] for ch in chunks])
                for c, _, _ in tpch_gen.TABLES[t]} for t in tpch_gen.TABLES}


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_control_fails_and_reference_passes(cell, seed):
    with open(os.path.join(ROOT, "bench", "limits", f"{cell}.json")) as f:
        limits = json.load(f)
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    with open(os.path.join(ROOT, "bench", "traffic",
                           f"{w['traffic']}.json")) as f:
        mix = {c["query"] for c in json.load(f)["clients"]}
    t = tables(0.01, seed)
    for q in mix:
        want = queries.reference(q, t)
        limit = limits[queries.CHECK[q]]
        assert queries.error(q, want, want) <= limit
        assert queries.error(q, queries.control(q, t), want) > limit


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_control_in_the_programs_place_is_not_correct(bench_run, capsys,
                                                      monkeypatch, cell):
    """Every answer of a run replaced by the control's over the rows the
    run serves: the run's own comparison turns ``correct`` false."""
    from bench import control
    from bench.tests.helpers import cpu_ok, last_json
    seed = 2**31 + 77
    c = bench_run.load_cell(cell, False)
    mix = {x["query"] for x in c.traffic["clients"]}
    t = control.tables(c.config, seed, queries.columns_of(mix))
    monkeypatch.setattr(queries, "answer",
                        lambda q, result: queries.control(q, t))
    rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", "0.5", "--trace", "0"],
                        require=cpu_ok)
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    last = last_json(out)
    assert last["correct"] is False and last["failed"] == 0
    (name, check), = last["checks"].items()
    assert check["value"] > check["limit"]
