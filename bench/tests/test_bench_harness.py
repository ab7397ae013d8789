"""The harness end to end on the CPU, and its refusals."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest
from bench.tests.helpers import ROOT, cpu_ok, last_json

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files_by_name(cell):
    from bench import run
    for trace in (False, True):
        c = run.load_cell(cell, trace)
        assert c.chips in (1, 4)
        assert c.traffic["clients"]
        for m in c.metrics:
            assert callable(run.load_reader(m["name"]))
        from bench import queries
        for client in c.traffic["clients"]:
            assert queries.CHECK[client["query"]] in c.limits
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    cfg = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    assert os.path.isfile(os.path.join(ROOT, cfg["file"]))


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for cell in CELLS:
        e2e = {m["name"] for m in SPEC["end_to_end"]
               if cell in m.get("workloads", [cell])}
        layer = [m for m in SPEC["per_layer"]
                 if cell in m.get("workloads", [cell])]
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        assert all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("cell,trace", [("q6.sf10_gpu_aware", 0),
                                        ("q12.sf1_gpu_aware", 1)])
def test_tiny_run_prints_a_correct_result_line(bench_run, capsys,
                                               monkeypatch, cell, trace):
    if trace:     # the CPU has no device plane: device metrics are left out
        monkeypatch.setattr(bench_run, "peaks_for", lambda kind: {})
    rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 12345),
                         "--seconds", "1", "--trace", str(trace)],
                        require=cpu_ok)
    out, err = capsys.readouterr()
    assert rc == 0, err[-2000:]
    last = last_json(out)
    assert list(last) == KEYS
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert last["device"]["platform"] == "cpu"
    names = {m["name"] for m in SPEC["per_layer" if trace else "end_to_end"]}
    want = (names - {"device_idle_share", "scan_roofline_share"}
            if trace else names)
    assert set(last["metrics"]) == want
    tail = err.strip().splitlines()[-len(last["checks"]):]
    for line, (name, check) in zip(tail, last["checks"].items()):
        assert line.startswith(f"check {name}: ")
        assert check["value"] <= check["limit"]


def test_same_seed_same_data(bench_run, tmp_path):
    from bench import queries
    cell = bench_run.load_cell("q12.sf1_gpu_aware", False)
    needed = {t: c for t, c in queries.COLUMNS["q12"].items()}
    seen = []
    for d in ("a", "b"):
        os.makedirs(tmp_path / d)
        paths, _, _ = bench_run.write_tables(cell.config, 7, str(tmp_path / d),
                                             needed)
        seen.append({t: open(p, "rb").read() for t, p in paths.items()})
    assert seen[0] == seen[1]


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_refuses_to_run_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and 1 <= SPEC["run_seconds"] <= 51
    n = 24   # a full check with every cell there can ever be fits
    assert (2 + 14 * n) * (SPEC["run_seconds"] + 60) + n * 180 + 1200 \
        <= 43200
    text = {"why", "layer", "source"}
    for kind, keys in (("configs", {"name", "source", "file", "reduced",
                                    "why"}),
                       ("workloads", {"name", "config", "traffic", "chips",
                                      "why"}),
                       ("end_to_end", {"name", "unit", "better", "bound",
                                       "source"}),
                       ("per_layer", {"name", "unit", "better", "source",
                                      "layer", "moves"})):
        entries = SPEC[kind]
        assert len({e["name"] for e in entries}) == len(entries)
        for e in entries:
            assert set(e) - {"workloads"} == keys, e["name"]
            assert NAME.match(e["name"])
            for k in text & set(e):
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
            if "bound" in e:
                assert 0.01 <= e["bound"] <= 0.25
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    assert all(NAME.match(k) for c in SPEC["configs"] for k in c["reduced"])
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) \
        == len(SPEC["workloads"])
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) <= max(
        1, len(SPEC["workloads"]) // 2)


def test_readers_read_nothing_from_an_empty_run():
    from bench import run
    empty = run.Run(records=[], window_s=51.0, setup_s=1.0,
                    logical_bytes={}, required_bytes={}, trace=None,
                    peaks=None)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        value = run.load_reader(m["name"])(empty)
        assert value is None or m["name"] == "setup_s"
