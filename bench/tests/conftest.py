"""Fixtures for the benchmark's CPU tests: the harness's modules on the
path, and a tiny rehearsal of a cell with the TPU check injected."""

from __future__ import annotations

import functools
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# a cell shrunk for the CPU: several row groups of a few pages each
TINY_SF = 0.002
TINY_ROWS_PER_RG = 5_000
TINY_PAGES = 4
TINY_ROWS = {"lineitem": 11_000, "orders": 3_000}   # ~12,000 lines drawn


@pytest.fixture
def bench_run(monkeypatch, tmp_path):
    """``bench/run.py`` set up to rehearse a cell on the CPU: tiny data,
    no persistent compile cache, no delivered-result window (at this size
    it would hold every row group, and no query would reach a kernel)."""
    from bench import run
    import repro.serve.engine as engine

    real_load = run.load_cell

    def tiny(name, trace):
        cell = real_load(name, trace)
        cell.config = dict(cell.config, scale_factor=TINY_SF,
                           rows=TINY_ROWS,
                           file_layout=dict(
                               cell.config["file_layout"],
                               rows_per_rg=TINY_ROWS_PER_RG,
                               target_pages_per_chunk=TINY_PAGES))
        return cell

    monkeypatch.setattr(run, "load_cell", tiny)
    monkeypatch.setattr(run, "use_compile_cache",
                        lambda: str(tmp_path / "cache"))
    monkeypatch.setattr(run, "CACHE", str(tmp_path / "bench_cache"))
    monkeypatch.setattr(engine, "QueryFrontEnd", functools.partial(
        engine.QueryFrontEnd, window_bytes=0))
    return run
