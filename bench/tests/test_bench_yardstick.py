"""The benchmark's frozen copies (generator, references, footer byte
counts) agree with the program's own at a small scale today."""

from __future__ import annotations

import os

import numpy as np
import pytest

from bench import footer, queries, tpch_gen

SF = 0.17          # two generator chunks of orders


@pytest.fixture(scope="module")
def both():
    from repro.data import tpch
    ours = list(tpch_gen.tpch_chunks(SF, 99))
    theirs = list(tpch.tpch_chunks(SF, 99, include_strings=False))
    return ours, theirs


def test_generator_matches_the_program(both):
    from repro.data import tpch
    ours, theirs = both
    assert len(ours) == len(theirs) == 2
    for o, (oc, lc) in zip(ours, theirs):
        for table, prog in (("orders", oc), ("lineitem", lc)):
            assert list(o[table]) == prog.schema.names
            for name, phys, logical in tpch_gen.TABLES[table]:
                f = prog.schema.field(name)
                assert (f.physical.name, f.logical.value) == (phys, logical)
                np.testing.assert_array_equal(o[table][name],
                                              np.asarray(prog[name]))
                assert o[table][name].dtype == np.asarray(prog[name]).dtype
    assert tpch_gen.N_SHIPMODES == len(tpch.SHIPMODES)


def test_references_match_the_program(both):
    from repro.core import query
    _, theirs = both
    line = {c: np.concatenate([np.asarray(lc[c]) for _, lc in theirs])
            for c in theirs[0][1].names}
    orders = {c: np.concatenate([np.asarray(oc[c]) for oc, _ in theirs])
              for c in theirs[0][0].names}
    tables = {"lineitem": line, "orders": orders}
    assert queries.reference("q6", tables) == query.q6_reference(line)
    assert queries.reference("q12", tables) == query.q12_reference(line,
                                                                   orders)
    assert (queries.D1994, queries.D1995) == (query.D_1994_01_01,
                                              query.D_1995_01_01)
    assert (queries.MAIL, queries.SHIP) == (query.SHIPMODE_MAIL,
                                            query.SHIPMODE_SHIP)
    assert queries.COLUMNS["q6"]["lineitem"] == query.Q6_COLUMNS
    assert queries.COLUMNS["q12"] == {
        "lineitem": query.Q12_LINEITEM_COLUMNS,
        "orders": query.Q12_ORDERS_COLUMNS}


def test_written_files_match_the_programs_writer(tmp_path):
    """The harness's streamed writing gives the bytes of the program's
    own ``write_tpch`` for the same seed and layout."""
    import sys
    from repro.core import ACCELERATOR_OPTIMIZED
    from repro.data import tpch
    sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))
    from bench import run
    layout = {"rows_per_rg": 3000, "target_pages_per_chunk": 4,
              "encodings": "flex", "codec": "gzip", "min_gain": 0.1,
              "level": 1}
    fc = ACCELERATOR_OPTIMIZED.replace(rows_per_rg=3000,
                                       target_pages_per_chunk=4)
    assert run.file_config(layout) == fc
    metas = tpch.write_tpch(str(tmp_path / "prog"), sf=0.002, config=fc,
                            seed=5, include_strings=False)
    os.makedirs(tmp_path / "bench")
    needed = {t: [c for c, _, _ in tpch_gen.TABLES[t]]
              for t in ("lineitem", "orders")}
    rows = {t: metas[t].num_rows for t in ("lineitem", "orders")}
    paths, ours, _ = run.write_tables(
        {"scale_factor": 0.002, "file_layout": layout, "rows": rows}, 5,
        str(tmp_path / "bench"), needed)
    for t in ("lineitem", "orders"):
        with open(metas[f"{t}_path"], "rb") as a, open(paths[t], "rb") as b:
            assert a.read() == b.read()
        prog = metas[t]
        cols = [c for c, _, _ in tpch_gen.TABLES[t]]
        assert footer.logical_bytes(ours[t], cols) == prog.logical_nbytes
        assert footer.required_stored_bytes(ours[t], cols, {}) == \
            prog.stored_bytes


def test_zone_maps_exclude_only_pages_no_row_can_pass(tmp_path):
    """A page whose shipdate range misses 1994 is not required; one whose
    range covers it is."""
    from repro.core import ACCELERATOR_OPTIMIZED, write_table
    from repro.core.table import Table
    n = 4000
    ship = np.where(np.arange(n) < 2000, 100, 800).astype(np.int32)
    cols = {"l_shipdate": ship, "l_discount": np.full(n, .06, np.float32),
            "l_quantity": np.full(n, 5, np.float32),
            "l_extendedprice": np.full(n, 10, np.float32)}
    path = str(tmp_path / "t.tab")
    write_table(Table(cols), path, ACCELERATOR_OPTIMIZED.replace(
        target_pages_per_chunk=2))
    meta = footer.read(path)
    every = footer.required_stored_bytes(meta, list(cols), {})
    need = footer.required_stored_bytes(meta, list(cols),
                                        queries.ZONES["q6"]["lineitem"])
    pages = meta["row_groups"][0]["columns"]
    second = sum(c["pages"][1]["stored_size"]
                 + (c["dict_page"] or {}).get("stored_size", 0)
                 for c in pages)
    assert need == second < every


def test_widths_script_writes_tpch_forms_of_the_same_rows(monkeypatch,
                                                          capsys, tmp_path):
    """``bench/widths.py`` names the codes as the program's generator
    does, and its exact decimal reference agrees with the float32 form's
    (the witness for the stand-in cells)."""
    import functools
    import json

    import repro.serve.engine as engine
    from repro.data import tpch

    from bench import run, widths
    assert widths.SHIPMODES == tpch.SHIPMODES
    assert widths.PRIORITIES == tpch.PRIORITIES
    real = run._json

    def tiny(path):
        d = real(path)
        if os.sep + "configs" + os.sep in path:
            d = dict(d, scale_factor=0.002,
                     rows={"lineitem": 11_000, "orders": 3_000},
                     file_layout=dict(d["file_layout"], rows_per_rg=5_000,
                                      target_pages_per_chunk=4))
        return d

    monkeypatch.setattr(run, "_json", tiny)
    monkeypatch.setattr(run, "CACHE", str(tmp_path))
    monkeypatch.setattr(engine, "QueryFrontEnd", functools.partial(
        engine.QueryFrontEnd, window_bytes=0))
    assert widths.main(["--config", "tpch_sf1_gpu_aware",
                        "--seeds", str(2**31 + 3)]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["query"] for x in lines] == ["q6", "q12"]
    for x in lines:
        assert x["witness_error"] < 1e-6
