"""Column forms by a configuration's ``widths`` (``bench/forms.py``): the
program's forms write the files they always wrote, TPC-H's forms write
TPC-H's types, and the exact references and checks that go with them."""

from __future__ import annotations

import decimal
import hashlib
import json
import os

import numpy as np
import pytest
from bench.tests.helpers import ROOT

from bench import footer, forms, queries, tpch_gen

CONFIG = os.path.join(ROOT, "bench", "configs", "tpch_sf1_gpu_aware.json")
Q6_UNITS_LIMIT = 0.5     # the answer rounds to the exact DECIMAL(.,4) value


def small(widths, rows_per_rg=20_000, pages=10):
    with open(CONFIG) as f:
        cfg = json.load(f)
    return dict(cfg, widths=widths, scale_factor=0.01,
                rows={"lineitem": 59_000, "orders": 15_000},
                file_layout=dict(cfg["file_layout"], rows_per_rg=rows_per_rg,
                                 target_pages_per_chunk=pages))


@pytest.fixture(scope="module")
def tpch_files(tmp_path_factory):
    from bench import run
    d = tmp_path_factory.mktemp("tpch")
    needed = {t: [c for c, _, _ in tpch_gen.TABLES[t]]
              for t in ("lineitem", "orders")}
    return run.write_tables(small("tpch"), 2905150002, str(d), needed)


@pytest.fixture(scope="module")
def both_forms():
    """The same rows (SF 0.01, every column) in both forms."""
    chunks = list(tpch_gen.tpch_chunks(0.01, 2**31 + 15))
    return {w: {t: {c: np.concatenate(
                    [forms.convert(ch[t], w)[c] for ch in chunks])
                    for c, _, _ in tpch_gen.TABLES[t]}
                for t in tpch_gen.TABLES}
            for w in forms.WIDTHS}


def test_program_widths_write_the_files_they_always_wrote(tmp_path):
    """SHA-256 of the files that the harness wrote before a configuration
    stated its widths, for this configuration, size and seed."""
    from bench import run
    paths, _, _ = run.write_tables(small("program"), 2905150001,
                                   str(tmp_path),
                                   queries.columns_of({"q6", "q12"}))
    got = {t: hashlib.sha256(open(p, "rb").read()).hexdigest()
           for t, p in paths.items()}
    assert got == {
        "lineitem": "1c09762b84ad92bc0489151ac713804d"
                    "93a4fa2a8faa903db32f0bfbac53df5e",
        "orders": "530f6b7f238afff3a23a6d2f7093152d"
                  "b7def886ced74a237e2108359ddb8b7e"}


def test_tpch_footers_carry_tpch_types(tpch_files):
    _, metas, _ = tpch_files
    want = {
        "l_orderkey": (2, "none", 0), "l_partkey": (2, "none", 0),
        "l_suppkey": (2, "none", 0), "l_linenumber": (1, "none", 0),
        "l_quantity": (2, "decimal", 2), "l_extendedprice": (2, "decimal", 2),
        "l_discount": (2, "decimal", 2), "l_tax": (2, "decimal", 2),
        "l_returnflag": (6, "string", 0), "l_linestatus": (6, "string", 0),
        "l_shipdate": (1, "date", 0), "l_commitdate": (1, "date", 0),
        "l_receiptdate": (1, "date", 0), "l_shipinstruct": (6, "string", 0),
        "l_shipmode": (6, "string", 0),
        "o_orderkey": (2, "none", 0), "o_custkey": (2, "none", 0),
        "o_orderstatus": (6, "string", 0), "o_totalprice": (2, "decimal", 2),
        "o_orderdate": (1, "date", 0), "o_orderpriority": (6, "string", 0),
        "o_shippriority": (1, "none", 0)}
    got = {f["name"]: (f["physical"], f["logical"], f["decimal_scale"])
           for m in metas.values() for f in m["schema"]}
    assert got == want
    assert len(metas["lineitem"]["row_groups"]) == 3


def test_host_reader_returns_the_written_cents_and_strings(tpch_files):
    from repro.core.reader import TabFileReader
    from repro.core.table import StringColumn
    paths, metas, kept = tpch_files
    for t, cols in kept.items():
        table = TabFileReader(paths[t]).read_table(list(cols))
        assert table.num_rows == metas[t]["num_rows"]
        for c, v in cols.items():
            got = table[c]
            if v.dtype.kind == "S":
                assert isinstance(got, StringColumn)
                assert got.to_pylist() == v.tolist()
            else:
                assert got.dtype == v.dtype
                np.testing.assert_array_equal(got, v)
    li = kept["lineitem"]
    assert li["l_discount"].dtype == np.int64
    assert set(np.unique(li["l_discount"])) <= set(range(11))
    assert set(np.unique(li["l_shipinstruct"])) == {
        b"DELIVER IN PERSON", b"COLLECT COD", b"NONE", b"TAKE BACK RETURN"}


def test_byte_counts_at_tpch_widths(tpch_files):
    """Q6 reads 28 B a row; a string column counts each value's length
    plus PLAIN's 4-byte prefix, from the values written, and the footer
    alone cannot count it."""
    _, metas, kept = tpch_files
    li, n = metas["lineitem"], metas["lineitem"]["num_rows"]
    assert footer.logical_bytes(li, queries.COLUMNS["q6"]["lineitem"]) \
        == 28 * n
    text = forms.byte_array_bytes(kept["lineitem"])
    assert set(text) == set(forms.TEXT) & set(kept["lineitem"])
    modes = kept["lineitem"]["l_shipmode"]
    assert text["l_shipmode"] == sum(len(b) + 4 for b in modes.tolist())
    cols = queries.COLUMNS["q12"]["lineitem"]
    assert footer.logical_bytes(li, cols, text) == \
        8 * n + text["l_shipmode"] + 3 * 4 * n
    with pytest.raises(KeyError):
        footer.logical_bytes(li, cols)


def test_tpch_zones_keep_every_passing_row_and_strings_exclude_nothing(
        tpch_files, both_forms):
    _, metas, _ = tpch_files
    for w, t in both_forms.items():
        li = t["lineitem"]
        q6 = ((li["l_shipdate"] >= queries.D1994)
              & (li["l_shipdate"] < queries.D1995))
        if w == "tpch":
            q6 &= queries._q6_rows(li)
        else:
            q6 &= ((li["l_discount"] >= np.float32(0.05))
                   & (li["l_discount"] <= np.float32(0.07))
                   & (li["l_quantity"] < 24))
        for c, (lo, hi) in queries.ZONES[w]["q6"]["lineitem"].items():
            assert q6.any() and (li[c][q6] >= lo).all() and \
                (li[c][q6] <= hi).all()
    li = metas["lineitem"]
    every = footer.required_stored_bytes(li, ["l_shipmode"], {})
    assert footer.required_stored_bytes(
        li, ["l_shipmode"], {"l_shipmode": (b"MAIL", b"SHIP")}) == every
    assert footer.required_stored_bytes(
        li, queries.COLUMNS["q6"]["lineitem"],
        queries.ZONES["tpch"]["q6"]["lineitem"]) <= \
        footer.required_stored_bytes(li, queries.COLUMNS["q6"]["lineitem"], {})


def test_exact_q6_equals_a_decimal_sum(both_forms):
    li = both_forms["tpch"]["lineitem"]
    total = decimal.Decimal(0)
    for ship, disc, qty, price in zip(
            li["l_shipdate"].tolist(), li["l_discount"].tolist(),
            li["l_quantity"].tolist(), li["l_extendedprice"].tolist()):
        d = decimal.Decimal(disc).scaleb(-2)
        if (queries.D1994 <= ship < queries.D1995
                and decimal.Decimal("0.05") <= d <= decimal.Decimal("0.07")
                and decimal.Decimal(qty).scaleb(-2) < 24):
            total += decimal.Decimal(price).scaleb(-2) * d
    want = queries.reference("q6", both_forms["tpch"])
    assert total > 0 and decimal.Decimal(want).scaleb(-4) == total
    # the float32 form's float64 reference is near it, and not exact
    near = queries.reference("q6", both_forms["program"])
    assert abs(near - float(total)) < 1e-6 * float(total)


def test_cents_are_the_float32_values_rounded():
    v = np.array([0.05, 0.07, 24.0, 145000.0, 1234.56, 0.0], np.float32)
    got = forms.convert({"l_extendedprice": v}, "tpch")["l_extendedprice"]
    assert got.dtype == np.int64
    assert got.tolist() == [5, 7, 2400, 14500000, 123456, 0]


def test_q12_over_strings_equals_q12_over_codes(both_forms):
    want = queries.reference("q12", both_forms["program"])
    got = queries.reference("q12", both_forms["tpch"])
    assert got == want and min(want.values()) > 0
    assert set(want) == set(queries.Q12_GROUPS)


def test_program_generator_names_the_same_values():
    from repro.data import tpch
    assert forms.TEXT["l_shipmode"] == tpch.SHIPMODES
    assert forms.TEXT["o_orderpriority"] == tpch.PRIORITIES
    assert forms.TEXT["l_shipmode"][queries.MAIL] == "MAIL"
    assert forms.TEXT["l_shipmode"][queries.SHIP] == "SHIP"
    assert [forms.TEXT["o_orderpriority"][c].encode() for c, _ in
            queries.HIGH] == [b for _, b in queries.HIGH]


def test_exact_reference_passes_and_faults_fail_the_checks(both_forms):
    t = both_forms["tpch"]
    want = queries.reference("q6", t)
    as_float = want / 1e4     # how the program's float answer carries it
    assert queries.error("q6", as_float, want) < 0.01
    # the limit separates float32, not float64, which carries the answer
    assert queries.error("q6", queries.q6_float(t["lineitem"], np.float64),
                         want) < Q6_UNITS_LIMIT
    assert queries.error("q6", queries.control("q6", t), want) > \
        100 * Q6_UNITS_LIMIT
    assert queries.error("q6", (want + 1) / 1e4, want) > Q6_UNITS_LIMIT
    assert queries.error("q6", float("nan"), want) == float("inf")
    counts = queries.reference("q12", t)
    missing = {k: v for k, v in counts.items() if k != "SHIP_low"}
    assert queries.error("q12", missing, counts) == float("inf")
    assert queries.error("q12", dict(counts, MAIL_high=counts["MAIL_high"]
                                     + 1), counts) == 1.0
    assert queries.error("q12", counts, counts) == 0.0


def test_run_refuses_a_config_that_states_no_widths(monkeypatch):
    from bench import run
    real = run._json

    def without(path):
        d = real(path)
        d.pop("widths", None)
        return d

    monkeypatch.setattr(run, "_json", without)
    with pytest.raises(run.BenchError, match="states no widths"):
        run.load_cell("q12.sf1_gpu_aware", False)
