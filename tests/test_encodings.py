import numpy as np
import numpy.testing as npt
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # deterministic fallback shim (hypothesis not installed)
    from _hypothesis_fallback import given, settings, st

from repro.core import encodings as enc
from repro.core.config import EncodingPolicy, FileConfig
from repro.core.schema import Field, PhysicalType
from repro.core.table import StringColumn


def _field(pt):
    return Field("c", pt)


def _roundtrip_page(encoding, values, field):
    page = enc.encode_chunk_with(encoding, values, field,
                                 [(0, len(values) if isinstance(
                                     values, StringColumn)
                                   else values.shape[0])])
    assert page is not None
    dict_vals = None
    if page.dict_page is not None:
        dict_vals = enc.decode_plain_page(
            page.dict_page.payload, page.dict_page.n_values, field,
            page.dict_page.extra)
    out = enc.decode_page(page.encoding, page.pages[0].payload,
                          page.pages[0].n_values, field,
                          page.pages[0].extra, dict_vals)
    return out


@pytest.mark.parametrize("dtype,pt", [
    (np.int32, PhysicalType.INT32), (np.int64, PhysicalType.INT64)])
def test_delta_roundtrip(dtype, pt):
    rng = np.random.default_rng(0)
    vals = np.cumsum(rng.integers(-5, 100, 5000)).astype(dtype)
    out = _roundtrip_page(enc.Encoding.DELTA_BINARY_PACKED, vals,
                          _field(pt))
    npt.assert_array_equal(out, vals)


def test_delta_large_int64():
    vals = np.array([2 ** 55, -2 ** 50, 0, 2 ** 62, -2 ** 61, 17],
                    dtype=np.int64)
    out = _roundtrip_page(enc.Encoding.DELTA_BINARY_PACKED, vals,
                          _field(PhysicalType.INT64))
    npt.assert_array_equal(out, vals)


def test_rle_roundtrip():
    vals = np.repeat(np.arange(30, dtype=np.int32), 111)
    out = _roundtrip_page(enc.Encoding.RLE, vals, _field(PhysicalType.INT32))
    npt.assert_array_equal(out, vals)


def test_rle_bool():
    rng = np.random.default_rng(1)
    vals = rng.random(4000) < 0.01
    out = _roundtrip_page(enc.Encoding.RLE, vals,
                          _field(PhysicalType.BOOLEAN))
    npt.assert_array_equal(out, vals)


@pytest.mark.parametrize("dtype,pt", [
    (np.float32, PhysicalType.FLOAT), (np.float64, PhysicalType.DOUBLE)])
def test_bss_roundtrip(dtype, pt):
    rng = np.random.default_rng(2)
    vals = rng.normal(size=3333).astype(dtype)
    out = _roundtrip_page(enc.Encoding.BYTE_STREAM_SPLIT, vals, _field(pt))
    npt.assert_array_equal(out, vals)


def test_dict_numeric_and_string():
    rng = np.random.default_rng(3)
    ints = rng.integers(0, 50, 2000).astype(np.int32)
    out = _roundtrip_page(enc.Encoding.RLE_DICTIONARY, ints,
                          _field(PhysicalType.INT32))
    npt.assert_array_equal(out, ints)
    strs = StringColumn.from_pylist([f"v{i % 9}" for i in range(500)])
    out = _roundtrip_page(enc.Encoding.RLE_DICTIONARY, strs,
                          Field("s", PhysicalType.BYTE_ARRAY))
    assert out.to_pylist() == strs.to_pylist()


def test_dlba_roundtrip():
    strs = StringColumn.from_pylist(
        [("x" * (i % 37)) + str(i) for i in range(800)])
    out = _roundtrip_page(enc.Encoding.DELTA_LENGTH_BYTE_ARRAY, strs,
                          Field("s", PhysicalType.BYTE_ARRAY))
    assert out.to_pylist() == strs.to_pylist()


def test_candidate_sets_small():
    """The paper's feasibility claim: < 5 candidates per type."""
    for pt in PhysicalType:
        if pt == PhysicalType.BYTE_ARRAY:
            f = Field("s", pt)
        else:
            f = _field(pt)
        cands = enc.candidate_encodings(f, EncodingPolicy.FLEX)
        assert 1 <= len(cands) <= 4, (pt, cands)


def test_selection_picks_smallest():
    cfg = FileConfig(encodings=EncodingPolicy.FLEX)
    # sorted ints: DELTA should beat PLAIN and DICT
    vals = np.arange(100_000, dtype=np.int64)
    ce = enc.select_chunk_encoding(vals, _field(PhysicalType.INT64),
                                   [(0, 100_000)], cfg)
    assert ce.encoding == enc.Encoding.DELTA_BINARY_PACKED
    # low-cardinality floats: DICT
    rng = np.random.default_rng(4)
    fv = rng.choice(np.array([1.5, 2.5, 3.5], np.float32), 100_000)
    ce = enc.select_chunk_encoding(fv, _field(PhysicalType.FLOAT),
                                   [(0, 100_000)], cfg)
    assert ce.encoding == enc.Encoding.RLE_DICTIONARY
    # long runs: RLE wins
    rv = np.repeat(np.arange(10, dtype=np.int32), 10_000)
    ce = enc.select_chunk_encoding(rv, _field(PhysicalType.INT32),
                                   [(0, 100_000)], cfg)
    assert ce.encoding == enc.Encoding.RLE


def test_v1_only_restricts():
    vals = np.arange(1000, dtype=np.int32)
    cfg = FileConfig(encodings=EncodingPolicy.V1_ONLY)
    ce = enc.select_chunk_encoding(vals, _field(PhysicalType.INT32),
                                   [(0, 1000)], cfg)
    assert ce.encoding in (enc.Encoding.PLAIN, enc.Encoding.RLE_DICTIONARY)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(-2 ** 31 + 1, 2 ** 31 - 1), min_size=1,
                max_size=400))
def test_delta_property_int32(values):
    vals = np.array(values, dtype=np.int64)  # deltas may exceed int32
    out = _roundtrip_page(enc.Encoding.DELTA_BINARY_PACKED, vals,
                          _field(PhysicalType.INT64))
    npt.assert_array_equal(out, vals)


@settings(max_examples=30, deadline=None)
@given(st.lists(st.text(max_size=20), min_size=1, max_size=100),
       st.sampled_from([enc.Encoding.PLAIN,
                        enc.Encoding.DELTA_LENGTH_BYTE_ARRAY,
                        enc.Encoding.RLE_DICTIONARY]))
def test_string_encodings_property(values, encoding):
    col = StringColumn.from_pylist(values)
    out = _roundtrip_page(encoding, col, Field("s", PhysicalType.BYTE_ARRAY))
    assert out.to_pylist() == col.to_pylist()


def _loop_unique_strings(values):
    """The per-value dictionary build the writer used before: first
    occurrence order, one Python dict lookup per row."""
    table, order = {}, []
    codes = np.empty(len(values), dtype=np.int64)
    for i, b in enumerate(values.to_pylist()):
        code = table.get(b)
        if code is None:
            code = table[b] = len(order)
            order.append(b)
        codes[i] = code
    return StringColumn.from_pylist(order), codes


def _seeded_strings(seed, n):
    """Repeated TPC-H-like values, empty strings, values that differ only
    by trailing NULs or past the first 8 bytes, and random bytes."""
    rng = np.random.default_rng(seed)
    pool = [b"", b"\x00", b"A", b"A\x00", b"MAIL", b"DELIVER IN PERSON",
            b"DELIVER IN PERSOM", b"12345678", b"12345678\x00",
            b"123456789", b"x" * 40]
    vals = [pool[i] for i in rng.integers(0, len(pool), n)]
    vals += [rng.integers(0, 256, int(k)).astype(np.uint8).tobytes()
             for k in rng.integers(0, 20, n // 10)]
    rng.shuffle(vals)
    return StringColumn.from_pylist(vals)


@pytest.mark.parametrize("seed,n", [(0, 0), (1, 1), (2, 7), (3, 2_000),
                                    (4, 70_000)])
def test_string_dictionary_matches_the_loop(seed, n):
    values = _seeded_strings(seed, n)
    want_dict, want_codes = _loop_unique_strings(values)
    got_dict, got_codes = enc._unique_with_codes(values)
    npt.assert_array_equal(got_dict.offsets, want_dict.offsets)
    npt.assert_array_equal(got_dict.payload, want_dict.payload)
    npt.assert_array_equal(got_codes, want_codes)


def test_string_dictionary_chunk_bytes_match_the_loop(monkeypatch,
                                                      tmp_path):
    """A dictionary-encoded string chunk, and a whole file written with
    the writer's smallest-wins choice (dictionary here), are byte for
    byte what the loop wrote."""
    from repro.core.config import ACCELERATOR_OPTIMIZED
    from repro.core.table import Table
    from repro.core.writer import write_table
    values = _seeded_strings(5, 30_000)
    field = Field("s", PhysicalType.BYTE_ARRAY)
    slices = [(0, 10_000), (10_000, 25_000), (25_000, len(values))]
    cfg = ACCELERATOR_OPTIMIZED.replace(rows_per_rg=20_000,
                                        target_pages_per_chunk=3)

    def written(name):
        chunk = enc.encode_dict_chunk(values, field, slices, 1.0)
        meta = write_table(Table({"s": values}), str(tmp_path / name), cfg)
        assert {c.encoding for rg in meta.row_groups for c in rg.columns} \
            == {enc.Encoding.RLE_DICTIONARY}
        return chunk, (tmp_path / name).read_bytes()

    got_chunk, got_file = written("vectorised.tab")
    monkeypatch.setattr(enc, "_unique_strings", _loop_unique_strings)
    want_chunk, want_file = written("loop.tab")
    assert got_chunk.dict_page.payload == want_chunk.dict_page.payload
    assert [p.payload for p in got_chunk.pages] == \
        [p.payload for p in want_chunk.pages]
    assert got_file == want_file
