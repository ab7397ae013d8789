"""TPC-H Q6 over DECIMAL(15,2) columns stored as INT64 cents: exact on
every path (scanner, front end, fused modes, datasets), compared with the
integer oracle ``q6_decimal_reference`` by ``==``, never within a
tolerance; PLAIN INT64 decoded on the device; the float path of float32
decimals unchanged."""

import decimal

import numpy as np
import pytest

from repro.core import ACCELERATOR_OPTIMIZED, trace
from repro.core.config import EncodingPolicy
from repro.core.query import (Q6_COLUMNS, DecimalQ6, _q6_jnp, q6,
                              q6_decimal, q6_decimal_reference)
from repro.core.scan import open_scanner
from repro.core.schema import Field, LogicalType, PhysicalType, Schema
from repro.core.table import Table
from repro.core.writer import write_table
from repro.kernels.ops import host_fallback_counts

CFG = ACCELERATOR_OPTIMIZED.replace(rows_per_rg=12_000,
                                    target_pages_per_chunk=4)


def _schema(scale: int = 2) -> Schema:
    def dec(name):
        return Field(name, PhysicalType.INT64, LogicalType.DECIMAL, scale)
    return Schema([Field("l_shipdate", PhysicalType.INT32, LogicalType.DATE),
                   dec("l_discount"), dec("l_quantity"),
                   dec("l_extendedprice")])


def _boundary_rows() -> dict[str, np.ndarray]:
    """Every combination of the edges: ship dates on both sides of
    FY1994's ends, discounts 4/5/7/8 cents, quantities 2,399/2,400
    cents; each row a price of its own."""
    grid = np.array(np.meshgrid([730, 731, 1095, 1096], [4, 5, 7, 8],
                                [2399, 2400], indexing="ij")).reshape(3, -1)
    return {"l_shipdate": grid[0].astype(np.int32),
            "l_discount": grid[1].astype(np.int64),
            "l_quantity": grid[2].astype(np.int64),
            "l_extendedprice": 1_000_003 + 7 * np.arange(grid.shape[1],
                                                         dtype=np.int64)}


def _columns(n: int, seed: int) -> dict[str, np.ndarray]:
    """TPC-H's value domains in cents, the boundary rows first."""
    rng = np.random.default_rng(seed)
    rand = {"l_shipdate": rng.integers(600, 1200, n).astype(np.int32),
            "l_discount": rng.integers(0, 11, n).astype(np.int64),
            "l_quantity": rng.integers(1, 51, n).astype(np.int64) * 100,
            "l_extendedprice": rng.integers(90_000, 10_494_951,
                                            n).astype(np.int64)}
    edge = _boundary_rows()
    return {c: np.concatenate([edge[c], rand[c]]) for c in Q6_COLUMNS}


def _write(path, cols, cfg=CFG, scale=2) -> str:
    write_table(Table(cols, _schema(scale)), str(path), cfg)
    return str(path)


def _counters() -> dict:
    return dict(trace.registry().snapshot()["counters"])


@pytest.fixture(scope="module")
def dec_file(tmp_path_factory):
    cols = _columns(30_000, seed=16)
    path = _write(tmp_path_factory.mktemp("dec") / "line.tab", cols)
    return path, cols, q6_decimal_reference(cols)


def test_decimal_constants_follow_the_scales():
    assert q6_decimal(_schema(2)) == DecimalQ6(5, 7, 2400, 4)
    assert q6_decimal(_schema(4)) == DecimalQ6(500, 700, 240_000, 8)
    mixed = Schema([Field("l_discount", PhysicalType.FLOAT),
                    *_schema().fields[2:]])
    with pytest.raises(ValueError, match="all DECIMAL"):
        q6_decimal(mixed)


@pytest.mark.parametrize("backend", ["host", "pallas"])
@pytest.mark.parametrize("overlapped", [True, False])
def test_scanner_q6_is_exact(dec_file, backend, overlapped):
    path, _, want = dec_file
    sc = open_scanner(path, columns=Q6_COLUMNS, decode_backend=backend)
    assert len(sc.meta.row_groups) == 3
    got, _ = q6(sc, overlapped=overlapped)
    assert isinstance(got, decimal.Decimal)
    assert got == want
    assert got.as_tuple().exponent == -4


def test_front_end_q6_is_exact_and_counted(dec_file):
    from repro.serve.engine import QueryFrontEnd
    path, _, want = dec_file
    sc = open_scanner(path, columns=Q6_COLUMNS, decode_backend="pallas")
    before = _counters().get("decimal_exact_queries", 0)
    with QueryFrontEnd(workers=2, window_bytes=64 << 20) as fe:
        answers = [fe.result(fe.submit("t0", "q6", sc), timeout=300)[0]
                   for _ in range(2)]
    assert answers == [want, want]
    assert _counters()["decimal_exact_queries"] == before + 2


@pytest.mark.parametrize("mode", ["fused", "reference", "env"])
def test_fused_modes_are_declined_and_exact(dec_file, monkeypatch, mode):
    """Fused Q6 is float32; over DECIMAL operands no FusedSpec is
    attached, whatever asks for it, and each such query is counted."""
    from repro.core import query
    path, _, want = dec_file
    kwargs = {"fused": True, "reference": "reference", "env": None}[mode]
    if mode == "env":
        monkeypatch.setenv("REPRO_FUSED", "1")
    sc = open_scanner(path, columns=Q6_COLUMNS, decode_backend="pallas")
    c0 = _counters()
    legacy0 = query.legacy_fused_consume_count()
    got, _ = q6(sc, fused=kwargs)
    assert got == want
    assert sc.fused_spec is None
    assert query.legacy_fused_consume_count() == legacy0
    c1 = _counters()
    assert c1["fused_declined_decimal"] == \
        c0.get("fused_declined_decimal", 0) + 1
    assert c1["decimal_exact_queries"] == \
        c0.get("decimal_exact_queries", 0) + 1


def test_a_scanner_opened_fused_is_served_unfused(dec_file):
    from repro.core.query import q6_fused_spec
    path, _, want = dec_file
    sc = open_scanner(path, columns=Q6_COLUMNS, decode_backend="pallas",
                      fused_spec=q6_fused_spec())
    c0 = _counters().get("fused_declined_decimal", 0)
    got, _ = q6(sc, fused=False)
    assert got == want and sc.fused_spec is None
    assert _counters()["fused_declined_decimal"] == c0 + 1


def test_use_kernel_refuses_decimal_operands(dec_file):
    path, _, _ = dec_file
    sc = open_scanner(path, columns=Q6_COLUMNS, decode_backend="pallas")
    with pytest.raises(ValueError, match="float32"):
        q6(sc, use_kernel=True)


@pytest.mark.parametrize("devices", [None, 1])
def test_two_fragment_dataset_is_exact(tmp_path, devices):
    from repro.dataset import write_dataset
    from repro.dataset.result_cache import FragmentResultCache
    cols = _columns(20_000, seed=17)
    want = q6_decimal_reference(cols)
    ds = write_dataset(Table(cols, _schema()), str(tmp_path / "ds"), CFG,
                       fragments=2)
    assert len(ds.fragments) == 2
    if devices is not None:
        got, _ = q6(ds, devices=devices)
        assert got == want
        return
    cache = FragmentResultCache()
    first, _ = q6(ds, result_cache=cache, fused=True)
    again, rep = q6(ds, result_cache=cache)
    assert first == again == want
    assert rep.result_cache_hits == 2


def test_boundary_rows_alone():
    """Only the rows on the predicate's edges: discount 5 and 7 cents and
    quantity 2,399 cents pass, in 1994-01-01 and 1994-12-31."""
    rows = _boundary_rows()
    keep = [(int(p), int(d)) for s, d, q, p in zip(*rows.values())
            if s in (731, 1095) and d in (5, 7) and q == 2399]
    assert len(keep) == 4
    want = decimal.Decimal(sum(p * d for p, d in keep)).scaleb(-4)
    assert q6_decimal_reference(rows) == want


def test_boundary_rows_served(tmp_path):
    rows = _boundary_rows()
    path = _write(tmp_path / "edge.tab", rows)
    sc = open_scanner(path, columns=Q6_COLUMNS, decode_backend="pallas")
    got, _ = q6(sc)
    assert got == q6_decimal_reference(rows)


def test_sum_past_int32_within_one_row_group(tmp_path):
    n = 5_000
    rng = np.random.default_rng(18)
    cols = {"l_shipdate": rng.integers(731, 1096, n).astype(np.int32),
            "l_discount": rng.integers(5, 8, n).astype(np.int64),
            "l_quantity": rng.integers(1, 24, n).astype(np.int64) * 100,
            "l_extendedprice": rng.integers(9_000_000, 10_494_951,
                                            n).astype(np.int64)}
    path = _write(tmp_path / "big.tab", cols)
    sc = open_scanner(path, columns=Q6_COLUMNS, decode_backend="pallas")
    assert len(sc.meta.row_groups) == 1
    want = q6_decimal_reference(cols)
    assert want * 10**4 > 2**31
    got, _ = q6(sc)
    assert got == want


def test_plain_int64_decodes_on_the_device(tmp_path):
    cols = _columns(8_000, seed=19)
    path = _write(tmp_path / "plain.tab", cols,
                  CFG.replace(encodings=EncodingPolicy.PLAIN_ONLY))
    sc = open_scanner(path, columns=Q6_COLUMNS, decode_backend="pallas")
    assert all(c.encoding == 0 for rg in sc.meta.row_groups
               for c in rg.columns)
    before = host_fallback_counts()
    trace.enable()
    try:
        for _, decoded in sc.scan():
            assert all(res.on_device for res in decoded.values())
            assert decoded["l_extendedprice"].array.dtype == np.int32
        narrowed = _counters()["int64_narrowed_chunks"]
    finally:
        trace.reset()
    assert host_fallback_counts() - before == {}
    assert narrowed == 3 * len(sc.meta.row_groups)
    got, _ = q6(sc)
    assert got == q6_decimal_reference(cols)


def test_a_planted_unit_is_caught(dec_file, monkeypatch):
    """One unit of 1e-4 added to one block sum: the served answer is off
    by exactly that unit, and ``==`` with the oracle catches it."""
    from repro.core import query
    path, _, want = dec_file
    real = query._q6_exact_blocks

    def planted(*args, **kwargs):
        return real(*args, **kwargs).at[0, 0].add(1)

    monkeypatch.setattr(query, "_q6_exact_blocks", planted)
    sc = open_scanner(path, columns=Q6_COLUMNS, decode_backend="pallas")
    got, _ = q6(sc, prune=False)
    assert got != want
    assert got - want == decimal.Decimal("0.0001") * 3


def test_products_past_int32_are_refused(tmp_path):
    cols = _columns(2_000, seed=20)
    cols["l_extendedprice"][5] = 400_000_000     # x 7 cents > 2**31
    path = _write(tmp_path / "wide.tab", cols)
    sc = open_scanner(path, columns=Q6_COLUMNS, decode_backend="pallas")
    with pytest.raises(ValueError, match="l_extendedprice"):
        q6(sc)


def test_program_float_path_is_the_old_arithmetic(tmp_path):
    """Float32 decimals keep the float consume: the served answer is the
    plan-ordered sum of ``_q6_jnp``'s per-row-group partials, bit for
    bit, and a float."""
    import jax.numpy as jnp
    cols = _columns(20_000, seed=21)
    floats = {"l_shipdate": cols["l_shipdate"],
              **{c: (cols[c] / 100).astype(np.float32)
                 for c in Q6_COLUMNS[1:]}}
    path = str(tmp_path / "f32.tab")
    write_table(Table(floats), path, CFG)
    sc = open_scanner(path, columns=Q6_COLUMNS, decode_backend="pallas")
    got, _ = q6(sc, prune=False, fused=False)
    acc = None
    for _, dec in open_scanner(path, columns=Q6_COLUMNS,
                               decode_backend="pallas").scan():
        part = float(_q6_jnp(
            jnp.asarray(dec["l_shipdate"].array).astype(jnp.int32),
            *(jnp.asarray(dec[c].array).astype(jnp.float32)
              for c in ("l_discount", "l_quantity", "l_extendedprice"))))
        acc = part if acc is None else acc + part
    assert type(got) is float
    assert got == acc
