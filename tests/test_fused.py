"""Late materialization (core/fused.py): fused == unfused, bit for bit.

The property tests drive randomized predicates, encodings/codecs (via the
paper's file configs), page/row-group sizes, and padding edges through the
fused aggregate and selection paths, always diffing against the reference
execution mode (``FusedSpec.with_mode("reference")``) — the unfused twin
that materializes everything and evaluates the same canonical per-page
reduce.  Exact equality is asserted on the raw float bits / selection
vectors / gathered arrays, not on tolerances.
"""

import shutil
import struct
import tempfile

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:  # deterministic fallback shim (hypothesis not installed)
    from _hypothesis_fallback import given, settings, st

from repro.core import ACCELERATOR_OPTIMIZED, CPU_DEFAULT, TPU_CASCADE, Table
from repro.core.fused import (FUSED_KEY, Compare, FusedRGResult, FusedSpec,
                              Interval, SumProduct)
from repro.core.query import Q6_COLUMNS, q6, q6_fused_spec, q6_reference
from repro.core.scan import Scanner
from repro.core.writer import write_table
from repro.data import tpch
from repro.kernels.common import kernel_launch_count

CONFIGS = {
    "cpu": CPU_DEFAULT,
    "opt": ACCELERATOR_OPTIMIZED,
    "cascade": TPU_CASCADE,
}


def _write(directory, name, n_rows, cfg, seed, rows_per_rg, pages):
    rng = np.random.default_rng(seed)
    tbl = Table({
        # sorted-ish int32 → DELTA; the stage-A predicate column
        "ship": np.cumsum(rng.integers(0, 3, n_rows)).astype(np.int32),
        # low-cardinality float32 → RLE_DICTIONARY
        "disc": rng.choice(np.linspace(0.0, 0.1, 11).astype(np.float32),
                           n_rows),
        "qty": rng.integers(1, 51, n_rows).astype(np.float32),
        # high-entropy float32 → PLAIN (or BSS under some configs)
        "price": (rng.random(n_rows) * 1e5).astype(np.float32),
        # int64 id → DELTA; emit column for selection mode
        "key": np.arange(n_rows, dtype=np.int64) * 3 + 7,
    })
    path = f"{directory}/{name}.tab"
    write_table(tbl, path, cfg.replace(rows_per_rg=rows_per_rg,
                                       target_pages_per_chunk=pages))
    return path, tbl


def _scan_fused(path, columns, spec, backend):
    sc = Scanner(path, columns, decode_backend=backend, fused_spec=spec)
    out = []
    for _, cols in sc.scan():
        res = cols[FUSED_KEY]
        assert isinstance(res, FusedRGResult)
        out.append(res)
    return out


def _assert_bitwise(fused_rgs, ref_rgs):
    assert len(fused_rgs) == len(ref_rgs)
    for f, r in zip(fused_rgs, ref_rgs):
        if f.partials is not None:
            assert f.partials.tobytes() == r.partials.tobytes()
            assert struct.pack("<d", f.partial) == \
                struct.pack("<d", r.partial)
        if f.selection is not None:
            np.testing.assert_array_equal(f.selection, r.selection)
            assert f.gathered.keys() == r.gathered.keys()
            for k in f.gathered:
                assert f.gathered[k].dtype == r.gathered[k].dtype
                assert f.gathered[k].tobytes() == r.gathered[k].tobytes()


def _oracle_sum(tbl, spec):
    mask = np.ones(tbl["ship"].shape[0], dtype=bool)
    for iv in spec.predicates:
        v = np.asarray(tbl[iv.column])
        cast = v.dtype.type
        if iv.lo is not None:
            mask &= (v >= cast(iv.lo)) if iv.lo_incl else (v > cast(iv.lo))
        if iv.hi is not None:
            mask &= (v <= cast(iv.hi)) if iv.hi_incl else (v < cast(iv.hi))
        if iv.in_set is not None:
            mask &= np.isin(v, np.asarray(iv.in_set, dtype=v.dtype))
    for cmp in spec.compares:
        mask &= np.asarray(tbl[cmp.left]) < np.asarray(tbl[cmp.right])
    return mask


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000),
       st.sampled_from(sorted(CONFIGS)),
       st.integers(500, 4000),     # rows (padding edges: rarely pow2)
       st.integers(1, 4),          # pages per chunk
       st.integers(0, 2))          # predicate shape
def test_fused_agg_matches_reference(seed, cfg_name, n_rows, pages, pshape):
    rng = np.random.default_rng(seed)
    lo = float(rng.uniform(0.0, 0.08))
    if pshape == 0:       # typical window
        preds = (Interval("disc", lo=round(lo, 2), hi=round(lo + 0.02, 2),
                          hi_incl=bool(rng.integers(0, 2))),
                 Interval("qty", hi=float(rng.integers(5, 45))),
                 Interval("ship", lo=int(n_rows * 0.1),
                          hi=int(n_rows * 1.2)))
    elif pshape == 1:     # all-pruned extreme: nothing can match
        preds = (Interval("disc", lo=9.0),)
    else:                 # nothing-pruned extreme: everything matches
        preds = (Interval("qty", lo=0.0, hi=1e9, hi_incl=True),)
    spec = FusedSpec(predicates=preds, agg=SumProduct("price", "disc"))
    rpg = int(rng.choice([700, 1000, 1500]))
    tmp = tempfile.mkdtemp(prefix="fusedprop")
    try:
        path, tbl = _write(tmp, f"agg{seed}", n_rows,
                           CONFIGS[cfg_name], seed, rpg, pages)
        cols = ["ship", "disc", "qty", "price"]
        ref = _scan_fused(path, cols, spec.with_mode("reference"), "pallas")
        for backend in ("pallas", "host"):
            got = _scan_fused(path, cols, spec, backend)
            _assert_bitwise(got, ref)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    mask = _oracle_sum(tbl, spec)
    oracle = float(np.sum((tbl["price"][mask].astype(np.float64)
                           * tbl["disc"][mask].astype(np.float64))))
    total = sum(r.partial for r in ref)
    assert total == pytest.approx(oracle, rel=1e-4, abs=1e-6)
    if pshape == 1:
        assert total == 0.0


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000),
       st.sampled_from(sorted(CONFIGS)),
       st.integers(500, 3000),
       st.integers(0, 2))
def test_fused_selection_matches_reference(seed, cfg_name, n_rows, pshape):
    rng = np.random.default_rng(seed + 77)
    if pshape == 0:
        preds = (Interval("qty", hi=float(rng.integers(5, 45))),
                 Interval("disc", in_set=(np.float32(0.02),
                                          np.float32(0.05))))
    elif pshape == 1:     # all-pruned
        preds = (Interval("ship", hi=-1),)
    else:                 # nothing-pruned
        preds = (Interval("ship", lo=-1),)
    spec = FusedSpec(predicates=preds,
                     compares=(Compare("disc", "qty"),),
                     emit=("key", "qty"))
    tmp = tempfile.mkdtemp(prefix="fusedprop")
    try:
        path, tbl = _write(tmp, f"sel{seed}", n_rows,
                           CONFIGS[cfg_name], seed, 900, 3)
        cols = ["ship", "disc", "qty", "key"]
        ref = _scan_fused(path, cols, spec.with_mode("reference"), "pallas")
        for backend in ("pallas", "host"):
            got = _scan_fused(path, cols, spec, backend)
            _assert_bitwise(got, ref)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    mask = _oracle_sum(tbl, spec)
    sel = np.concatenate([r.gathered["key"] for r in ref]) \
        if ref else np.zeros(0, np.int64)
    np.testing.assert_array_equal(sel, tbl["key"][mask])
    if pshape == 1:
        assert sel.shape[0] == 0
    if pshape == 2:
        assert all(r.n_selected == r.n_rows for r in ref)


# ---------------------------------------------------------------------------
# deterministic units: launch economy, zone skipping, Q6/Q12 wiring
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def q6_file(tmp_path_factory):
    d = tmp_path_factory.mktemp("fusedq6")
    metas = tpch.write_tpch(str(d), sf=0.004,
                            config=ACCELERATOR_OPTIMIZED.replace(
                                rows_per_rg=8_000,
                                target_pages_per_chunk=10),
                            seed=21)
    line, _ = tpch.generate_tables(sf=0.004, seed=21)
    return str(d / "lineitem.tab"), metas["lineitem"], line


def test_q6_fused_plan_shape(q6_file):
    """The Q6 spec must actually fuse on the paper's optimized config:
    shipdate (DELTA) decodes in stage A, disc/qty/price go late into one
    kernel — 2 launches per row group instead of 3+."""
    path, meta, _ = q6_file
    sc = Scanner(path, Q6_COLUMNS, decode_backend="pallas",
                 fused_spec=q6_fused_spec())
    fp = sc.planner.fused_plan_rg(0)
    assert fp.ok, fp.why
    assert set(fp.late) == {"l_discount", "l_quantity", "l_extendedprice"}
    assert [op.kind for op in fp.operands] == ["dict", "dict", "plain"]


def test_q6_fused_launch_economy(q6_file):
    path, meta, _ = q6_file
    def launches(fused):
        sc = Scanner(path, Q6_COLUMNS, decode_backend="pallas",
                     fused_spec=q6_fused_spec() if fused else None)
        n0 = kernel_launch_count()
        for _ in sc.scan():
            pass
        return kernel_launch_count() - n0
    n_rg = len(meta.row_groups)
    lf, lu = launches(True), launches(False)
    assert lf < lu                       # strictly fewer, the CI gate
    assert lf <= 2 * n_rg                # ≤ stage-A group + fused kernel


def test_q6_fused_bitwise_and_oracle(q6_file):
    path, _, line = q6_file
    got_f, _ = q6(Scanner(path, Q6_COLUMNS, decode_backend="pallas"),
                  fused=True)
    got_r, _ = q6(Scanner(path, Q6_COLUMNS, decode_backend="pallas"),
                  fused="reference")
    assert struct.pack("<d", got_f) == struct.pack("<d", got_r)
    ref = q6_reference({c: np.asarray(line[c]) for c in Q6_COLUMNS})
    assert got_f == pytest.approx(ref, rel=1e-5)


def test_q6_fused_counts_legacy_consumes(q6_file):
    """A plan-less scanner cannot fuse: every row group it delivers goes
    through the unfused consume, and the counter says so."""
    from repro.core.query import legacy_fused_consume_count
    path, meta, line = q6_file
    n0 = legacy_fused_consume_count()
    got, _ = q6(Scanner(path, Q6_COLUMNS, decode_backend="host",
                        use_plan=False), fused=True, overlapped=False)
    assert legacy_fused_consume_count() - n0 == len(meta.row_groups)
    ref = q6_reference({c: np.asarray(line[c]) for c in Q6_COLUMNS})
    assert got == pytest.approx(ref, rel=1e-5)
    n1 = legacy_fused_consume_count()
    q6(Scanner(path, Q6_COLUMNS, decode_backend="pallas"), fused=True)
    assert legacy_fused_consume_count() == n1


def test_zone_maps_skip_pages(tmp_path):
    """A predicate on a sorted column must skip whole pages via the
    writer's per-page vmin/vmax stamps — before any arena byte exists."""
    n = 4000
    tbl = Table({
        "ship": np.arange(n, dtype=np.int32),
        "disc": np.full(n, 0.05, dtype=np.float32),
        "price": np.linspace(1, 2, n).astype(np.float32),
    })
    path = str(tmp_path / "zone.tab")
    write_table(tbl, path, ACCELERATOR_OPTIMIZED.replace(
        rows_per_rg=n, target_pages_per_chunk=8))
    # predicate on the *late* dict column can't zone-skip (constant), but
    # the sorted late-fusable ship interval can: select one narrow band
    spec = FusedSpec(predicates=(Interval("ship", lo=100, hi=200),),
                     agg=SumProduct("price", "disc"))
    sc = Scanner(path, ["ship", "disc", "price"], decode_backend="pallas",
                 fused_spec=spec)
    fp = sc.planner.fused_plan_rg(0)
    (_, cols), = list(sc.scan())
    res = cols[FUSED_KEY]
    if "ship" in fp.late:
        assert res.pages_skipped > 0            # zone maps did the work
    else:
        # ship stayed in stage A: selection-skip covers the same pages
        assert res.pages_skipped >= fp.n_pages - 2
    ref = Scanner(path, ["ship", "disc", "price"], decode_backend="pallas",
                  fused_spec=spec.with_mode("reference"))
    (_, rcols), = list(ref.scan())
    assert res.partials.tobytes() == rcols[FUSED_KEY].partials.tobytes()


def test_fused_requires_plan(tmp_path):
    tbl = Table({"x": np.arange(64, dtype=np.int32)})
    path = str(tmp_path / "t.tab")
    write_table(tbl, path, CPU_DEFAULT)
    with pytest.raises(ValueError, match="use_plan"):
        Scanner(path, ["x"], use_plan=False, fused_spec=q6_fused_spec())


def test_fused_spec_validation():
    with pytest.raises(ValueError):
        FusedSpec()                              # selection needs predicates
    with pytest.raises(ValueError):
        FusedSpec(predicates=(Interval("a", lo=0),),
                  agg=SumProduct("a", "b"), emit=("c",))
    s = q6_fused_spec()
    assert s.with_mode("reference").mode == "reference"
    assert s.columns()[0] == "l_shipdate"
