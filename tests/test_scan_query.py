"""Scan engine, overlap executor, storage model, Q6/Q12 integration."""

import numpy as np
import pytest

from repro.core import ACCELERATOR_OPTIMIZED, CPU_DEFAULT, TPU_CASCADE
from repro.core.overlap import run_blocking, run_overlapped
from repro.core.query import (Q12_LINEITEM_COLUMNS, Q12_ORDERS_COLUMNS,
                              Q6_COLUMNS, q6, q6_reference, q12,
                              q12_reference)
from repro.core.scan import open_scanner
from repro.core.storage import SimulatedStorage
from repro.data import tpch


@pytest.fixture(scope="module")
def tpch_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch")
    metas = tpch.write_tpch(str(d), sf=0.004,
                            config=ACCELERATOR_OPTIMIZED.replace(
                                rows_per_rg=8_000,
                                target_pages_per_chunk=10),
                            seed=21)
    line, orders = tpch.generate_tables(sf=0.004, seed=21)
    return metas, line, orders


@pytest.mark.parametrize("decode_backend", ["host", "pallas"])
def test_scan_matches_table(tpch_files, decode_backend):
    metas, line, _ = tpch_files
    sc = open_scanner(metas["lineitem_path"], columns=["l_quantity",
                                                       "l_orderkey"],
                      decode_backend=decode_backend)
    got_q, got_k = [], []
    for _, cols in sc.scan():
        got_q.append(np.asarray(cols["l_quantity"].array))
        got_k.append(np.asarray(cols["l_orderkey"].array))
    np.testing.assert_array_equal(np.concatenate(got_q),
                                  np.asarray(line["l_quantity"]))
    np.testing.assert_array_equal(
        np.concatenate(got_k).astype(np.int64),
        np.asarray(line["l_orderkey"]))


def test_effective_bandwidth_accounting(tpch_files):
    metas, line, _ = tpch_files
    sc = open_scanner(metas["lineitem_path"], columns=Q6_COLUMNS,
                      backend="sim", n_lanes=2, decode_backend="host")
    _, m = sc.scan_with_metrics()
    assert m.logical_bytes == sum(
        np.asarray(line[c]).nbytes for c in Q6_COLUMNS)
    assert m.stored_bytes < m.logical_bytes        # encodings help
    assert m.compression_ratio > 1.0
    assert m.overlapped_seconds <= m.blocking_seconds + 1e-9


def test_blocking_vs_overlapped_same_result(tpch_files):
    metas, _, _ = tpch_files
    sc1 = open_scanner(metas["lineitem_path"], columns=Q6_COLUMNS,
                       decode_backend="host")
    sc2 = open_scanner(metas["lineitem_path"], columns=Q6_COLUMNS,
                       decode_backend="host")
    r1, rep1 = q6(sc1, overlapped=False)
    r2, rep2 = q6(sc2, overlapped=True)
    assert abs(r1 - r2) < 1e-6 * max(1.0, abs(r1))
    assert rep2.modeled_wall <= rep1.modeled_wall + 1e-9


def test_q6_against_reference(tpch_files):
    metas, line, _ = tpch_files
    ref = q6_reference({c: np.asarray(line[c]) for c in Q6_COLUMNS})
    sc = open_scanner(metas["lineitem_path"], columns=Q6_COLUMNS,
                      decode_backend="host")
    got, _ = q6(sc)
    assert abs(got - ref) / max(1.0, abs(ref)) < 1e-5


def test_q6_kernel_path(tpch_files):
    metas, line, _ = tpch_files
    ref = q6_reference({c: np.asarray(line[c]) for c in Q6_COLUMNS})
    sc = open_scanner(metas["lineitem_path"], columns=Q6_COLUMNS,
                      decode_backend="pallas")
    got, _ = q6(sc, use_kernel=True)
    assert abs(got - ref) / max(1.0, abs(ref)) < 1e-4


def test_pallas_scan_keeps_q6_q12_columns_on_device(tpch_files):
    """On the pallas backend no Q6/Q12 column takes the host fallback;
    a string column does, and is counted."""
    from repro.kernels.ops import host_fallback_counts
    metas, _, _ = tpch_files
    cols = sorted(set(Q6_COLUMNS) | set(Q12_LINEITEM_COLUMNS))
    before = host_fallback_counts()
    for path, names in ((metas["lineitem_path"], cols),
                        (metas["orders_path"], Q12_ORDERS_COLUMNS)):
        sc = open_scanner(path, columns=list(names),
                          decode_backend="pallas")
        for _, decoded in sc.scan():
            assert all(res.on_device for res in decoded.values())
    assert host_fallback_counts() - before == {}
    sc = open_scanner(metas["lineitem_path"], columns=["l_comment"],
                      decode_backend="pallas")
    for _ in sc.scan():
        pass
    assert (host_fallback_counts() - before)["l_comment"] >= 1


def test_q6_pruning_safe(tpch_files):
    metas, line, _ = tpch_files
    sc1 = open_scanner(metas["lineitem_path"], columns=Q6_COLUMNS,
                       decode_backend="host")
    sc2 = open_scanner(metas["lineitem_path"], columns=Q6_COLUMNS,
                       decode_backend="host")
    with_prune, rep_p = q6(sc1, prune=True)
    without, rep_n = q6(sc2, prune=False)
    assert abs(with_prune - without) < 1e-6 * max(1.0, abs(without))
    assert rep_p.metrics.n_row_groups <= rep_n.metrics.n_row_groups


def test_q12_against_reference(tpch_files):
    metas, line, orders = tpch_files
    ref = q12_reference(
        {c: np.asarray(line[c]) for c in Q12_LINEITEM_COLUMNS},
        {c: np.asarray(orders[c]) for c in Q12_ORDERS_COLUMNS})
    lsc = open_scanner(metas["lineitem_path"],
                       columns=Q12_LINEITEM_COLUMNS, decode_backend="host")
    osc = open_scanner(metas["orders_path"], columns=Q12_ORDERS_COLUMNS,
                       decode_backend="host")
    got, _, _ = q12(lsc, osc)
    assert got == ref


def test_served_unfused_pallas_repeats_bit_for_bit(tpch_files):
    """Unfused Q6 and Q12 served twice through a front end with a result
    window, on pallas scanners: every answer matches the numpy reference,
    and the repeats, whose consume reads window entries in place, match
    the first answers bit for bit."""
    from repro.serve.engine import QueryFrontEnd
    metas, line, orders = tpch_files
    ref6 = q6_reference({c: np.asarray(line[c]) for c in Q6_COLUMNS})
    ref12 = q12_reference(
        {c: np.asarray(line[c]) for c in Q12_LINEITEM_COLUMNS},
        {c: np.asarray(orders[c]) for c in Q12_ORDERS_COLUMNS})

    def scanner(path, columns):
        return open_scanner(path, columns=list(columns),
                            decode_backend="pallas")

    q6_src = scanner(metas["lineitem_path"], Q6_COLUMNS)
    q12_src = (scanner(metas["lineitem_path"], Q12_LINEITEM_COLUMNS),
               scanner(metas["orders_path"], Q12_ORDERS_COLUMNS))
    assert len(q6_src.meta.row_groups) > 1
    answers = []
    with QueryFrontEnd(workers=2, window_bytes=64 << 20) as fe:
        for _ in range(2):
            a6, _ = fe.result(fe.submit("t0", "q6", q6_src, prune=False,
                                        fused=False, decode_workers=2),
                              timeout=300)
            a12, _ = fe.result(fe.submit("t0", "q12", q12_src, fused=False,
                                         decode_workers=2), timeout=300)
            answers.append((a6, a12))
        assert fe.service.window_hits > 0
    for a6, a12 in answers:
        assert abs(a6 - ref6) / abs(ref6) <= 1e-5
        assert a12 == ref12
    assert answers[1] == answers[0]


def test_cascade_file_scans(tmp_path, tpch_files):
    _, line, _ = tpch_files
    from repro.core import write_table
    path = str(tmp_path / "casc.tab")
    write_table(line.select(Q6_COLUMNS), path,
                TPU_CASCADE.replace(rows_per_rg=10_000,
                                    target_pages_per_chunk=8))
    sc = open_scanner(path, columns=Q6_COLUMNS, decode_backend="pallas")
    got, _ = q6(sc)
    ref = q6_reference({c: np.asarray(line[c]) for c in Q6_COLUMNS})
    assert abs(got - ref) / max(1.0, abs(ref)) < 1e-5


# -- storage model -----------------------------------------------------------

def test_sim_lane_scaling(tpch_files):
    metas, _, _ = tpch_files
    sizes = [1_000_000] * 8
    t1 = SimulatedStorage(metas["lineitem_path"],
                          n_lanes=1).batch_seconds(sizes)
    t4 = SimulatedStorage(metas["lineitem_path"],
                          n_lanes=4).batch_seconds(sizes)
    assert t1 / t4 == pytest.approx(4.0, rel=0.05)


def test_sim_small_io_penalty(tpch_files):
    """Insight 2: same bytes in small requests → lower bandwidth."""
    metas, _, _ = tpch_files
    s = SimulatedStorage(metas["lineitem_path"], n_lanes=1)
    big = s.batch_seconds([10_000_000])
    small = s.batch_seconds([100_000] * 100)
    assert small > big * 1.5
    assert s.effective_bandwidth(100_000) < 0.5 * s.lane_bandwidth
    assert s.effective_bandwidth(50_000_000) > 0.95 * s.lane_bandwidth


def test_overlap_error_propagates(tpch_files):
    metas, _, _ = tpch_files
    sc = open_scanner(metas["lineitem_path"], columns=Q6_COLUMNS,
                      decode_backend="host")

    def bad_consume(acc, i, cols):
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError):
        run_overlapped(sc, bad_consume)
