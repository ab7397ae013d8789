"""Distributed scans (DESIGN.md §8): contiguous sharding, deterministic
tree reduce, the object-store storage model, background prefetch, decode
affinity, and multi-device bit-identity of Q6/Q12."""

import os
import struct
import subprocess
import sys

import numpy as np
import pytest

from repro.core.config import ACCELERATOR_OPTIMIZED
from repro.core.faults import FaultPlan
from repro.core.query import q6, q6_rg_stats_predicate, q12
from repro.core.scheduler import _apply_affinity, decode_affinity_mode
from repro.core.storage import (DEFAULT_OBJECT_COALESCE_GAP,
                                DEFAULT_OBJECT_CONNECTIONS,
                                DEFAULT_OBJECT_LATENCY, ObjectStoreStorage,
                                PrefetchingStorage, backend_io_defaults,
                                open_storage)
from repro.data import tpch
from repro.dataset import (plan_dataset_scan, run_distributed_scan,
                           write_dataset)
from repro.launch.mesh import scan_devices
from repro.parallel.collectives import tree_reduce
from repro.parallel.sharding import contiguous_shards

TUNED = ACCELERATOR_OPTIMIZED.replace(rows_per_rg=1_500,
                                      target_pages_per_chunk=4)
HOST_OPTS = {"backend": "sim", "decode_backend": "host"}
REPO_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def bits(x: float) -> bytes:
    return struct.pack("<d", float(x))


@pytest.fixture(scope="module")
def tables():
    return tpch.generate_tables(sf=0.002, seed=42, include_strings=False)


@pytest.fixture(scope="module")
def range_ds(tables, tmp_path_factory):
    line, _ = tables
    root = str(tmp_path_factory.mktemp("ds_dist"))
    return write_dataset(line, root, TUNED, partition_by="l_shipdate",
                         how="range", fragments=8)


@pytest.fixture(scope="module")
def q12_ds(tables, tmp_path_factory):
    line, orders = tables
    base = tmp_path_factory.mktemp("ds_q12")
    lds = write_dataset(line, str(base / "l"), TUNED,
                        partition_by="l_shipdate", how="range", fragments=6)
    ods = write_dataset(orders, str(base / "o"), TUNED, fragments=3)
    return lds, ods


# -- contiguous sharding ----------------------------------------------------

def test_contiguous_shards_partition_properties():
    for m in (1, 2, 5, 8, 17):
        for n in (1, 2, 3, 4, 9):
            weights = [(i * 37) % 11 + 1 for i in range(m)]
            shards = contiguous_shards(weights, n)
            assert len(shards) == n
            # contiguous, ordered, covering [0, m)
            pos = 0
            for lo, hi in shards:
                assert lo == pos and hi >= lo
                pos = hi
            assert pos == m
            # non-empty while items remain
            nonempty = sum(1 for lo, hi in shards if hi > lo)
            assert nonempty == min(n, m)


def test_contiguous_shards_weighted_balance():
    # one huge fragment up front: it gets a shard of its own
    shards = contiguous_shards([100, 1, 1, 1], 2)
    assert shards == [(0, 1), (1, 4)]
    shards = contiguous_shards([1, 1, 1, 100], 2)
    assert shards == [(0, 3), (3, 4)]
    # deterministic
    w = [5, 3, 8, 1, 9, 2, 7, 4]
    assert contiguous_shards(w, 3) == contiguous_shards(list(w), 3)


def test_scan_devices_cycles_on_small_hosts():
    devs = scan_devices(4)
    assert len(devs) == 4          # cycles when fewer real devices exist
    assert scan_devices(1) == [devs[0]]


def test_scan_devices_refuses_missing_accelerators(monkeypatch):
    import jax
    n = len(jax.devices())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert len(scan_devices(n)) == n
    with pytest.raises(ValueError, match="backend has"):
        scan_devices(n + 1)


# -- tree reduce ------------------------------------------------------------

def test_tree_reduce_pairing_depends_only_on_length():
    pairings = []

    def record(a, b):
        pairings.append((a, b))
        return f"({a}+{b})"

    tree_reduce(list("abcde"), record)
    first = list(pairings)
    pairings.clear()
    tree_reduce(list("abcde"), record)
    assert pairings == first       # same shape every time
    # 5 leaves: (a+b)(c+d) then ((a+b)+(c+d)) then (...+e)
    assert first[0] == ("a", "b") and first[1] == ("c", "d")


def test_tree_reduce_values_and_nones():
    assert tree_reduce([1, 2, 3, 4, 5], lambda a, b: a + b) == 15
    assert tree_reduce([], min) is None
    assert tree_reduce([None, None], min) is None
    assert tree_reduce([None, 7, None], max) == 7


# -- object-store storage model ---------------------------------------------

def test_object_store_model(tmp_path):
    p = tmp_path / "blob.bin"
    payload = bytes(range(256)) * 64
    p.write_bytes(payload)
    st = ObjectStoreStorage(str(p), connections=2,
                            connection_bandwidth=1e9, latency=5e-3,
                            sleep=False)
    assert st.kind == "object" and st.connections == 2
    assert st.request_seconds(1_000_000) == pytest.approx(5e-3 + 1e-3)
    # LPT over 2 connections: three requests, largest two on separate
    # lanes, the third behind the smaller — batch drains with the slowest
    sizes = [4_000_000, 2_000_000, 1_000_000]
    per = [st.request_seconds(s) for s in sizes]
    assert st.batch_seconds(sizes) == pytest.approx(max(per[0],
                                                        per[1] + per[2]))
    data = st.fetch(0, 512)
    assert data == payload[:512]
    assert st.stats.requests == 1 and len(st.stats.latencies) == 1
    assert st.stats.latencies[0] == pytest.approx(st.request_seconds(512))
    st.close()


def test_object_store_sleeps_modeled_time(tmp_path):
    import time
    p = tmp_path / "blob.bin"
    p.write_bytes(b"x" * 4096)
    st = ObjectStoreStorage(str(p), latency=20e-3)
    t0 = time.perf_counter()
    st.fetch(0, 1024)
    assert time.perf_counter() - t0 >= 20e-3   # remote latency is wall
    st.close()


def test_open_storage_object_defaults(tmp_path):
    p = tmp_path / "blob.bin"
    p.write_bytes(b"y" * 128)
    st = open_storage(str(p), backend="object")
    assert st.kind == "object"
    assert st.n_lanes == DEFAULT_OBJECT_CONNECTIONS
    assert st.latency == DEFAULT_OBJECT_LATENCY
    st.close()
    bw, lat, gap = backend_io_defaults("object")
    assert gap == DEFAULT_OBJECT_COALESCE_GAP > backend_io_defaults("sim")[2]
    assert lat == DEFAULT_OBJECT_LATENCY


# -- prefetch ---------------------------------------------------------------

def test_prefetch_hit_and_miss_accounting(tmp_path):
    p = tmp_path / "blob.bin"
    payload = os.urandom(1 << 16)
    p.write_bytes(payload)
    inner = open_storage(str(p), backend="sim", n_lanes=2)
    st = PrefetchingStorage(inner)
    assert st.prefetch([(0, 1024), (2048, 512)]) == 2
    assert st.prefetch([(0, 1024)]) == 0       # dedup against in-buffer
    data = st.fetch(0, 1024)                   # hit
    assert data == payload[:1024]
    miss = st.fetch(8192, 256)                 # never prefetched
    assert miss == payload[8192:8192 + 256]
    assert st.prefetch_stats.hits == 1
    assert st.prefetch_stats.misses == 1
    # consumption-time accounting: exactly one request per demand fetch,
    # nothing for the still-buffered (2048, 512) range
    assert inner.stats.requests == 2
    # single-use entries: the same range misses the second time
    st.fetch(0, 1024)
    assert st.prefetch_stats.misses == 2
    # hidden + stall partition the modeled request time of each hit
    ps = st.prefetch_stats
    assert (ps.hidden_seconds + ps.stall_seconds
            == pytest.approx(inner.request_seconds(1024)))
    st.close()


def test_prefetch_batch_hits_keep_request_counts(tmp_path):
    p = tmp_path / "blob.bin"
    p.write_bytes(os.urandom(1 << 15))
    inner = open_storage(str(p), backend="sim")
    st = PrefetchingStorage(inner)
    reqs = [(0, 512), (4096, 1024)]
    st.prefetch(reqs)
    datas, _ = st.fetch_batch(reqs)
    assert [len(d) for d in datas] == [512, 1024]
    assert inner.stats.requests == 2 and inner.stats.batches == 1
    assert st.prefetch_stats.hits == 2 and st.prefetch_stats.misses == 0
    st.close()


# -- decode affinity --------------------------------------------------------

def test_decode_affinity_modes(monkeypatch):
    monkeypatch.delenv("REPRO_DECODE_AFFINITY", raising=False)
    assert decode_affinity_mode() == "off"
    monkeypatch.setenv("REPRO_DECODE_AFFINITY", "auto")
    assert decode_affinity_mode().startswith("auto:")
    _apply_affinity(0)             # linux: pins; elsewhere: unsupported
    assert decode_affinity_mode() in ("auto:pinned", "auto:unsupported")
    monkeypatch.setenv("REPRO_DECODE_AFFINITY", "not-a-cpu-list")
    _apply_affinity(0)
    assert decode_affinity_mode() == "not-a-cpu-list:unsupported"


def test_affinity_logged_in_scan_metrics(range_ds, monkeypatch):
    monkeypatch.setenv("REPRO_DECODE_AFFINITY", "auto")
    plan = plan_dataset_scan(range_ds,
                             predicate_stats=q6_rg_stats_predicate)
    _, rep = run_distributed_scan(
        plan, lambda acc, i, cols: 1, lambda a, b: a + b,
        devices=1, decode_workers=1, open_opts=HOST_OPTS)
    assert rep.reports
    mode = rep.reports[0].metrics.decode_affinity
    assert mode.startswith("auto:")


# -- multi-device bit-identity ----------------------------------------------

@pytest.mark.parametrize("prune", [True, False])
@pytest.mark.parametrize("fused", [False, True])
def test_q6_device_sweep_bit_identical(range_ds, prune, fused):
    results = {}
    for d in (1, 2, 4):
        r, rep = q6(range_ds, prune=prune, fused=fused, devices=d,
                    decode_workers=2, open_opts=HOST_OPTS)
        results[d] = (r, rep)
        assert rep.devices == d
        assert sum(rep.device_fragments) == rep.files_scanned
        assert rep.fragments_quarantined == 0
    assert bits(results[1][0]) == bits(results[2][0]) == bits(results[4][0])


def test_q6_distributed_matches_windowed(range_ds):
    rd, _ = q6(range_ds, devices=1, decode_workers=2, open_opts=HOST_OPTS)
    rw, _ = q6(range_ds, decode_workers=2, open_opts=HOST_OPTS)
    # 2 surviving FY94 fragments: tree reduce == left fold at this width;
    # the executors agree bitwise on the same plan
    assert bits(rd) == bits(rw)


@pytest.mark.parametrize("fused", [False, True])
def test_q12_device_sweep_bit_identical(q12_ds, fused):
    lds, ods = q12_ds
    out = {}
    for d in (1, 2):
        res, brep, prep = q12(lds, ods, fused=fused, devices=d,
                              decode_workers=2, open_opts=HOST_OPTS)
        out[d] = res
        assert brep.devices == d and prep.devices == d
    assert out[1] == out[2]


def test_distributed_partials_are_plan_ordered(range_ds):
    plan = plan_dataset_scan(range_ds)

    def consume(acc, i, cols):
        n = int(cols["l_shipdate"].array.shape[0])
        return n if acc is None else acc + n

    parts1, rep1 = run_distributed_scan(plan, consume, None, devices=1,
                                        decode_workers=2,
                                        open_opts=HOST_OPTS)
    parts4, rep4 = run_distributed_scan(plan, consume, None, devices=4,
                                        decode_workers=2,
                                        open_opts=HOST_OPTS)
    assert parts1 == parts4        # slot list ignores which device ran it
    assert len(parts1) == len(plan.fragments)
    assert rep4.stolen_fragments >= 0
    assert sum(rep4.device_fragments) == len(plan.fragments)


def test_more_devices_than_fragments(tables, tmp_path):
    line, _ = tables
    ds = write_dataset(line.slice(0, 3_000), str(tmp_path), TUNED,
                       fragments=2)
    plan = plan_dataset_scan(ds)
    parts, rep = run_distributed_scan(
        plan, lambda acc, i, cols: 1, lambda a, b: a + b,
        devices=4, decode_workers=1, open_opts=HOST_OPTS)
    assert parts == 2 and rep.devices == 4
    assert sum(rep.device_fragments) == 2


# -- object backend through the distributed executor ------------------------

def test_distributed_object_backend_prefetch(range_ds):
    opts = dict(HOST_OPTS, backend="object", prefetch=True)
    r_obj, rep = q6(range_ds, prune=False, devices=2, decode_workers=2,
                    open_opts=opts)
    r_sim, _ = q6(range_ds, prune=False, devices=2, decode_workers=2,
                  open_opts=HOST_OPTS)
    assert bits(r_obj) == bits(r_sim)     # backend never changes results
    assert rep.bytes_by_backend.get("object", 0) == rep.stored_bytes
    assert rep.prefetch_hits + rep.prefetch_misses == rep.n_io_requests
    assert rep.prefetch_hits > 0          # lookahead actually landed
    assert rep.prefetch_hidden_seconds > 0
    assert rep.io_p95_us >= rep.io_p50_us > 0


# -- chaos: one device's fragments fault, the run heals ---------------------

def test_one_shard_faults_heal_bit_identical(range_ds):
    plan = plan_dataset_scan(range_ds)
    n = len(plan.fragments)
    lo, hi = contiguous_shards(
        [max(1, f.stored_bytes) for f in plan.fragments], 2)[0]
    shard0 = set(range(lo, hi))
    assert shard0 and len(shard0) < n

    def consume(acc, i, cols):
        s = float(np.asarray(cols["l_discount"].array,
                             dtype=np.float64).sum())
        return s if acc is None else acc + s

    clean, crep = run_distributed_scan(plan, consume, lambda a, b: a + b,
                                       devices=2, decode_workers=2,
                                       open_opts=HOST_OPTS)

    def chaos_opts(pos, frag):
        if pos in shard0:
            return {"fault_plan": FaultPlan(seed=pos + 1, io_error=0.5,
                                            bit_flip=0.3)}
        return None

    healed, hrep = run_distributed_scan(plan, consume, lambda a, b: a + b,
                                        devices=2, decode_workers=2,
                                        open_opts=HOST_OPTS,
                                        open_opts_for=chaos_opts)
    assert bits(clean) == bits(healed)
    assert hrep.retries > 0
    assert hrep.fragments_quarantined == 0
    assert crep.retries == 0


# -- real 4-device emulation (subprocess, XLA host platform) ----------------

@pytest.mark.slow
def test_four_emulated_devices_bit_identical(range_ds):
    code = f"""
import struct
import jax
assert len(jax.devices()) == 4, jax.devices()
from repro.core.query import q6
from repro.dataset import Dataset
ds = Dataset.load({range_ds.root!r})
opts = {{"backend": "sim", "decode_backend": "host"}}
r1, _ = q6(ds, devices=1, decode_workers=2, open_opts=opts)
r4, rep = q6(ds, devices=4, decode_workers=2, open_opts=opts)
assert struct.pack("<d", r1) == struct.pack("<d", r4), (r1, r4)
assert rep.devices == 4
assert len(set(rep.device_names)) == 4      # four distinct real devices
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep * bool(
        env.get("PYTHONPATH")) + env.get("PYTHONPATH", "")
    env["TF_CPP_MIN_LOG_LEVEL"] = "3"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout
