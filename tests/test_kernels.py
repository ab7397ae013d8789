"""Per-kernel sweeps: Pallas (interpret=True) vs ref.py oracle vs host
numpy decoders, across shapes/dtypes/widths."""

import jax.numpy as jnp
import numpy as np
import numpy.testing as npt
import pytest

from repro.core import bitpack
from repro.core.compression import cascade_compress, cascade_manifest
from repro.kernels import ref
from repro.kernels.bitunpack import bitunpack_pages
from repro.kernels.bss_decode import bss_decode_pages
from repro.kernels.cascade_decode import cascade_decode_pages
from repro.kernels.delta_decode import delta_decode_pages
from repro.kernels.dict_decode import dict_decode_pages
from repro.kernels.filter_agg import TILE, filter_agg_q6
from repro.kernels.rle_decode import rle_decode_pages


@pytest.mark.parametrize("width", [1, 4, 7, 11, 16, 23, 32])
@pytest.mark.parametrize("n_pages", [1, 5])
def test_bitunpack_sweep(width, n_pages):
    rng = np.random.default_rng(width * 7 + n_pages)
    vals = rng.integers(0, 2 ** min(width, 31), size=(n_pages, 352),
                        dtype=np.uint64)
    words = np.stack([bitpack.pack(v, width) for v in vals])
    out = bitunpack_pages(jnp.asarray(words), width=width)
    out_ref = ref.bitunpack_pages_ref(jnp.asarray(words), width=width)
    npt.assert_array_equal(np.asarray(out), np.asarray(out_ref))
    npt.assert_array_equal(np.asarray(out)[:, :352], vals)


@pytest.mark.parametrize("n_dict,dtype", [
    (5, np.int32), (300, np.int32), (7, np.float32), (64, np.uint32)])
def test_dict_decode_sweep(n_dict, dtype):
    rng = np.random.default_rng(n_dict)
    width = bitpack.bit_width(max(1, n_dict - 1))
    codes = rng.integers(0, n_dict, size=(3, 224), dtype=np.uint64)
    words = np.stack([bitpack.pack(c, width) for c in codes])
    if dtype == np.float32:
        dictionary = rng.normal(size=n_dict).astype(dtype)
    else:
        dictionary = rng.integers(-500, 500, n_dict).astype(dtype)
    out = dict_decode_pages(jnp.asarray(words), jnp.asarray(dictionary),
                            width=width)
    out_ref = ref.dict_decode_pages_ref(jnp.asarray(words),
                                        jnp.asarray(dictionary),
                                        width=width)
    npt.assert_array_equal(np.asarray(out), np.asarray(out_ref))
    npt.assert_array_equal(np.asarray(out)[:, :224], dictionary[codes])


def _delta_pages_of_width(width: int, rng) -> list[np.ndarray]:
    """Three INT64 pages of 2900, 1500 and 700 values (3, 2 and 1 blocks)
    whose full blocks hold miniblocks of mixed widths, the widest exactly
    ``width``: each block's deltas are ``base + rel`` with ``base <= 0``,
    a zero ``rel`` in every miniblock and bit ``w - 1`` set in one."""
    pages = []
    for n in (2900, 1500, 700):
        n_blocks = -(-(n - 1) // 1024)
        rel = np.zeros((n_blocks, 4, 256), np.int64)
        base = rng.integers(-1000, 1, (n_blocks, 1, 1))
        for b in range(n_blocks):
            widths = rng.permutation(
                [width, width // 2, rng.integers(0, width + 1), 0])
            for m, w in enumerate(widths):
                if w:
                    rel[b, m] = rng.integers(0, 2 ** int(w), 256)
                    rel[b, m, 0] = 2 ** (int(w) - 1)
                    rel[b, m, 1] = 0
        deltas = (base + rel).reshape(-1)[:n - 1]
        first = rng.integers(-2 ** 20, 2 ** 20)
        pages.append(first + np.concatenate([[0], np.cumsum(deltas)]))
    return pages


# ints: three INT32 random walks of n_values; "w<k>": the three mixed-width
# INT64 pages above, widest miniblock k bits, padded to 4 blocks (the
# planner's pow2 class of 3) as delta_group_arrays pads them
@pytest.mark.parametrize("n_values", [
    1025, 4096, 10_000,
    *(pytest.param(f"w{w}", id=f"w{w}") for w in (1, 2, 7, 13, 16, 25, 31,
                                                   32))])
def test_delta_decode_sweep(n_values):
    from repro.core.encodings import (build_delta_manifest,
                                      decode_delta_page, encode_delta_page)
    from repro.core.schema import Field, PhysicalType
    from repro.kernels.ops import delta_group_arrays
    width = int(n_values[1:]) if isinstance(n_values, str) else 0
    if width:
        field = Field("c", PhysicalType.INT64)
        pages = _delta_pages_of_width(width, np.random.default_rng(width))
    else:
        field = Field("c", PhysicalType.INT32)
        rng = np.random.default_rng(n_values)
        pages = [np.cumsum(rng.integers(-3, 50, n_values)).astype(np.int32)
                 for _ in range(3)]
    encoded = [encode_delta_page(p, field) for p in pages]
    mans = [build_delta_manifest(e.payload, e.n_values, e.extra)
            for e in encoded]
    n_blocks = 4 if width else max(m["n_blocks"] for m in mans)
    args = [jnp.asarray(x) for x in delta_group_arrays(
        mans, [e.payload for e in encoded], n_blocks)]
    if width:
        assert width in args[2][0]
        if width > 1:                      # the writer's least width is 1
            assert len(set(np.asarray(args[2][0, :4]).tolist())) > 1
        assert np.asarray(args[1][0, 4:8] % 128).any()   # not 128-aligned
    out = np.asarray(delta_decode_pages(*args, n_blocks=n_blocks))
    out_ref = ref.delta_decode_pages_ref(*args, n_blocks=n_blocks)
    npt.assert_array_equal(out, np.asarray(out_ref))
    for i, (p, e) in enumerate(zip(pages, encoded)):
        host = decode_delta_page(e.payload, e.n_values, field, e.extra)
        npt.assert_array_equal(host, p)
        # the device path is int32: INT64 values wrap modulo 2**32
        npt.assert_array_equal(out[i, :p.shape[0]], p.astype(np.int32))


@pytest.mark.parametrize("max_run", [1, 50, 3000])
def test_rle_decode_sweep(max_run):
    rng = np.random.default_rng(max_run)
    n_runs = 40
    vals = rng.integers(-99, 99, size=(2, n_runs)).astype(np.int32)
    counts = rng.integers(1, max_run + 1,
                          size=(2, n_runs)).astype(np.int32)
    totals = counts.sum(axis=1)
    n_out = -(-int(totals.max()) // 1024) * 1024
    out = rle_decode_pages(jnp.asarray(vals), jnp.asarray(counts),
                           n_out=n_out)
    out_ref = ref.rle_decode_pages_ref(jnp.asarray(vals),
                                       jnp.asarray(counts), n_out=n_out)
    npt.assert_array_equal(np.asarray(out), np.asarray(out_ref))
    for i in range(2):
        expect = np.repeat(vals[i], counts[i])
        npt.assert_array_equal(np.asarray(out)[i, :totals[i]], expect)


@pytest.mark.parametrize("n", [64, 1000, 4093])
def test_bss_decode_sweep(n):
    rng = np.random.default_rng(n)
    pages = rng.normal(size=(2, n)).astype(np.float32)
    stride = (n + (-n) % 4) // 4

    def pack_page(p):
        planes = p.view(np.uint8).reshape(n, 4)
        body = b"".join(planes[:, s].tobytes()
                        + b"\x00" * ((-n) % 4) for s in range(4))
        return np.frombuffer(body, np.uint32)

    payload = np.stack([pack_page(p) for p in pages])
    out = bss_decode_pages(jnp.asarray(payload), stride_words=stride,
                           n_out=stride * 4)
    out_ref = ref.bss_decode_pages_ref(jnp.asarray(payload),
                                       stride_words=stride,
                                       n_out=stride * 4)
    npt.assert_array_equal(np.asarray(out), np.asarray(out_ref))
    npt.assert_array_equal(np.asarray(out)[:, :n], pages)


def test_cascade_decode_kernel():
    rng = np.random.default_rng(9)
    raw = np.repeat(rng.integers(0, 30, 50, dtype=np.uint32),
                    rng.integers(1, 200, 50)).tobytes()
    man = cascade_manifest(cascade_compress(raw))
    n_out = -(-man["n_words"] // 1024) * 1024
    out = cascade_decode_pages(
        jnp.asarray(man["value_words"][None]),
        jnp.asarray(man["count_words"][None]),
        value_width=man["value_width"], count_width=man["count_width"],
        n_runs=man["n_runs"], n_out=n_out)
    out_ref = ref.cascade_decode_pages_ref(
        jnp.asarray(man["value_words"][None]),
        jnp.asarray(man["count_words"][None]),
        value_width=man["value_width"], count_width=man["count_width"],
        n_runs=man["n_runs"], n_out=n_out)
    npt.assert_array_equal(np.asarray(out), np.asarray(out_ref))
    expect = np.frombuffer(raw, np.uint32)
    npt.assert_array_equal(np.asarray(out)[0, :man["n_words"]], expect)


def test_filter_agg_q6_kernel():
    rng = np.random.default_rng(10)
    n = TILE * 3
    key = rng.integers(0, 2000, n).astype(np.int32)
    qty = rng.integers(1, 51, n).astype(np.float32)
    disc = (rng.integers(0, 11, n) / 100).astype(np.float32)
    price = rng.normal(1000, 100, n).astype(np.float32)
    kw = dict(lo=731, hi=1096, dlo=0.05, dhi=0.07, qmax=24.0)
    out = filter_agg_q6(jnp.asarray(key), jnp.asarray(qty),
                        jnp.asarray(disc), jnp.asarray(price), **kw)
    out_ref = ref.filter_agg_q6_ref(jnp.asarray(key), jnp.asarray(qty),
                                    jnp.asarray(disc), jnp.asarray(price),
                                    **kw)
    npt.assert_allclose(float(out), float(out_ref), rtol=1e-5)


@pytest.mark.parametrize("b,s,h,kvh,dh,causal,cap", [
    (2, 256, 4, 2, 64, True, 0.0),
    (1, 384, 8, 8, 128, True, 50.0),
    (2, 128, 4, 1, 32, False, 0.0),
])
def test_flash_attention_kernel(b, s, h, kvh, dh, causal, cap):
    import jax
    from repro.kernels.flash_attention import flash_attention
    rng = np.random.default_rng(s + h)
    q = jnp.asarray(rng.normal(size=(b, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, kvh, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, kvh, dh)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, cap=cap,
                          q_block=128, kv_block=128)
    # oracle: materialized-softmax attention
    g = h // kvh
    qf = q.astype(jnp.float32).reshape(b, s, kvh, g, dh)
    sc = jnp.einsum("bqkgd,bskd->bqkgs", qf,
                    k.astype(jnp.float32)) * dh ** -0.5
    if cap:
        sc = cap * jnp.tanh(sc / cap)
    if causal:
        m = np.tril(np.ones((s, s), bool))
        sc = jnp.where(jnp.asarray(m)[None, :, None, None, :], sc, -1e30)
    w = jax.nn.softmax(sc, -1)
    ref = jnp.einsum("bqkgs,bskd->bqkgd", w,
                     v.astype(jnp.float32)).reshape(b, s, h, dh)
    npt.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_matches_model_blockwise():
    from repro.kernels.flash_attention import flash_attention
    from repro.models.attention import blockwise_attention
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 256, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 64)), jnp.float32)
    a = flash_attention(q, k, v, causal=True)
    b_ = blockwise_attention(q, k, v, causal=True)
    npt.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5)
