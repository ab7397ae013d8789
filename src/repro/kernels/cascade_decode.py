"""Pallas kernel: CASCADE codec decompression (TPU-native, beyond-paper).

The cascade frame is word-level RLE with bit-transposed packed run values
and counts (core/compression.py).  Decompression = two static-width unpacks
+ run expansion, i.e. exactly the vector primitives the VPU is good at —
this is the TPU-idiomatic replacement for GPU Snappy kernels (DESIGN.md §2).

grid = (num_pages,).  The packed run words ride in SMEM; each run's value
and count unpack as scalars (``width`` bit reads of one group's words) and
the run expansion paints rows like rle_decode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (LANES, ROW, cdiv, count_launch,
                                  interpret_default, pad_axis, smem_pages)
from repro.kernels.rle_decode import paint_runs

TILE = 1024


def _scalar_unpack(words_ref, k, width: int):
    """Value ``k`` of a bit-transposed stream, as a uint32 scalar."""
    k = jnp.asarray(k, jnp.int32)
    base = (k // LANES) * width
    j = (k % LANES).astype(jnp.uint32)
    v = jnp.uint32(0)
    for b in range(width):
        w = words_ref[base + b].astype(jnp.uint32)
        v = v | (((w >> j) & jnp.uint32(1)) << jnp.uint32(b))
    return v


def _kernel(val_words_ref, cnt_words_ref, out_ref, *,
            value_width: int, count_width: int, n_runs: int, n_rows: int):
    paint_runs(
        out_ref, n_runs, n_rows,
        lambda k: _scalar_unpack(val_words_ref, k, value_width),
        lambda k: _scalar_unpack(cnt_words_ref, k,
                                 count_width).astype(jnp.int32))


def cascade_decode_pages(val_words: jnp.ndarray, cnt_words: jnp.ndarray, *,
                         value_width: int, count_width: int, n_runs: int,
                         n_out: int, interpret: bool | None = None
                         ) -> jnp.ndarray:
    """val_words/cnt_words: (n_pages, Wv)/(n_pages, Wc) uint32.

    n_runs: padded run count (common to the batch; padding runs count 0).
    n_out: output words per page, multiple of TILE.
    → (n_pages, n_out) uint32 — the decompressed page payload words.
    """
    if interpret is None:
        interpret = interpret_default()
    count_launch()
    return _cascade_decode_pages_jit(val_words, cnt_words,
                                     value_width=value_width,
                                     count_width=count_width,
                                     n_runs=n_runs, n_out=n_out,
                                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "value_width", "count_width", "n_runs", "n_out", "interpret"))
def _cascade_decode_pages_jit(val_words, cnt_words, *,
                              value_width: int, count_width: int,
                              n_runs: int, n_out: int,
                              interpret: bool) -> jnp.ndarray:
    n_pages = val_words.shape[0]
    assert n_out % TILE == 0
    n_rows = n_out // ROW

    # one SMEM row per page holding every group of the run table
    vw, vspec = smem_pages(pad_axis(val_words, 1,
                                    cdiv(n_runs, LANES) * value_width))
    cw, cspec = smem_pages(pad_axis(cnt_words, 1,
                                    cdiv(n_runs, LANES) * count_width))
    out = pl.pallas_call(
        functools.partial(_kernel, value_width=value_width,
                          count_width=count_width, n_runs=n_runs,
                          n_rows=n_rows),
        grid=(n_pages,),
        in_specs=[vspec, cspec],
        out_specs=pl.BlockSpec((1, n_rows, ROW), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pages, n_rows, ROW), jnp.uint32),
        interpret=interpret,
    )(vw, cw)
    return out.reshape(n_pages, n_out)
