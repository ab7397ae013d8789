"""Pallas kernel: BYTE_STREAM_SPLIT float32 reassembly (V2).

grid = (num_pages,).  A BSS page stores the 4 byte-planes of the float
stream contiguously (each plane padded to a word boundary); the kernel
re-interleaves them with word-level shifts and a bitcast — no byte-serial
work, ideal for the VPU.  float64 pages use the host path (x32 JAX).

Layout: each plane is a (rows, 128) block of words; a row of 128 plane
words feeds 512 output values, written as a (rows, 512) output block whose
lane quarter ``b`` takes words 32b … 32b+31 of the row, each spread over
four lanes by an in-row lane gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.common import (ROW, cdiv, count_launch, interpret_default,
                                  pad_axis)

STEP = 8  # rows per loop step


def _kernel(planes_ref, out_ref, *, n_rows: int):
    shape = (STEP, ROW)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    shift = ((lane & 3) * 8).astype(jnp.uint32)

    def body(i, carry):
        rows = pl.ds(pl.multiple_of(i * STEP, STEP), STEP)
        planes = [planes_ref[0, s, rows, :] for s in range(4)]
        for b in range(4):
            idx = 32 * b + (lane >> 2)
            out = jnp.zeros(shape, jnp.uint32)
            for s in range(4):
                w = jnp.take_along_axis(planes[s], idx, axis=1)
                out = out | (((w >> shift) & jnp.uint32(0xFF))
                             << jnp.uint32(8 * s))
            out_ref[0, rows, pl.ds(ROW * b, ROW)] = \
                lax.bitcast_convert_type(out, jnp.float32)
        return carry

    lax.fori_loop(0, n_rows // STEP, body, 0)


def bss_decode_pages(payload: jnp.ndarray, *, stride_words: int, n_out: int,
                     interpret: bool | None = None) -> jnp.ndarray:
    """payload: (n_pages, ≥4*stride_words) uint32 → (n_pages, n_out) f32."""
    if interpret is None:
        interpret = interpret_default()
    count_launch()
    return _bss_decode_pages_jit(payload, stride_words=stride_words,
                                 n_out=n_out, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("stride_words", "n_out", "interpret"))
def _bss_decode_pages_jit(payload, *, stride_words: int, n_out: int,
                          interpret: bool) -> jnp.ndarray:
    n_pages = payload.shape[0]
    n_rows = cdiv(max(cdiv(stride_words, ROW), 1), STEP) * STEP
    planes = pad_axis(payload, 1, 4 * stride_words).reshape(
        n_pages, 4, stride_words)
    planes = pad_axis(planes, 2, n_rows * ROW).reshape(
        n_pages, 4, n_rows, ROW)
    out = pl.pallas_call(
        functools.partial(_kernel, n_rows=n_rows),
        grid=(n_pages,),
        in_specs=[pl.BlockSpec((1, 4, n_rows, ROW),
                               lambda i: (i, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, n_rows, 4 * ROW), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pages, n_rows, 4 * ROW),
                                       jnp.float32),
        interpret=interpret,
    )(planes)
    return out.reshape(n_pages, n_rows * 4 * ROW)[:, :n_out]
