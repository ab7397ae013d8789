"""Pallas kernel: bit-transposed unpack (the primitive under DICT/DELTA).

grid = (num_pages,) — one grid step unpacks one page (Insight 1), as a
(rows, 128) block walked in CHUNK_ROWS-row steps (kernels/common.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.common import (CHUNK_ROWS, LANES, ROW, interpret_default,
                                  padded_rows, unpack_rows, words_to_rows)


def _kernel(words_ref, out_ref, *, width: int, n_rows: int):
    def body(i, carry):
        rows = pl.ds(pl.multiple_of(i * CHUNK_ROWS, CHUNK_ROWS), CHUNK_ROWS)
        out_ref[0, rows, :] = unpack_rows(words_ref[0, rows, :], width)
        return carry

    lax.fori_loop(0, n_rows // CHUNK_ROWS, body, 0)


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def bitunpack_pages(words: jnp.ndarray, *, width: int,
                    interpret: bool | None = None) -> jnp.ndarray:
    """words: (n_pages, G*width) uint32 → (n_pages, G*32) uint32."""
    if interpret is None:
        interpret = interpret_default()
    n_pages, n_words = words.shape
    n_vals = (n_words // width) * LANES
    n_rows = padded_rows(n_vals)
    out = pl.pallas_call(
        functools.partial(_kernel, width=width, n_rows=n_rows),
        grid=(n_pages,),
        in_specs=[pl.BlockSpec((1, n_rows, ROW), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, n_rows, ROW), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pages, n_rows, ROW), jnp.uint32),
        interpret=interpret,
    )(words_to_rows(words, width, n_rows))
    return out.reshape(n_pages, n_rows * ROW)[:, :n_vals]
