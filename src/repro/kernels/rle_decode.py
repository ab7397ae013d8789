"""Pallas kernel: RLE run expansion.

grid = (num_pages,) — page-parallel (Insight 1).  A page's run values and
counts ride in SMEM; the kernel fills the page's (rows, 128) output block
with the padding value, then walks the runs in order and paints each run's
rows with a masked select.  Work is O(runs + rows) per page, and no
gather or prefix-sum primitive is needed.  ops.py bounds the run count and
falls back to the host for high-run-count pages, where RLE would not have
been selected anyway (Insight 3 picks the smallest encoding).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.common import (ROW, count_launch, interpret_default,
                                  smem_pages)

TILE = 1024


def paint_runs(out_ref, n_runs: int, n_rows: int, value, count) -> None:
    """Expand runs into ``out_ref[0]`` ((n_rows, 128) block), in-kernel.

    ``value(k)`` / ``count(k)`` read run ``k`` as scalars.  Positions past
    the last run keep the value of run ``n_runs - 1`` (padding runs count
    0), matching ``ref.rle_decode_pages_ref``'s clipped run index.
    """
    lane = lax.broadcasted_iota(jnp.int32, (1, ROW), 1)
    fill = jnp.full((8, ROW), value(n_runs - 1), out_ref.dtype)

    def fill_rows(i, carry):
        out_ref[0, pl.ds(pl.multiple_of(i * 8, 8), 8), :] = fill
        return carry

    lax.fori_loop(0, n_rows // 8, fill_rows, 0)

    def run(k, start):
        end = start + count(k)
        v = value(k)

        def paint(r, carry):
            pos = r * ROW + lane
            cur = out_ref[0, pl.ds(r, 1), :]
            out_ref[0, pl.ds(r, 1), :] = jnp.where(
                (pos >= start) & (pos < end), v, cur)
            return carry

        lax.fori_loop(start // ROW,
                      jnp.minimum((end + ROW - 1) // ROW, n_rows), paint, 0)
        return end

    lax.fori_loop(0, n_runs, run, jnp.int32(0))


def _kernel(vals_ref, counts_ref, out_ref, *, n_runs: int, n_rows: int):
    paint_runs(out_ref, n_runs, n_rows, lambda k: vals_ref[k],
               lambda k: counts_ref[k])


def rle_decode_pages(run_values: jnp.ndarray, run_counts: jnp.ndarray,
                     *, n_out: int, interpret: bool | None = None
                     ) -> jnp.ndarray:
    """run_values/run_counts: (n_pages, R) int32 (padding runs have count 0).

    n_out: padded output length per page (multiple of TILE).
    → (n_pages, n_out) int32; positions past a page's true value count hold
    the last run's value (callers slice by true counts).
    """
    if interpret is None:
        interpret = interpret_default()
    count_launch()
    return _rle_decode_pages_jit(run_values, run_counts, n_out=n_out,
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_out", "interpret"))
def _rle_decode_pages_jit(run_values, run_counts, *, n_out: int,
                          interpret: bool) -> jnp.ndarray:
    n_pages, r = run_values.shape
    assert n_out % TILE == 0
    n_rows = n_out // ROW
    vals, vspec = smem_pages(run_values)
    counts, cspec = smem_pages(run_counts)
    out = pl.pallas_call(
        functools.partial(_kernel, n_runs=r, n_rows=n_rows),
        grid=(n_pages,),
        in_specs=[vspec, cspec],
        out_specs=pl.BlockSpec((1, n_rows, ROW), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pages, n_rows, ROW), jnp.int32),
        interpret=interpret,
    )(vals, counts)
    return out.reshape(n_pages, n_out)
