"""Pallas kernel: fused predicate + aggregate (the TPC-H Q6 hot loop).

Beyond-paper: the paper overlaps the *reading* stage with query operators;
fusing the Q6 filter+aggregate into one kernel removes a full HBM round-trip
of the filtered columns.  grid = (num_tiles,) over the decoded column
stream — (64, 128) tiles — and each tile emits one partial sum,
reduced outside.

Predicate (Q6 shape):  lo <= key < hi  AND  dlo <= disc <= dhi  AND
qty < qmax;  aggregate: sum(price * disc).
Padding convention: tiles are padded with key = INT32_MAX (predicate false).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import ROW, interpret_default

TILE = 8192
TILE_ROWS = TILE // ROW


def _kernel(key_ref, qty_ref, disc_ref, price_ref, out_ref, *,
            lo: int, hi: int, dlo: float, dhi: float, qmax: float):
    key = key_ref[...]
    disc = disc_ref[...]
    mask = ((key >= lo) & (key < hi)
            & (disc >= dlo) & (disc <= dhi)
            & (qty_ref[...] < qmax))
    part = jnp.sum(jnp.where(mask, price_ref[...] * disc, jnp.float32(0)))
    out_ref[0] = jnp.full((1, ROW), part, jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "lo", "hi", "dlo", "dhi", "qmax", "interpret"))
def filter_agg_q6(key: jnp.ndarray, qty: jnp.ndarray, disc: jnp.ndarray,
                  price: jnp.ndarray, *, lo: int, hi: int, dlo: float,
                  dhi: float, qmax: float,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Inputs: (n,) padded to TILE multiple (key padding = INT32_MAX).

    Returns scalar float32 revenue.
    """
    if interpret is None:
        interpret = interpret_default()
    n = key.shape[0]
    assert n % TILE == 0, "pad inputs to TILE"
    n_tiles = n // TILE
    tile = pl.BlockSpec((TILE_ROWS, ROW), lambda i: (i, 0))
    partials = pl.pallas_call(
        functools.partial(_kernel, lo=lo, hi=hi, dlo=dlo, dhi=dhi,
                          qmax=qmax),
        grid=(n_tiles,),
        in_specs=[tile, tile, tile, tile],
        out_specs=pl.BlockSpec((1, 1, ROW), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, ROW), jnp.float32),
        interpret=interpret,
    )(*(x.reshape(n // ROW, ROW) for x in (key, qty, disc, price)))
    return jnp.sum(partials[:, 0, 0])
