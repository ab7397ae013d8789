"""Pallas kernel: DELTA_BINARY_PACKED page decode (V2).

grid = (num_pages,).  Each grid step decodes one page: a fori_loop walks the
page's 1024-value blocks — one (8, 128) tile each, two rows per miniblock —
carrying the running prefix as a tile.

Miniblock widths are per-miniblock runtime values, so the unpack reads the
page payload as 128-word lines: a block's words span a few lines starting
at a dynamic row (``pl.ds`` on the sublane axis), and every tile lane picks
its word out of those lines with one in-row lane gather per line.  After
min_delta, an exact int32 log-step scan (kernels/common.py) turns deltas
into values.  Per-miniblock offsets/widths, min_deltas and the first value
ride in SMEM, one row per page.

Device path is int32 (x32 JAX); ops.py routes int64-range pages to the host
decoder.  The varint-free page manifests (encodings.build_delta_manifest)
supply per-miniblock word offsets/widths so the kernel never parses headers —
the same split cuDF uses (lightweight header pass, bulk decode pass).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.common import (BLOCK_VALUES, MB_GROUPS, MINIBLOCKS, ROW,
                                  cdiv, count_launch, inclusive_scan_tile,
                                  interpret_default, smem_pages, to_rows)

TAIL = 128  # lane-aligned tail block holding the final value


def _kernel(sc_ref, payload_ref, out_ref, *, n_blocks: int, n_lines: int):
    """sc_ref (SMEM, one page): [mb_off (n_mb) | mb_width (n_mb) |
    min_delta (n_blocks) | first]; payload_ref (1, n_lines, 128)."""
    n_mb = n_blocks * MINIBLOCKS
    shape = (8, ROW)
    sub = lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    mb_of_row = sub >> 1                      # 2 rows (256 values) per mb
    group = (sub & 1) * 4 + (lane >> 5)       # packing group within the mb
    j = (lane & 31).astype(jnp.uint32)

    def block(b, carry):
        offs = [sc_ref[b * MINIBLOCKS + m] for m in range(MINIBLOCKS)]
        wids = [sc_ref[n_mb + b * MINIBLOCKS + m] for m in range(MINIBLOCKS)]
        line0 = offs[0] // ROW
        end = offs[-1] + MB_GROUPS * wids[-1]          # offsets ascend
        lines = jnp.clip((end - line0 * ROW + ROW - 1) // ROW, 0,
                         n_lines - line0)
        off_v = jnp.zeros(shape, jnp.int32)
        w_v = jnp.zeros(shape, jnp.int32)
        for m in range(MINIBLOCKS):
            off_v = jnp.where(mb_of_row == m, offs[m], off_v)
            w_v = jnp.where(mb_of_row == m, wids[m], w_v)
        base = off_v - line0 * ROW + group * w_v
        w_max = jnp.maximum(jnp.maximum(wids[0], wids[1]),
                            jnp.maximum(wids[2], wids[3]))

        def bit(k, rel):
            idx = base + k
            line_of, lane_of = idx >> 7, idx & (ROW - 1)

            def pick(t, word):
                line = payload_ref[0, pl.ds(line0 + t, 1), :]
                got = jnp.take_along_axis(jnp.broadcast_to(line, shape),
                                          lane_of, axis=1)
                return jnp.where(line_of == t, got, word)

            word = lax.fori_loop(0, lines, pick,
                                 jnp.zeros(shape, jnp.uint32))
            b_k = ((word >> j) & jnp.uint32(1)) << k.astype(jnp.uint32)
            return rel | jnp.where(k < w_v, b_k, jnp.uint32(0))

        rel = lax.fori_loop(0, w_max, bit, jnp.zeros(shape, jnp.uint32))
        deltas = rel.astype(jnp.int32) + sc_ref[2 * n_mb + b]
        incl = inclusive_scan_tile(deltas)
        row0 = pl.multiple_of(b * 8, 8)
        out_ref[0, pl.ds(row0, 8), :] = carry + (incl - deltas)
        # broadcast the block total (row 7, lane 127) to every lane
        total = jnp.take_along_axis(incl, jnp.full(shape, ROW - 1, jnp.int32),
                                    axis=1)
        total = jnp.take_along_axis(total, jnp.full(shape, 7, jnp.int32),
                                    axis=0)
        return carry + total

    first = sc_ref[2 * n_mb + n_blocks]
    last = lax.fori_loop(0, n_blocks, block,
                         jnp.full(shape, first, jnp.int32))
    # deltas count n-1: the final value (index n_blocks*1024) lands in the
    # tail lane block
    out_ref[0, pl.ds(n_blocks * 8, 1), :] = last[0:1, :]


def delta_decode_pages(payload: jnp.ndarray, mb_off: jnp.ndarray,
                       mb_width: jnp.ndarray, min_delta: jnp.ndarray,
                       first_value: jnp.ndarray, *, n_blocks: int,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Decode DELTA_BINARY_PACKED pages.

    payload     (n_pages, W)        uint32, padded page payloads
    mb_off      (n_pages, n_blocks*4) int32, miniblock word offsets
    mb_width    (n_pages, n_blocks*4) int32
    min_delta   (n_pages, n_blocks) int32
    first_value (n_pages, 1)        int32
    → (n_pages, n_blocks*1024 + 128) int32  (exclusive-cumsum semantics:
      position 0 is first_value; the final value — index n_blocks*1024 when
      the page holds exactly n_blocks·1024 deltas — fills the tail block)
    """
    if interpret is None:
        interpret = interpret_default()
    count_launch()
    return _delta_decode_pages_jit(payload, mb_off, mb_width, min_delta,
                                   first_value, n_blocks=n_blocks,
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_blocks", "interpret"))
def _delta_decode_pages_jit(payload, mb_off, mb_width, min_delta,
                            first_value, *, n_blocks: int,
                            interpret: bool) -> jnp.ndarray:
    n_pages, n_words = payload.shape
    sc, sc_spec = smem_pages(jnp.concatenate(
        [mb_off.astype(jnp.int32), mb_width.astype(jnp.int32),
         min_delta.astype(jnp.int32), first_value.astype(jnp.int32)],
        axis=1))
    n_lines = cdiv(n_words, ROW) + 1
    n_rows = n_blocks * 8 + 1
    out = pl.pallas_call(
        functools.partial(_kernel, n_blocks=n_blocks, n_lines=n_lines),
        grid=(n_pages,),
        in_specs=[sc_spec,
                  pl.BlockSpec((1, n_lines, ROW), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, n_rows, ROW), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pages, n_rows, ROW), jnp.int32),
        interpret=interpret,
    )(sc, to_rows(payload, n_lines))
    return out.reshape(n_pages, n_blocks * BLOCK_VALUES + TAIL)
