"""Pallas kernel: DELTA_BINARY_PACKED page decode (V2).

grid = (num_pages,).  Each grid step decodes one page: a fori_loop walks the
page's 1024-value blocks two at a time — one (8, 128) tile each, two rows
per miniblock — carrying the running prefix as a tile.

Miniblock widths (≤ 32 on the device path) are runtime values read from
SMEM, so a tile row's words sit at a dynamic offset of the page payload,
which the kernel holds as 128-word lines.  A row's four packing groups are
``4·w ≤ 128`` consecutive words, so they lie within two lines.  Per block:

* fetch: two single-line loads per row (16 in all), then one lane gather
  from each of the two puts word ``j`` of group ``g`` in lane ``(g, j)``:
  every word of the block is read once, whatever its width (lanes
  ``j ≥ w`` are zeroed);
* unpack: lane ``(g, j)`` now holds bit ``j`` of the group's 32 values
  (bit-transposed packing, core/bitpack.py), so a 32×32 bit transpose of
  each 32-lane segment — 5 butterfly steps of one lane gather and a few
  shifts, masks and selects — leaves value ``j`` in lane ``(g, j)``.

So a block costs 16 loads, 7 lane gathers and ~40 vector ops at every
width ``w`` from 1 to 32, with no loop over bits or lines.  After
min_delta an exact int32 log-step scan (kernels/common.py) turns deltas
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

from repro.kernels.common import (BLOCK_VALUES, LANES, MINIBLOCKS, ROW, cdiv,
                                  count_launch, inclusive_scan_tile,
                                  interpret_default, smem_pages, to_rows)

TAIL = 128  # lane-aligned tail block holding the final value


# the 32x32 bit transpose: at step ``s`` a lane with bit ``s`` of its index
# clear swaps its bits selected by ``mask << s`` with its partner's (lane
# ``+ s``) bits selected by ``mask``
_TRANSPOSE_STEPS = ((16, 0x0000FFFF), (8, 0x00FF00FF), (4, 0x0F0F0F0F),
                    (2, 0x33333333), (1, 0x55555555))


def _kernel(sc_ref, payload_ref, out_ref, *, n_blocks: int, n_lines: int):
    """sc_ref (SMEM, one page): [mb_off (n_mb) | mb_width (n_mb) |
    min_delta (n_blocks) | first]; payload_ref (1, n_lines, 128)."""
    n_mb = n_blocks * MINIBLOCKS
    shape = (8, ROW)
    sub = lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    group = lane >> 5                         # packing group within the row
    j = lane & (LANES - 1)

    def block(b, carry):
        # each tile row holds 4 packing groups of one miniblock (2 rows per
        # miniblock): 4·w ≤ 128 words from `start`, within 2 lines
        words_lo = jnp.zeros(shape, jnp.uint32)
        words_hi = jnp.zeros(shape, jnp.uint32)
        start_v = jnp.zeros(shape, jnp.int32)
        w_v = jnp.zeros(shape, jnp.int32)
        for r in range(8):
            m = b * MINIBLOCKS + (r >> 1)
            w = sc_ref[n_mb + m]
            start = sc_ref[m] + (r & 1) * 4 * w
            # only a width-0 row can start on the page's last line
            line = jnp.minimum(start // ROW, n_lines - 2)
            row = sub == r
            words_lo = jnp.where(row, jnp.broadcast_to(
                payload_ref[0, pl.ds(line, 1), :], shape), words_lo)
            words_hi = jnp.where(row, jnp.broadcast_to(
                payload_ref[0, pl.ds(line + 1, 1), :], shape), words_hi)
            start_v = jnp.where(row, start - line * ROW, start_v)
            w_v = jnp.where(row, w, w_v)
        # lane (g, j) takes word j of group g: bit j of the group's values
        pos = start_v + group * w_v + j
        idx = pos & (ROW - 1)
        bits = jnp.where(pos < ROW,
                         jnp.take_along_axis(words_lo, idx, axis=1),
                         jnp.take_along_axis(words_hi, idx, axis=1))
        bits = jnp.where(j < w_v, bits, jnp.uint32(0))
        # transpose each 32-lane segment's 32x32 bits: lane (g, j) ← value j
        for s, mask in _TRANSPOSE_STEPS:
            low = (j & s) == 0
            partner = jnp.take_along_axis(bits, lane ^ s, axis=1)
            lo_word = jnp.where(low, bits, partner)
            hi_word = jnp.where(low, partner, bits)
            t = ((lo_word >> jnp.uint32(s)) ^ hi_word) & jnp.uint32(mask)
            bits = bits ^ jnp.where(low, t << jnp.uint32(s), t)
        deltas = bits.astype(jnp.int32) + sc_ref[2 * n_mb + b]
        incl = inclusive_scan_tile(deltas)
        row0 = pl.multiple_of(b * 8, 8)
        out_ref[0, pl.ds(row0, 8), :] = carry + (incl - deltas)
        # broadcast the block total (row 7, lane 127) to every lane
        total = jnp.take_along_axis(incl, jnp.full(shape, ROW - 1, jnp.int32),
                                    axis=1)
        total = jnp.take_along_axis(total, jnp.full(shape, 7, jnp.int32),
                                    axis=0)
        return carry + total

    # two blocks a step: the second block's fetch and transpose do not wait
    # on the first block's carry, so the scheduler overlaps the two
    def pair(i, carry):
        return block(2 * i + 1, block(2 * i, carry))

    first = sc_ref[2 * n_mb + n_blocks]
    last = lax.fori_loop(0, n_blocks // 2, pair,
                         jnp.full(shape, first, jnp.int32))
    if n_blocks % 2:
        last = block(n_blocks - 1, last)
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
    n_lines = max(cdiv(n_words, ROW), 1) + 1
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
