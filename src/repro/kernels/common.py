"""Shared in-kernel helpers for the TabFile decode kernels.

All decode kernels share three conventions (DESIGN.md §2):

* **grid = (num_pages, …)** — the paper's Insight 1 made structural: each
  grid step decodes one page, so the file's page count *is* the device
  parallelism, exactly as cuDF maps pages to its kernel grid.
* **bit-transposed packing** — a 32-value group with width ``w`` occupies
  ``w`` uint32 words; word ``k`` holds bit ``k`` of all 32 values.  Unpacking
  is ``w`` shift/mask/or steps over full vector lanes (VPU-shaped, no
  byte-serial dependencies).
* **pages as (rows, 128) tiles** — inside a kernel every page is a
  ``(rows, 128)`` block whose row ``r`` holds page values ``128r … 128r+127``.
  Blocks then meet the TPU's (8, 128) tiling with no in-kernel reshape, and
  every gather is a lane gather within one 128-lane row (the only gather the
  TPU lowering has).  The jitted wrappers do the (n_pages, n) ↔
  (n_pages, rows, 128) relayouts in XLA, outside the kernel.

``interpret_default()`` is True off-TPU: the Pallas interpreter is the CPU
test path only.  On a TPU every kernel compiles for the chip.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import trace

MB_GROUPS = 8          # packing groups per DELTA miniblock (256 values)
MB_VALUES = 256
BLOCK_VALUES = 1024
MINIBLOCKS = 4
LANES = 32             # values per packing group
ROW = 128              # values per tile row (TPU lane count)
CHUNK_ROWS = 32        # rows per in-kernel loop step (the uint8 (32, 128) tile)

# Pallas dispatch counter: every decode-kernel entry point increments this
# once per pallas_call it issues (outside jit, so retraces don't matter).
# The DecodePlan's launch economy — O(encoding groups) instead of
# O(columns × stride groups) per row group — is asserted against it.
# Lock-guarded: the pipeline executor's decode workers dispatch kernels
# concurrently with the consume thread.
_kernel_launches = 0
_launch_lock = threading.Lock()


def count_launch(n: int = 1) -> None:
    global _kernel_launches
    with _launch_lock:
        _kernel_launches += n
    tr = trace.active()
    if tr is not None:
        tr.instant("kernel_launch", "kernel", n=n)


def kernel_launch_count() -> int:
    return _kernel_launches


def interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def padded_rows(n_values: int) -> int:
    """Tile rows for ``n_values`` per page, a whole number of loop chunks."""
    return cdiv(max(cdiv(n_values, ROW), 1), CHUNK_ROWS) * CHUNK_ROWS


def pad_axis(x: jnp.ndarray, axis: int, size: int) -> jnp.ndarray:
    """Zero-pad (or truncate) ``x`` along ``axis`` to ``size``."""
    n = x.shape[axis]
    if n > size:
        return lax.slice_in_dim(x, 0, size, axis=axis)
    if n == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, size - n)
    return jnp.pad(x, pads)


def to_rows(x: jnp.ndarray, n_rows: int) -> jnp.ndarray:
    """(n_pages, n) → (n_pages, n_rows, 128), zero-padded (XLA side)."""
    return pad_axis(x, 1, n_rows * ROW).reshape(x.shape[0], n_rows, ROW)


def smem_pages(x: jnp.ndarray):
    """(n_pages, m) 32-bit scalars → (flat int32 array, SMEM BlockSpec)
    giving grid step ``i`` page ``i``'s row as a 1-D SMEM block.  Rows pad
    to whole 1024-word tiles, the tiling of 1-D arrays (XLA side)."""
    if x.dtype != jnp.int32:
        x = (lax.bitcast_convert_type(x, jnp.int32)
             if x.dtype.itemsize == 4 else x.astype(jnp.int32))
    stride = cdiv(max(x.shape[1], 1), 1024) * 1024
    return (pad_axis(x, 1, stride).reshape(-1),
            pl.BlockSpec((stride,), lambda i: (i,), memory_space=pltpu.SMEM))


def words_to_rows(words: jnp.ndarray, width: int, n_rows: int) -> jnp.ndarray:
    """Bit-transposed words (n_pages, G*width) → (n_pages, n_rows, 128).

    Row ``r`` holds the ``4*width`` words of packing groups 4r … 4r+3 (the
    groups of page values 128r … 128r+127), zero-padded to 128 lanes, so
    ``unpack_rows`` finds every word of a row inside that row (XLA side).
    """
    n_pages = words.shape[0]
    w = pad_axis(words, 1, n_rows * 4 * width)
    w = w.reshape(n_pages, n_rows, 4 * width)
    return pad_axis(w, 2, ROW)


def unpack_rows(words: jnp.ndarray, width: int) -> jnp.ndarray:
    """Unpack ``words_to_rows`` rows with a *static* width, in-kernel.

    words: (C, 128) uint32 → (C, 128) uint32 values.  Lane ``l`` of a row
    is value ``l % 32`` of the row's group ``l // 32``; bit ``k`` comes from
    word ``(l // 32) * width + k`` of the same row — one lane gather per bit.
    """
    lane = lax.broadcasted_iota(jnp.int32, words.shape, 1)
    base = (lane >> 5) * width
    j = (lane & (LANES - 1)).astype(jnp.uint32)
    vals = jnp.zeros(words.shape, jnp.uint32)
    for k in range(width):
        w = jnp.take_along_axis(words, base + k, axis=1)
        vals = vals | (((w >> j) & jnp.uint32(1)) << jnp.uint32(k))
    return vals


def dict_rows(dictionary: jnp.ndarray) -> jnp.ndarray:
    """(…, D) dictionary → (…, ceil(D/128), 128) int32 bit patterns, the
    layout ``lookup_rows`` gathers from (XLA side)."""
    if dictionary.dtype in (jnp.float32, jnp.uint32):
        d = lax.bitcast_convert_type(dictionary, jnp.int32)
    else:
        d = dictionary.astype(jnp.int32)
    n = dictionary.shape[-1]
    d = pad_axis(d, d.ndim - 1, cdiv(n, ROW) * ROW)
    return d.reshape(*d.shape[:-1], cdiv(n, ROW), ROW)


def from_dict_bits(x: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of ``dict_rows``'s dtype mapping on gathered values."""
    if dtype in (jnp.float32, jnp.uint32):
        return lax.bitcast_convert_type(x, dtype)
    return x.astype(dtype)


def lookup_rows(codes: jnp.ndarray, dict_ref) -> jnp.ndarray:
    """Dictionary lookup, in-kernel: compare-and-select over 128-entry
    dictionary rows, one lane gather each.

    codes: (C, 128) int32, already clipped to the dictionary;
    dict_ref: ref whose last two dims are (n_chunks, 128) int32.
    """
    n_chunks = dict_ref.shape[-2]
    lo = codes & (ROW - 1)
    hi = codes >> 7
    lead = (0,) * (len(dict_ref.shape) - 2)

    def row(c):
        r = dict_ref[(*lead, pl.ds(c, 1), slice(None))]
        return jnp.take_along_axis(jnp.broadcast_to(r, codes.shape), lo,
                                   axis=1)

    if n_chunks == 1:
        return row(0)

    def body(c, acc):
        return jnp.where(hi == c, row(c), acc)

    return lax.fori_loop(1, n_chunks, body, row(0))


def shift_in(x: jnp.ndarray, s: int, axis: int) -> jnp.ndarray:
    """``y[i] = x[i - s]`` along ``axis`` (zeros for ``i < s``), in-kernel.

    Built on the TPU rotate, which is cyclic; the rotate's direction is
    read off a rotated iota so the result never depends on it.
    """
    n = x.shape[axis]
    idx = lax.broadcasted_iota(jnp.int32, x.shape, axis)
    fwd = pltpu.roll(x, s, axis)
    src = pltpu.roll(idx, s, axis)
    back = (idx + (n - s)) & (n - 1)          # n is 8 or 128
    rolled = jnp.where(src == back, fwd, pltpu.roll(x, n - s, axis))
    return jnp.where(idx >= s, rolled, jnp.zeros_like(x))


def inclusive_scan_tile(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of an (8, 128) int32 tile in row-major order,
    in-kernel: log-step shifts along lanes, then along rows.  Exact in
    int32 (wrapping adds associate), so it equals ``jnp.cumsum``."""
    for s in (1, 2, 4, 8, 16, 32, 64):
        x = x + shift_in(x, s, 1)
    last = jnp.full(x.shape, ROW - 1, jnp.int32)
    tot = jnp.take_along_axis(x, last, axis=1)       # row totals, per lane
    acc = tot
    for s in (1, 2, 4):
        acc = acc + shift_in(acc, s, 0)
    return x + (acc - tot)
