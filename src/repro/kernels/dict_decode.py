"""Pallas kernel: RLE_DICTIONARY page decode (unpack codes + lookup).

grid = (num_pages,).  Each page is a (rows, 128) block (kernels/common.py);
the kernel walks it in CHUNK_ROWS-row steps: unpack the codes with one lane
gather per bit, then look them up with compare-and-select over 128-entry
dictionary rows (the TPU lowers only in-row lane gathers, so a dictionary
of D entries costs ceil(D/128) gathers per tile).  The dictionary stays in
VMEM for the whole call (one dictionary per column chunk).
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.lru import ByteCappedLRU
from repro.kernels.common import (CHUNK_ROWS, LANES, ROW, count_launch,
                                  dict_rows, from_dict_bits,
                                  interpret_default, lookup_rows,
                                  padded_rows, unpack_rows, words_to_rows)


def _kernel(words_ref, dict_ref, out_ref, *, width: int, d: int,
            n_rows: int):
    def body(i, carry):
        rows = pl.ds(pl.multiple_of(i * CHUNK_ROWS, CHUNK_ROWS), CHUNK_ROWS)
        codes = unpack_rows(words_ref[0, rows, :], width).astype(jnp.int32)
        codes = jnp.clip(codes, 0, d - 1)
        out_ref[0, rows, :] = lookup_rows(codes, dict_ref)
        return carry

    lax.fori_loop(0, n_rows // CHUNK_ROWS, body, 0)


def _decode(words, drows, *, width: int, d: int, shared: bool,
            interpret: bool):
    """Shared pallas_call: drows is (n_chunks, 128) (one dictionary) or
    (n_pages, n_chunks, 128) (one per page).  Returns int32 bit patterns
    (n_pages, n_vals)."""
    n_pages, n_words = words.shape
    n_vals = (n_words // width) * LANES
    n_rows = padded_rows(n_vals)
    rows = words_to_rows(words, width, n_rows)
    if shared:
        dspec = pl.BlockSpec(drows.shape, lambda i: (0, 0))
    else:
        dspec = pl.BlockSpec((1, *drows.shape[1:]), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, width=width, d=d, n_rows=n_rows),
        grid=(n_pages,),
        in_specs=[pl.BlockSpec((1, n_rows, ROW), lambda i: (i, 0, 0)),
                  dspec],
        out_specs=pl.BlockSpec((1, n_rows, ROW), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pages, n_rows, ROW), jnp.int32),
        interpret=interpret,
    )(rows, drows)
    return out.reshape(n_pages, n_rows * ROW)[:, :n_vals]


def dict_decode_pages(words: jnp.ndarray, dictionary: jnp.ndarray, *,
                      width: int, interpret: bool | None = None
                      ) -> jnp.ndarray:
    """words: (n_pages, G*width) uint32; dictionary: (D,) int32/uint32/f32
    (or uint8).

    Returns (n_pages, G*32) of dictionary.dtype.
    """
    if interpret is None:
        interpret = interpret_default()
    count_launch()
    return _dict_decode_pages_jit(words, dictionary, width=width,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def _dict_decode_pages_jit(words, dictionary, *, width: int,
                           interpret: bool) -> jnp.ndarray:
    out = _decode(words, dict_rows(dictionary), width=width,
                  d=dictionary.shape[0], shared=True, interpret=interpret)
    return from_dict_bits(out, dictionary.dtype)


def dict_decode_pages_multi(words: jnp.ndarray, dictionaries: jnp.ndarray, *,
                            width: int, interpret: bool | None = None
                            ) -> jnp.ndarray:
    """Cross-column batched variant: one dictionary row *per page*.

    words: (n_pages, G*width) uint32; dictionaries: (n_pages, D) — row i is
    page i's (padded) dictionary, so pages of many column chunks decode in
    a single pallas_call (the DecodePlan group path).
    Returns (n_pages, G*32) of dictionaries.dtype.
    """
    if interpret is None:
        interpret = interpret_default()
    count_launch()
    return _dict_decode_pages_multi_jit(words, dictionaries, width=width,
                                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def _dict_decode_pages_multi_jit(words, dictionaries, *, width: int,
                                 interpret: bool) -> jnp.ndarray:
    out = _decode(words, dict_rows(dictionaries), width=width,
                  d=dictionaries.shape[1], shared=False,
                  interpret=interpret)
    return from_dict_bits(out, dictionaries.dtype)


# ---------------------------------------------------------------------------
# device-resident dictionary cache
#
# A dictionary page decodes to the same array every time a scan revisits its
# chunk (repeated queries over one file, Q6 then Q12, the serving loop).
# Caching the decoded dictionary — and its device copy — skips both the host
# PLAIN-decode and the host→device staging on every revisit.  Keyed by
# (file token, column, dict-page offset): the token carries st_size/mtime so
# a same-path rewrite can never serve a stale dictionary.
# ---------------------------------------------------------------------------

class CachedDictionary:
    """One decoded dictionary: host array + lazily materialized device copy.

    The device copy is built on first use and then stays resident, so row
    groups that share a dictionary shape — and repeated scans of the same
    row group — reuse one device buffer instead of re-staging per launch.
    """

    __slots__ = ("host", "_device", "_lock")

    def __init__(self, host):
        self.host = host
        self._device = None
        self._lock = threading.Lock()

    @property
    def device(self) -> jnp.ndarray:
        if self._device is None:
            with self._lock:
                if self._device is None:
                    self._device = jnp.asarray(self.host)
        return self._device

    @property
    def nbytes(self) -> int:
        return int(self.host.nbytes)


_DICT_CACHE = ByteCappedLRU(64 * 1024 * 1024, lambda e: e.nbytes)


def dict_cache_get(key: tuple) -> CachedDictionary | None:
    return _DICT_CACHE.get(key)


def dict_cache_put(key: tuple, host_array) -> CachedDictionary:
    return _DICT_CACHE.put(key, CachedDictionary(host_array))


def dict_cache_evict(pred) -> int:
    """Evict entries whose key matches ``pred`` (fault recovery: drop
    dictionaries a failed/retried scan may have decoded from bad bytes)."""
    return _DICT_CACHE.pop_matching(pred)


def dict_cache_stats() -> dict:
    return {"entries": len(_DICT_CACHE), "bytes": _DICT_CACHE.bytes,
            "hits": _DICT_CACHE.hits, "misses": _DICT_CACHE.misses}


def dict_cache_clear() -> None:
    _DICT_CACHE.clear()
