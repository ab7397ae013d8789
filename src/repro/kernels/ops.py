"""Device decode entry points: chunk payloads → padded arrays → kernels.

This is the cuDF-reader analogue: a lightweight host pass turns varint-free
page headers/manifests into flat int32 arrays, pages are stacked into padded
(n_pages, …) batches, and one Pallas call per column chunk decodes every
page in parallel (grid = page count — Insight 1).

Dispatch rules (documented fallbacks, DESIGN.md §2):
  * numeric int32/float32 payloads decode on device;
  * int64 pages whose chunk stats fit int32 are narrowed, otherwise host;
  * strings and float64 decode on the host path;
  * gzip chunks are host-decompressed first (no TPU LZ77); cascade chunks
    are decompressed on-device by cascade_decode_pages.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import Counter
from collections.abc import Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.compression import (Codec, cascade_manifest, decompress,
                                    verify_page)
from repro.core.encodings import (Encoding, build_delta_manifest,
                                  decode_page, decode_plain_page)
from repro.core.metadata import ChunkMeta, PageMeta
from repro.core.schema import Field, PhysicalType
from repro.kernels.bss_decode import bss_decode_pages
from repro.kernels.cascade_decode import cascade_decode_pages
from repro.kernels.delta_decode import delta_decode_pages
from repro.kernels.dict_decode import (dict_decode_pages,
                                       dict_decode_pages_multi)
from repro.kernels.rle_decode import rle_decode_pages

_INT32_SAFE = 2 ** 30  # conservative: keeps deltas within int32 too
_RLE_MAX_RUNS = 8192   # beyond this the host path wins (and Insight 3 would
                       # not have selected RLE anyway)


# Columns a use_kernels decode sent to the host decoders (process-wide
# counts by column name).  DESIGN.md §2.2 allows it for strings, float64
# and out-of-range int64; a numeric TPC-H scan column should never land here.
_host_fallbacks: Counter = Counter()
_fallback_lock = threading.Lock()


def host_fallback_counts() -> Counter:
    with _fallback_lock:
        return Counter(_host_fallbacks)


@dataclasses.dataclass
class DecodeResult:
    array: object              # jnp.ndarray (device) or np/StringColumn (host)
    on_device: bool
    n_values: int
    encoding: int
    codec: int
    stored_bytes: int          # bytes moved from storage
    logical_bytes: int         # decoded raw bytes (effective-bw numerator)


def _stack_pad_u32(payloads: Sequence[bytes]) -> np.ndarray:
    words = [np.frombuffer(p, dtype=np.uint32) for p in payloads]
    w = max((x.shape[0] for x in words), default=1)
    w = max(w, 1)
    out = np.zeros((len(words), w), dtype=np.uint32)
    for i, x in enumerate(words):
        out[i, :x.shape[0]] = x
    return out


def _stack_pad(arrs: Sequence[np.ndarray], width: int, dtype) -> np.ndarray:
    out = np.zeros((len(arrs), max(width, 1)), dtype=dtype)
    for i, a in enumerate(arrs):
        out[i, :a.shape[0]] = a
    return out


def _compact(batch: jnp.ndarray, counts: Sequence[int]) -> jnp.ndarray:
    """(n_pages, P) → (sum counts,) honoring per-page true value counts."""
    rpp = counts[0] if counts else 0
    total = sum(counts)
    if all(c == rpp for c in counts[:-1]) and batch.shape[1] >= rpp:
        return batch[:, :rpp].reshape(-1)[:total]
    return jnp.concatenate([batch[i, :c] for i, c in enumerate(counts)])


def _stats_fit_int32(chunk: ChunkMeta) -> bool:
    s = chunk.stats
    return (s is not None and isinstance(s.get("min"), int)
            and -_INT32_SAFE <= s["min"] <= _INT32_SAFE
            and -_INT32_SAFE <= s["max"] <= _INT32_SAFE)


# ---------------------------------------------------------------------------
# group-level device decoders (pre-batched inputs)
#
# These accept already-batched (n_pages, …) arrays so a caller may batch
# pages from *many* column chunks into one pallas_call (the DecodePlan path,
# core/decode_plan.py).  The per-chunk decoders below are thin assemblers
# over these and remain the reference/fallback path.
# ---------------------------------------------------------------------------

def decode_dict_group(words: np.ndarray, dictionaries: np.ndarray,
                      width: int) -> jnp.ndarray:
    """words (n_pages, G*width) u32; dictionaries (n_pages, D) — one padded
    dictionary row per page (pages may come from different columns)."""
    return dict_decode_pages_multi(jnp.asarray(words),
                                   jnp.asarray(dictionaries), width=width)


def decode_dict_group_shared(words: np.ndarray, dictionary: np.ndarray,
                             width: int) -> jnp.ndarray:
    """Single-column group: one dictionary shared by every page — no
    per-page duplication (same kernel as the per-chunk reference path)."""
    return dict_decode_pages(jnp.asarray(words), jnp.asarray(dictionary),
                             width=width)


def decode_delta_group(payload: np.ndarray, mb_off: np.ndarray,
                       mb_width: np.ndarray, min_delta: np.ndarray,
                       first: np.ndarray, n_blocks: int) -> jnp.ndarray:
    return delta_decode_pages(
        jnp.asarray(payload), jnp.asarray(mb_off), jnp.asarray(mb_width),
        jnp.asarray(min_delta), jnp.asarray(first), n_blocks=n_blocks)


def decode_rle_group(vals: np.ndarray, counts: np.ndarray,
                     n_out: int) -> jnp.ndarray:
    return rle_decode_pages(jnp.asarray(vals), jnp.asarray(counts),
                            n_out=n_out)


def decode_bss_group(payload: np.ndarray, stride: int) -> jnp.ndarray:
    return bss_decode_pages(jnp.asarray(payload), stride_words=stride,
                            n_out=stride * 4)


def delta_group_arrays(mans: Sequence[dict], payloads: Sequence[bytes],
                       n_blocks: int) -> tuple[np.ndarray, ...]:
    """Assemble the batched host arrays for a DELTA group.  ``n_blocks`` may
    exceed any page's true block count (class padding): padded miniblocks get
    width 0 / min_delta 0, which the kernel decodes as constant carry —
    positions below each page's n_values are unaffected."""
    n_mb = n_blocks * 4
    payload = _stack_pad_u32(payloads)
    mb_off = _stack_pad([m["mb_off"] for m in mans], n_mb, np.int32)
    mb_width = _stack_pad([m["mb_width"] for m in mans], n_mb, np.int32)
    min_delta = _stack_pad(
        [m["min_delta"][:m["n_blocks"]].astype(np.int32) for m in mans],
        n_blocks, np.int32)
    first = np.array([[m["first_value"]] for m in mans], dtype=np.int32)
    return payload, mb_off, mb_width, min_delta, first


def rle_group_arrays(pages_runs: Sequence[tuple[np.ndarray, np.ndarray]]
                     ) -> tuple[np.ndarray, np.ndarray]:
    """(vals, counts) per page → padded (n_pages, R) int32 pair."""
    r_max = max(max((v.shape[0] for v, _ in pages_runs), default=1), 1)
    vals = _stack_pad([v for v, _ in pages_runs], r_max, np.int32)
    counts = _stack_pad([c for _, c in pages_runs], r_max, np.int32)
    return vals, counts


# ---------------------------------------------------------------------------
# per-encoding device decoders (per-chunk reference path)
# ---------------------------------------------------------------------------

def _decode_plain_device(pages, field):
    dt = {PhysicalType.INT32: np.int32, PhysicalType.FLOAT: np.float32,
          PhysicalType.BOOLEAN: np.uint8}.get(field.physical)
    if dt is None:
        return None
    parts = [np.frombuffer(p, dtype=dt, count=pm.n_values)
             for pm, p in pages]
    return jnp.asarray(np.concatenate(parts))  # PLAIN decode is a memcpy


def _decode_dict_device(chunk, field, dict_payload, pages):
    if field.physical == PhysicalType.BYTE_ARRAY:
        return None
    dp = chunk.dict_page
    dictionary = decode_plain_page(dict_payload, dp.n_values, field, dp.extra)
    if field.physical == PhysicalType.INT64:
        if not _stats_fit_int32(chunk):
            return None
        dictionary = dictionary.astype(np.int32)
    elif field.physical == PhysicalType.DOUBLE:
        return None
    elif field.physical == PhysicalType.BOOLEAN:
        dictionary = dictionary.astype(np.uint8)
    width = pages[0][0].extra["bitwidth"]
    words = _stack_pad_u32([p for _, p in pages])
    out = dict_decode_pages(jnp.asarray(words), jnp.asarray(dictionary),
                            width=width)
    return _compact(out, [pm.n_values for pm, _ in pages])


def _decode_delta_device(chunk, field, pages):
    if not _stats_fit_int32(chunk):
        return None
    mans = [build_delta_manifest(p, pm.n_values, pm.extra)
            for pm, p in pages]
    n_blocks = max(m["n_blocks"] for m in mans)
    if n_blocks == 0:
        return None
    if any(abs(int(m["min_delta"].min(initial=0))) > _INT32_SAFE
           for m in mans):
        return None
    arrays = delta_group_arrays(mans, [p for _, p in pages], n_blocks)
    out = decode_delta_group(*arrays, n_blocks=n_blocks)
    return _compact(out, [pm.n_values for pm, _ in pages])


def _decode_rle_device(chunk, field, pages):
    if field.physical == PhysicalType.INT64 and not _stats_fit_int32(chunk):
        return None
    vdt = np.int64 if field.physical == PhysicalType.INT64 else np.int32
    vals, counts = [], []
    for pm, p in pages:
        r = pm.extra["n_runs"]
        if r > _RLE_MAX_RUNS:
            return None
        vals.append(np.frombuffer(p, dtype=vdt, count=r).astype(np.int32))
        counts.append(np.frombuffer(p, dtype=np.int32, count=r,
                                    offset=r * np.dtype(vdt).itemsize))
    max_nv = max(pm.n_values for pm, _ in pages)
    n_out = -(-max_nv // 1024) * 1024
    bvals, bcounts = rle_group_arrays(list(zip(vals, counts)))
    out = decode_rle_group(bvals, bcounts, n_out=n_out)
    res = _compact(out, [pm.n_values for pm, _ in pages])
    if field.physical == PhysicalType.BOOLEAN:
        res = res.astype(jnp.uint8)
    return res


def _decode_bss_device(chunk, field, pages):
    if field.physical != PhysicalType.FLOAT:
        return None  # float64 host path (x32)
    groups = {}
    for pm, p in pages:
        n = pm.n_values
        stride = (n + (-n) % 4) // 4
        groups.setdefault(stride, []).append((pm, p))
    outs = {}
    for stride, grp in groups.items():
        payload = _stack_pad_u32([p for _, p in grp])
        dec = decode_bss_group(payload, stride)
        for (pm, _), row in zip(grp, dec):
            outs[id(pm)] = row[:pm.n_values]
    return jnp.concatenate([outs[id(pm)] for pm, _ in pages])


_DEVICE_DECODERS = {
    Encoding.PLAIN: lambda c, f, d, p: _decode_plain_device(p, f),
    Encoding.RLE_DICTIONARY: _decode_dict_device,
    Encoding.DELTA_BINARY_PACKED:
        lambda c, f, d, p: _decode_delta_device(c, f, p),
    Encoding.RLE: lambda c, f, d, p: _decode_rle_device(c, f, p),
    Encoding.BYTE_STREAM_SPLIT:
        lambda c, f, d, p: _decode_bss_device(c, f, p),
}


# ---------------------------------------------------------------------------
# cascade decompression on device
# ---------------------------------------------------------------------------

def cascade_decompress_pages_grouped(raw_pages: list[tuple[PageMeta, bytes]]
                                     ) -> list[bytes]:
    """One device launch decompressing pages that share a (value_width,
    count_width) class — the caller grouped them (either the DecodePlan's
    plan-time (vw, cw) groups or cascade_decompress_device's execute-time
    grouping).  Returns the decompressed payload per page, input order."""
    mans = [cascade_manifest(p) for _, p in raw_pages]
    vw = mans[0]["value_width"]
    cw = mans[0]["count_width"]
    n_runs = max(max(m["n_runs"] for m in mans), 1)
    n_words = max(m["n_words"] for m in mans)
    n_out = -(-n_words // 1024) * 1024
    from repro.core import bitpack
    vwords = _stack_pad([m["value_words"] for m in mans],
                        bitpack.packed_words(n_runs, vw), np.uint32)
    cwords = _stack_pad([m["count_words"] for m in mans],
                        bitpack.packed_words(n_runs, cw), np.uint32)
    dec = cascade_decode_pages(jnp.asarray(vwords), jnp.asarray(cwords),
                               value_width=vw, count_width=cw,
                               n_runs=n_runs, n_out=n_out)
    return [np.asarray(row[:m["n_words"]]).tobytes()[:pm.uncompressed_size]
            for row, m, (pm, _) in zip(dec, mans, raw_pages)]


def cascade_decompress_device(raw_pages: list[tuple[PageMeta, bytes]]
                              ) -> list[tuple[PageMeta, bytes]]:
    """Decompress CASCADE page payloads on-device; returns bytes again so the
    per-encoding decoders above can run unchanged (in a fused deployment the
    words would stay resident in HBM).  Pages are grouped by their manifest
    (vw, cw) pair — one launch per class; the DecodePlan path skips this
    re-grouping by precomputing the classes at plan time."""
    mans = [cascade_manifest(p) for _, p in raw_pages]
    groups: dict = {}
    for i, m in enumerate(mans):
        groups.setdefault((m["value_width"], m["count_width"]), []).append(i)
    out: dict = {}
    for idxs in groups.values():
        datas = cascade_decompress_pages_grouped(
            [raw_pages[i] for i in idxs])
        for i, data in zip(idxs, datas):
            out[i] = data
    return [(pm, out[i]) for i, (pm, _) in enumerate(raw_pages)]


# ---------------------------------------------------------------------------
# public chunk decode
# ---------------------------------------------------------------------------

def decode_chunk(chunk: ChunkMeta, field: Field, raw: bytes,
                 use_kernels: bool = True,
                 payloads: dict | None = None) -> DecodeResult:
    """Decode one column chunk from its raw stored bytes.

    ``raw`` covers chunk.byte_range (dict page + data pages, possibly
    compressed).  Device-decodable encodings go through the Pallas kernels;
    everything else uses the host decoders.

    ``payloads``, if given, is pre-decompressed page data keyed by page
    index (plus ``"dict"``) — the DecodePlanner passes it so fallback
    columns share the chunk-level decompress memo instead of re-inflating
    per scan (core/compression.py).
    """
    off0, _ = chunk.byte_range
    codec = Codec(chunk.codec)
    encoding = Encoding(chunk.encoding)

    def stored(pm):
        data = raw[pm.offset - off0:pm.offset - off0 + pm.stored_size]
        verify_page(data, pm, where=f"{chunk.name} page@{pm.offset}")
        return data

    # --- decompression stage ------------------------------------------------
    if payloads is not None:
        pages = [(pm, payloads[pi]) for pi, pm in enumerate(chunk.pages)]
        dict_payload = payloads.get("dict")
    elif codec == Codec.CASCADE and use_kernels:
        pages = cascade_decompress_device(
            [(pm, stored(pm)) for pm in chunk.pages])
        dict_payload = None
        if chunk.dict_page is not None:
            dict_payload = decompress(stored(chunk.dict_page), codec,
                                      chunk.dict_page.uncompressed_size)
    else:
        pages = [(pm, decompress(stored(pm), codec, pm.uncompressed_size))
                 for pm in chunk.pages]
        dict_payload = None
        if chunk.dict_page is not None:
            dict_payload = decompress(stored(chunk.dict_page), codec,
                                      chunk.dict_page.uncompressed_size)

    # --- decode stage --------------------------------------------------------
    arr = None
    if use_kernels:
        dec = _DEVICE_DECODERS.get(encoding)
        if dec is not None:
            arr = dec(chunk, field, dict_payload, pages)
    on_device = arr is not None
    if use_kernels and arr is None:
        with _fallback_lock:
            _host_fallbacks[chunk.name] += 1
    if arr is None:  # host fallback
        dictionary = None
        if dict_payload is not None:
            dp = chunk.dict_page
            dictionary = decode_plain_page(dict_payload, dp.n_values, field,
                                           dp.extra)
        parts = [decode_page(encoding, payload, pm.n_values, field, pm.extra,
                             dictionary) for pm, payload in pages]
        from repro.core.table import StringColumn
        if isinstance(parts[0], StringColumn):
            if len(parts) == 1:
                arr = parts[0]
            else:
                lens = np.concatenate([p.lengths() for p in parts])
                offsets = np.zeros(lens.shape[0] + 1, dtype=np.int64)
                np.cumsum(lens, out=offsets[1:])
                arr = StringColumn(offsets,
                                   np.concatenate([p.payload for p in parts]))
        else:
            arr = np.concatenate(parts)

    n_values = chunk.n_values
    from repro.core.table import StringColumn as _SC
    logical = (arr.nbytes if isinstance(arr, _SC)
               else int(np.dtype(field.numpy_dtype or np.int64).itemsize
                        * n_values)
               if not on_device else int(arr.dtype.itemsize) * n_values)
    return DecodeResult(array=arr, on_device=on_device, n_values=n_values,
                        encoding=int(encoding), codec=int(codec),
                        stored_bytes=chunk.stored_bytes,
                        logical_bytes=int(logical))
