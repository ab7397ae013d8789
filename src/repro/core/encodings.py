"""Parquet V1/V2 encodings: host encoders/decoders + smallest-wins selection.

Insight 3 of the paper: most writers pin one V1 encoding per column; letting
every column *chunk* pick the smallest among all spec-valid candidates (V1
and V2) shrinks the bytes the storage path must move, which is what effective
bandwidth is made of.  The candidate set per physical type is < 5, so the
paper (and we) simply try them all.

Encodings implemented (ids match parquet.thrift where they exist):
  PLAIN(0)                 all types
  RLE(3)                   bool + integer runs
  DELTA_BINARY_PACKED(5)   int32/int64 (V2)
  DELTA_LENGTH_BYTE_ARRAY(6) strings (V2)
  RLE_DICTIONARY(8)        all types (chunk-level dictionary page)
  BYTE_STREAM_SPLIT(9)     float/double (V2)

All payloads are 4-byte aligned, varint-free (DESIGN.md §2): tiny headers are
parsed on host into *page manifests*; the bulk bit-packed payload is what the
Pallas kernels consume.
"""

from __future__ import annotations

import dataclasses
import enum
from collections.abc import Sequence

import numpy as np

from repro.core import bitpack
from repro.core.config import EncodingPolicy, FileConfig
from repro.core.schema import Field, PhysicalType
from repro.core.table import StringColumn

BLOCK = 1024           # values per DELTA block
MINIBLOCKS = 4         # miniblocks per block
MB_VALUES = BLOCK // MINIBLOCKS  # 256 values per miniblock
MB_GROUPS = MB_VALUES // bitpack.GROUP  # 8 packing groups per miniblock


class Encoding(enum.IntEnum):
    PLAIN = 0
    RLE = 3
    DELTA_BINARY_PACKED = 5
    DELTA_LENGTH_BYTE_ARRAY = 6
    RLE_DICTIONARY = 8
    BYTE_STREAM_SPLIT = 9


@dataclasses.dataclass
class EncodedPage:
    payload: bytes          # 4-byte aligned
    n_values: int
    extra: dict             # JSON-safe metadata required for decode

    @property
    def nbytes(self) -> int:
        return len(self.payload)


@dataclasses.dataclass
class ChunkEncoding:
    encoding: Encoding
    pages: list[EncodedPage]
    dict_page: EncodedPage | None = None

    @property
    def total_bytes(self) -> int:
        n = sum(p.nbytes for p in self.pages)
        if self.dict_page is not None:
            n += self.dict_page.nbytes
        return n


Values = np.ndarray | StringColumn


def _pad4(b: bytes) -> bytes:
    pad = (-len(b)) % 4
    return b + b"\x00" * pad


def _slice(values: Values, s: int, e: int) -> Values:
    if isinstance(values, StringColumn):
        return values.slice(s, e)
    return values[s:e]


def _n(values: Values) -> int:
    return len(values) if isinstance(values, StringColumn) else values.shape[0]


# ---------------------------------------------------------------------------
# PLAIN
# ---------------------------------------------------------------------------

def encode_plain_page(values: Values, field: Field) -> EncodedPage:
    if field.physical == PhysicalType.BYTE_ARRAY:
        assert isinstance(values, StringColumn)
        offsets = values.offsets.astype(np.int32)
        body = offsets.tobytes() + values.payload.tobytes()
        return EncodedPage(_pad4(body), len(values),
                           {"payload_len": int(values.payload.shape[0])})
    arr = np.ascontiguousarray(values)
    if field.physical == PhysicalType.BOOLEAN:
        arr = arr.astype(np.uint8)
    return EncodedPage(_pad4(arr.tobytes()), arr.shape[0], {})


def decode_plain_page(payload: bytes, n: int, field: Field,
                      extra: dict) -> Values:
    if field.physical == PhysicalType.BYTE_ARRAY:
        offsets = np.frombuffer(payload, dtype=np.int32, count=n + 1)
        plen = extra["payload_len"]
        start = (n + 1) * 4
        data = np.frombuffer(payload, dtype=np.uint8,
                             count=plen, offset=start).copy()
        return StringColumn(offsets.astype(np.int64), data)
    if field.physical == PhysicalType.BOOLEAN:
        return np.frombuffer(payload, dtype=np.uint8, count=n).astype(np.bool_)
    dt = field.numpy_dtype
    return np.frombuffer(payload, dtype=dt, count=n).copy()


# ---------------------------------------------------------------------------
# DELTA_BINARY_PACKED (V2) — block 1024, 4 miniblocks, bit-transposed packing
# ---------------------------------------------------------------------------

def _bit_widths_of(maxv: np.ndarray) -> np.ndarray:
    """Vectorized bit_length (≥1) for a small uint64 array."""
    out = np.ones(maxv.shape, dtype=np.int64)
    v = maxv.copy()
    for shift in (32, 16, 8, 4, 2, 1):
        big = v >= (np.uint64(1) << np.uint64(shift))
        out[big] += shift
        v = np.where(big, v >> np.uint64(shift), v)
    return out


def _delta_encode_ints(values: np.ndarray) -> tuple[bytes, dict]:
    """Vectorized across blocks: miniblocks grouped by bit-width so each
    distinct width packs in one numpy pass."""
    n = values.shape[0]
    first = int(values[0]) if n else 0
    work = values.astype(np.int64, copy=False)
    deltas = np.diff(work) if n > 1 else np.zeros(0, dtype=np.int64)
    n_deltas = deltas.shape[0]
    n_blocks = max(0, -(-n_deltas // BLOCK))
    if n_blocks == 0:
        return b"", {"first_value": first, "n_blocks": 0}
    padded = np.zeros(n_blocks * BLOCK, dtype=np.int64)
    padded[:n_deltas] = deltas
    blocks = padded.reshape(n_blocks, BLOCK)
    min_delta = blocks.min(axis=1)
    rel = (blocks - min_delta[:, None]).astype(np.uint64)
    mbs = rel.reshape(n_blocks * MINIBLOCKS, MB_VALUES)
    widths = _bit_widths_of(mbs.max(axis=1))            # (n_mb,)
    packed: dict = {}
    for w in np.unique(widths):
        sel = np.flatnonzero(widths == w)
        words = bitpack.pack(mbs[sel].reshape(-1), int(w))
        packed[int(w)] = dict(zip(
            sel.tolist(),
            words.reshape(sel.shape[0], MB_GROUPS * int(w))))
    out = bytearray()
    for b in range(n_blocks):
        out += np.int64(min_delta[b]).tobytes()         # 8 bytes
        ws = widths[b * MINIBLOCKS:(b + 1) * MINIBLOCKS]
        out += bytes(int(x) for x in ws)                # 4 bytes (u8 x 4)
        for m in range(MINIBLOCKS):
            i = b * MINIBLOCKS + m
            out += packed[int(widths[i])][i].tobytes()
    return bytes(_pad4(bytes(out))), {"first_value": first,
                                      "n_blocks": n_blocks}


def encode_delta_page(values: np.ndarray, field: Field) -> EncodedPage:
    if field.physical not in (PhysicalType.INT32, PhysicalType.INT64):
        raise TypeError("DELTA_BINARY_PACKED is for integers")
    payload, extra = _delta_encode_ints(np.ascontiguousarray(values))
    return EncodedPage(payload, values.shape[0], extra)


def build_delta_manifest(payload: bytes, n_values: int, extra: dict) -> dict:
    """Host header pass → flat manifest arrays for device decode.

    Returns dict with:
      mb_off   int32 (n_blocks*4,)  word offset of each miniblock's packed data
      mb_width int32 (n_blocks*4,)
      min_delta int64 (n_blocks,)
      first_value int
    """
    n_blocks = extra["n_blocks"]
    words = np.frombuffer(payload, dtype=np.uint32)
    mb_off = np.zeros(n_blocks * MINIBLOCKS, dtype=np.int32)
    mb_width = np.zeros(n_blocks * MINIBLOCKS, dtype=np.int32)
    min_delta = np.zeros(max(n_blocks, 1), dtype=np.int64)
    pos = 0  # in words
    for b in range(n_blocks):
        min_delta[b] = np.frombuffer(
            payload, dtype=np.int64, count=1, offset=pos * 4)[0]
        wbytes = np.frombuffer(
            payload, dtype=np.uint8, count=4, offset=pos * 4 + 8)
        pos += 3  # 8B min_delta + 4B widths
        for m in range(MINIBLOCKS):
            w = int(wbytes[m])
            mb_off[b * MINIBLOCKS + m] = pos
            mb_width[b * MINIBLOCKS + m] = w
            pos += MB_GROUPS * w
    return {"mb_off": mb_off, "mb_width": mb_width, "min_delta": min_delta,
            "first_value": int(extra["first_value"]), "words": words,
            "n_blocks": n_blocks, "n_values": n_values}


def decode_delta_page(payload: bytes, n: int, field: Field,
                      extra: dict) -> np.ndarray:
    man = build_delta_manifest(payload, n, extra)
    n_blocks = man["n_blocks"]
    words = man["words"]
    n_mb = n_blocks * MINIBLOCKS
    rel = np.zeros((max(n_mb, 1), MB_VALUES), dtype=np.uint64)
    widths = man["mb_width"]
    offs = man["mb_off"]
    for w in np.unique(widths[:n_mb]) if n_mb else []:
        w = int(w)
        sel = np.flatnonzero(widths[:n_mb] == w)
        idx = offs[sel][:, None] + np.arange(MB_GROUPS * w)[None, :]
        gathered = words[idx]                      # (k, 8w) fancy gather
        vals = bitpack.unpack(gathered.reshape(-1), w,
                              sel.shape[0] * MB_VALUES)
        rel[sel] = vals.reshape(sel.shape[0], MB_VALUES)
    deltas = rel.reshape(-1)[:n_blocks * BLOCK].astype(np.int64)
    deltas += np.repeat(man["min_delta"][:n_blocks], BLOCK)
    out = np.empty(n, dtype=np.int64)
    if n:
        out[0] = man["first_value"]
        if n > 1:
            np.cumsum(deltas[:n - 1], out=out[1:])
            out[1:] += man["first_value"]
    return out.astype(field.numpy_dtype)


# ---------------------------------------------------------------------------
# RLE — runs of identical values
# ---------------------------------------------------------------------------

def encode_rle_page(values: np.ndarray, field: Field) -> EncodedPage:
    arr = np.ascontiguousarray(values)
    if field.physical == PhysicalType.BOOLEAN:
        arr = arr.astype(np.int32)
    n = arr.shape[0]
    if n == 0:
        return EncodedPage(b"", 0, {"n_runs": 0})
    change = np.flatnonzero(arr[1:] != arr[:-1]) + 1
    starts = np.concatenate([[0], change])
    ends = np.concatenate([change, [n]])
    run_vals = arr[starts]
    run_counts = (ends - starts).astype(np.int32)
    vdt = np.int64 if field.physical == PhysicalType.INT64 else np.int32
    body = run_vals.astype(vdt).tobytes() + run_counts.tobytes()
    return EncodedPage(_pad4(body), n, {"n_runs": int(run_vals.shape[0])})


def decode_rle_page(payload: bytes, n: int, field: Field,
                    extra: dict) -> np.ndarray:
    r = extra["n_runs"]
    if r == 0:
        dt = (np.bool_ if field.physical == PhysicalType.BOOLEAN
              else field.numpy_dtype)
        return np.zeros(0, dtype=dt)
    vdt = np.int64 if field.physical == PhysicalType.INT64 else np.int32
    vals = np.frombuffer(payload, dtype=vdt, count=r)
    counts = np.frombuffer(payload, dtype=np.int32, count=r,
                           offset=r * np.dtype(vdt).itemsize)
    out = np.repeat(vals, counts)
    assert out.shape[0] == n, (out.shape, n)
    if field.physical == PhysicalType.BOOLEAN:
        return out.astype(np.bool_)
    return out.astype(field.numpy_dtype)


# ---------------------------------------------------------------------------
# BYTE_STREAM_SPLIT (V2) — float/double
# ---------------------------------------------------------------------------

def encode_bss_page(values: np.ndarray, field: Field) -> EncodedPage:
    arr = np.ascontiguousarray(values)
    k = arr.dtype.itemsize
    streams = arr.view(np.uint8).reshape(arr.shape[0], k)
    body = b"".join(_pad4(streams[:, s].tobytes()) for s in range(k))
    return EncodedPage(body, arr.shape[0], {"itemsize": k})


def decode_bss_page(payload: bytes, n: int, field: Field,
                    extra: dict) -> np.ndarray:
    k = extra["itemsize"]
    stride = n + ((-n) % 4)
    out = np.empty((n, k), dtype=np.uint8)
    for s in range(k):
        out[:, s] = np.frombuffer(payload, dtype=np.uint8, count=n,
                                  offset=s * stride)
    return out.reshape(-1).view(field.numpy_dtype)[:n].copy()


# ---------------------------------------------------------------------------
# DELTA_LENGTH_BYTE_ARRAY (V2) — strings
# ---------------------------------------------------------------------------

def encode_dlba_page(values: StringColumn, field: Field) -> EncodedPage:
    lengths = values.lengths().astype(np.int64)
    lp, lextra = _delta_encode_ints(lengths)
    body = lp + _pad4(values.payload.tobytes())
    return EncodedPage(body, len(values),
                       {"lengths_extra": lextra, "lengths_size": len(lp),
                        "payload_len": int(values.payload.shape[0])})


def decode_dlba_page(payload: bytes, n: int, field: Field,
                     extra: dict) -> StringColumn:
    lsize = extra["lengths_size"]
    lf = Field("_lengths", PhysicalType.INT64)
    lengths = decode_delta_page(payload[:lsize], n, lf,
                                extra["lengths_extra"])
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    data = np.frombuffer(payload, dtype=np.uint8, count=extra["payload_len"],
                         offset=lsize).copy()
    return StringColumn(offsets, data)


# ---------------------------------------------------------------------------
# RLE_DICTIONARY (chunk-level)
# ---------------------------------------------------------------------------

#: little-endian masks of the first r bytes of a word, r = 0..8
_BYTE_MASKS = np.array([(1 << (8 * r)) - 1 for r in range(9)], dtype="<u8")


def _string_keys(values: StringColumn) -> list[np.ndarray]:
    """Keys that tell every distinct string apart: its bytes zero-padded
    to whole 8-byte words, one uint64 key per word, each gathered from the
    payload as one unaligned load, and its length (which tells "a" from
    "a\\0")."""
    lengths = values.lengths()
    n_words = max(1, -(-int(lengths.max(initial=0)) // 8))
    pay = np.concatenate([values.payload, np.zeros(8 * n_words, np.uint8)])
    # every byte offset of the payload as the start of a little-endian word
    at = np.ndarray(buffer=pay, dtype="<u8", shape=(pay.shape[0] - 7,),
                    strides=(1,))
    starts = values.offsets[:-1]
    keys = []
    for w in range(n_words):
        left = np.clip(lengths - 8 * w, 0, 8)
        keys.append(at[starts + 8 * w] & _BYTE_MASKS[left])
    return keys + [lengths]


def _unique_strings(values: StringColumn) -> tuple[StringColumn, np.ndarray]:
    """The distinct strings in order of first occurrence, and each row's
    code into them, from one stable sort of the rows' keys."""
    n = len(values)
    if n == 0:
        return values, np.zeros(0, np.int64)
    keys = _string_keys(values)
    order = np.lexsort(keys)        # stable: a value's first row leads
    new = np.zeros(n, bool)
    new[0] = True
    for k in keys:
        ks = k[order]
        new[1:] |= ks[1:] != ks[:-1]
    firsts = order[new]
    rank = np.empty(firsts.shape[0], np.int64)
    rank[np.argsort(firsts)] = np.arange(firsts.shape[0])
    codes = np.empty(n, np.int64)
    codes[order] = rank[np.cumsum(new) - 1]
    return values.take(np.sort(firsts)), codes


def _unique_with_codes(values: Values) -> tuple[Values, np.ndarray]:
    if isinstance(values, StringColumn):
        return _unique_strings(values)
    uniq, codes = np.unique(np.ascontiguousarray(values),
                            return_inverse=True)
    return uniq, codes.astype(np.int64)


def encode_dict_chunk(values: Values, field: Field,
                      page_slices: Sequence[tuple[int, int]],
                      max_dict_fraction: float) -> ChunkEncoding | None:
    n = _n(values)
    uniq, codes = _unique_with_codes(values)
    n_dict = _n(uniq)
    if n == 0 or n_dict > max(1, int(max_dict_fraction * n)):
        return None
    dict_page = encode_plain_page(uniq, field)
    width = bitpack.bit_width(max(1, n_dict - 1))
    pages = []
    for (s, e) in page_slices:
        packed = bitpack.pack(codes[s:e].astype(np.uint64), width)
        pages.append(EncodedPage(packed.tobytes(), e - s,
                                 {"bitwidth": width, "n_dict": n_dict}))
    return ChunkEncoding(Encoding.RLE_DICTIONARY, pages, dict_page)


def decode_dict_page(payload: bytes, n: int, field: Field, extra: dict,
                     dictionary: Values) -> Values:
    width = extra["bitwidth"]
    words = np.frombuffer(payload, dtype=np.uint32)
    codes = bitpack.unpack(words, width, n, out_dtype=np.int64)
    if isinstance(dictionary, StringColumn):
        return dictionary.take(codes)
    return np.ascontiguousarray(dictionary)[codes]


# ---------------------------------------------------------------------------
# Candidate sets + chunk encode/decode entry points (Insight 3)
# ---------------------------------------------------------------------------

_INT_TYPES = (PhysicalType.INT32, PhysicalType.INT64)
_FLOAT_TYPES = (PhysicalType.FLOAT, PhysicalType.DOUBLE)


def candidate_encodings(field: Field, policy: EncodingPolicy,
                        allow_dict: bool = True) -> list[Encoding]:
    if policy == EncodingPolicy.PLAIN_ONLY:
        return [Encoding.PLAIN]
    if policy == EncodingPolicy.V1_ONLY:
        cands = [Encoding.PLAIN]
        if allow_dict:
            cands.append(Encoding.RLE_DICTIONARY)
        return cands
    # FLEX — every spec-valid candidate for the type (< 5 per the paper)
    if field.physical in _INT_TYPES:
        cands = [Encoding.PLAIN, Encoding.DELTA_BINARY_PACKED, Encoding.RLE]
        if allow_dict:
            cands.insert(1, Encoding.RLE_DICTIONARY)
        return cands
    if field.physical in _FLOAT_TYPES:
        cands = [Encoding.PLAIN, Encoding.BYTE_STREAM_SPLIT]
        if allow_dict:
            cands.insert(1, Encoding.RLE_DICTIONARY)
        return cands
    if field.physical == PhysicalType.BOOLEAN:
        return [Encoding.PLAIN, Encoding.RLE]
    if field.physical == PhysicalType.BYTE_ARRAY:
        cands = [Encoding.PLAIN, Encoding.DELTA_LENGTH_BYTE_ARRAY]
        if allow_dict:
            cands.insert(1, Encoding.RLE_DICTIONARY)
        return cands
    raise TypeError(field.physical)


_PAGE_ENCODERS = {
    Encoding.PLAIN: encode_plain_page,
    Encoding.DELTA_BINARY_PACKED: encode_delta_page,
    Encoding.RLE: encode_rle_page,
    Encoding.BYTE_STREAM_SPLIT: encode_bss_page,
    Encoding.DELTA_LENGTH_BYTE_ARRAY: encode_dlba_page,
}

_PAGE_DECODERS = {
    Encoding.PLAIN: decode_plain_page,
    Encoding.DELTA_BINARY_PACKED: decode_delta_page,
    Encoding.RLE: decode_rle_page,
    Encoding.BYTE_STREAM_SPLIT: decode_bss_page,
    Encoding.DELTA_LENGTH_BYTE_ARRAY: decode_dlba_page,
}


def encode_chunk_with(encoding: Encoding, values: Values, field: Field,
                      page_slices: Sequence[tuple[int, int]],
                      max_dict_fraction: float = 1.0
                      ) -> ChunkEncoding | None:
    """Encode one column chunk with a specific encoding (None if invalid)."""
    if encoding == Encoding.RLE_DICTIONARY:
        return encode_dict_chunk(values, field, page_slices,
                                 max_dict_fraction)
    enc = _PAGE_ENCODERS[encoding]
    try:
        pages = [enc(_slice(values, s, e), field) for (s, e) in page_slices]
    except TypeError:
        return None
    return ChunkEncoding(encoding, pages)


def select_chunk_encoding(values: Values, field: Field,
                          page_slices: Sequence[tuple[int, int]],
                          config: FileConfig) -> ChunkEncoding:
    """Insight 3: try every candidate, keep the smallest encoded size."""
    allow_dict = field.name not in set(config.no_dict_columns)
    cands = candidate_encodings(field, config.encodings, allow_dict)
    best: ChunkEncoding | None = None
    for c in cands:
        ce = encode_chunk_with(c, values, field, page_slices,
                               config.max_dict_fraction)
        if ce is None:
            continue
        if best is None or ce.total_bytes < best.total_bytes:
            best = ce
    assert best is not None, "PLAIN always succeeds"
    return best


def decode_page(encoding: Encoding, payload: bytes, n: int, field: Field,
                extra: dict, dictionary: Values | None = None) -> Values:
    if encoding == Encoding.RLE_DICTIONARY:
        assert dictionary is not None
        return decode_dict_page(payload, n, field, extra, dictionary)
    return _PAGE_DECODERS[encoding](payload, n, field, extra)
