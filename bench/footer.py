"""Byte counts taken from a TabFile's footer, independent of the program.

The footer is JSON followed by its CRC32 (4 bytes), its length (8 bytes,
little-endian) and the 8-byte magic.  ``logical_bytes`` is what the
paper's effective bandwidth counts: rows times physical width of the
columns a query reads, and for a BYTE_ARRAY column its values' lengths
plus Parquet PLAIN's 4-byte length prefix each, which the footer does not
hold: the caller counts them from the values it wrote
(``forms.byte_array_bytes``).  ``required_stored_bytes`` is a lower
bound on what any implementation has to bring to the device for a query: the
stored bytes of every page that the footer's page zone maps cannot
exclude under the query's predicate, with each needed dictionary page.
"""

from __future__ import annotations

import json
import struct

MAGIC = b"TABF0001"
# Parquet physical type ids (BOOLEAN, INT32, INT64, FLOAT, DOUBLE); a
# BYTE_ARRAY (6) has no width
WIDTH = {0: 1, 1: 4, 2: 8, 4: 4, 5: 8}
BYTE_ARRAY = 6


def read(path: str) -> dict:
    with open(path, "rb") as f:
        f.seek(-16, 2)
        tail = f.read(16)
        if tail[8:] != MAGIC:
            raise ValueError(f"{path}: not a TabFile")
        (n,) = struct.unpack("<Q", tail[:8])
        f.seek(-16 - n, 2)
        return json.loads(f.read(n)[:-4])


def logical_bytes(meta: dict, columns: list[str],
                  byte_arrays: dict[str, int] | None = None) -> int:
    """``byte_arrays`` gives each BYTE_ARRAY column's count; a string
    column it leaves out raises ``KeyError``."""
    phys = {f["name"]: f["physical"] for f in meta["schema"]}
    return sum((byte_arrays or {})[c] if phys[c] == BYTE_ARRAY
               else meta["num_rows"] * WIDTH[phys[c]] for c in columns)


def _excluded(pages: dict, zones: dict, i: int) -> bool:
    """Page slice ``i`` holds no row that can pass: some predicate
    column's page range misses its interval."""
    for col, (lo, hi) in zones.items():
        extra = pages[col][i]["extra"]
        if "vmin" in extra and (extra["vmax"] < lo or extra["vmin"] > hi):
            return True
    return False


def required_stored_bytes(meta: dict, columns: list[str],
                          zones: dict) -> int:
    total = 0
    for rg in meta["row_groups"]:
        chunks = {c["name"]: c for c in rg["columns"]}
        pages = {c: chunks[c]["pages"] for c in set(columns) | set(zones)}
        n_pages = {len(p) for p in pages.values()}
        # the writer slices every column of a row group alike; if it did
        # not, no page could be excluded safely
        keep = (range(n_pages.pop()) if len(n_pages) == 1 and zones
                else None)
        for c in columns:
            idx = (range(len(pages[c])) if keep is None else
                   [i for i in keep if not _excluded(pages, zones, i)])
            if not idx:
                continue
            total += sum(pages[c][i]["stored_size"] for i in idx)
            if chunks[c].get("dict_page"):
                total += chunks[c]["dict_page"]["stored_size"]
    return total
