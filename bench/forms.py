"""Each column's stored form, as a configuration's ``widths`` states it.

The frozen generator (``bench/tpch_gen.py``) yields DECIMAL columns as
float32 and the low-cardinality text columns as int32 codes.  A
configuration says how the file stores them:

- ``"program"``: as the generator yields them, the program's own forms
  (DECIMAL(15,2) as FLOAT, CHAR as INT32 codes).  The written bytes are
  those of the generator's arrays, unchanged.
- ``"tpch"``: TPC-H v3 section 1.3's types in Parquet's forms for them
  (Parquet format, LogicalTypes.md).  DECIMAL(15,2) as INT64 with logical
  DECIMAL and scale 2, the narrowest integer form Parquet allows for a
  precision of 15, each value ``rint(f32 * 100)`` cents; CHAR and VARCHAR
  as BYTE_ARRAY strings from TPC-H's value lists, picked by the
  generator's codes; dates as INT32 DATE.  The integer widths are assumed,
  not taken from any writer's schema: the Identifier columns (keys) as
  INT64, since o_orderkey ranges up to 6,000,000 x SF (TPC-H v3 4.2.3)
  and passes 2**31 above SF 357, and the other integers as INT32.

``convert`` gives a chunk's columns in the stored form, which is also the
form the reference reads: int64 cents, and each string column a numpy
bytes array (``S`` dtype; no TPC-H value holds a NUL byte, so it keeps
every value whole).  ``writable`` hands them to the program's writer.
"""

from __future__ import annotations

import numpy as np

from bench import tpch_gen

WIDTHS = ("program", "tpch")

# TPC-H v3 4.2.2.13 and 4.2.3's value lists, indexed by the generator's
# codes.  Ship modes and priorities are in the program generator's order
# (queries.MAIL and queries.SHIP are codes into it).
TEXT = {
    "l_returnflag": ["A", "N", "R"],
    "l_linestatus": ["F", "O"],
    "l_shipinstruct": ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                       "TAKE BACK RETURN"],
    "l_shipmode": ["REG AIR", "AIR", "MAIL", "RAIL", "SHIP", "TRUCK", "FOB"],
    "o_orderstatus": ["F", "O", "P"],
    "o_orderpriority": ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
                        "5-LOW"],
}
DECIMALS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
            "o_totalprice")
KEYS = ("l_orderkey", "l_partkey", "l_suppkey", "o_orderkey", "o_custkey")
_TEXT_BYTES = {c: np.array([v.encode() for v in vals], dtype="S")
               for c, vals in TEXT.items()}


def fields(table: str, widths: str) -> list:
    """The program's ``Field`` of each column of ``table``, in file order."""
    from repro.core.schema import Field, LogicalType, PhysicalType
    out = []
    for name, physical, logical in tpch_gen.TABLES[table]:
        scale = 0
        if widths == "tpch":
            if name in DECIMALS:
                physical, logical, scale = "INT64", "decimal", 2
            elif name in TEXT:
                physical, logical = "BYTE_ARRAY", "string"
            else:
                physical = "INT64" if name in KEYS else "INT32"
        out.append(Field(name, PhysicalType[physical], LogicalType(logical),
                         scale))
    return out


def convert(cols: dict[str, np.ndarray], widths: str) -> dict:
    """A chunk's columns in their stored form (see the module's doc)."""
    if widths == "program":
        return cols
    out = {}
    for name, v in cols.items():
        if name in DECIMALS:
            out[name] = np.rint(v.astype(np.float64) * 100).astype(np.int64)
        elif name in TEXT:
            out[name] = _TEXT_BYTES[name][v]
        elif name in KEYS:
            out[name] = v.astype(np.int64)
        else:
            out[name] = v
    return out


def _grid(v: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """A bytes array as a (rows, itemsize) grid of bytes, and the mask of
    each value's own bytes: numpy pads every value with NULs."""
    grid = v.view(np.uint8).reshape(len(v), v.itemsize)
    return grid, grid != 0


def writable(cols: dict) -> dict:
    """The stored form as the program's ``Table`` takes it: each bytes
    array as a ``StringColumn`` of its values."""
    from repro.core.table import StringColumn
    out = {}
    for name, v in cols.items():
        if v.dtype.kind != "S":
            out[name] = v
            continue
        grid, used = _grid(v)
        offsets = np.zeros(len(v) + 1, np.int64)
        np.cumsum(used.sum(1), out=offsets[1:])
        out[name] = StringColumn(offsets, grid[used])
    return out


def byte_array_bytes(cols: dict) -> dict[str, int]:
    """Logical bytes of each string column: each value's length plus the
    4-byte length prefix of Parquet's PLAIN encoding."""
    out = {}
    for name, v in cols.items():
        if v.dtype.kind == "S":
            _, used = _grid(v)
            out[name] = int(used.sum()) + 4 * len(v)
    return out
