"""Seeded synthetic TPC-H generator (lineitem + orders).

Faithful to dbgen's column types and value distributions at the level the
paper's experiments depend on: sorted orderkeys (delta-friendly), low-
cardinality dictionary columns (quantity, discount, flags, modes), dates in
1992–1998, and free-text comments.  Scale factor 1 ≈ 6M lineitem rows;
generation is chunked so arbitrarily large SFs stream to disk at bounded
memory through the streaming writer.

Dates are int32 days since 1992-01-01.
"""

from __future__ import annotations

import os
from collections.abc import Iterator

import numpy as np

from repro.core.config import FileConfig
from repro.core.metadata import FileMeta
from repro.core.schema import Field, LogicalType, PhysicalType, Schema
from repro.core.table import StringColumn, Table
from repro.core.writer import TabFileWriter

LINEITEM_ROWS_PER_SF = 6_000_000
ORDERS_ROWS_PER_SF = 1_500_000

SHIPMODES = ["REG AIR", "AIR", "MAIL", "RAIL", "SHIP", "TRUCK", "FOB"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
_WORDS = ("the quick final pending special express ironic regular bold "
          "furious careful silent even blithe dogged").split()


def _comments(rng: np.random.Generator, n: int) -> StringColumn:
    w = rng.integers(0, len(_WORDS), size=(n, 3))
    vals = [f"{_WORDS[a]} {_WORDS[b]} {_WORDS[c]}" for a, b, c in w]
    return StringColumn.from_pylist(vals)


def lineitem_schema(include_strings: bool = True) -> Schema:
    fields = [
        Field("l_orderkey", PhysicalType.INT64),
        Field("l_partkey", PhysicalType.INT32),
        Field("l_suppkey", PhysicalType.INT32),
        Field("l_linenumber", PhysicalType.INT32),
        Field("l_quantity", PhysicalType.FLOAT),
        Field("l_extendedprice", PhysicalType.FLOAT),
        Field("l_discount", PhysicalType.FLOAT),
        Field("l_tax", PhysicalType.FLOAT),
        Field("l_returnflag", PhysicalType.INT32),
        Field("l_linestatus", PhysicalType.INT32),
        Field("l_shipdate", PhysicalType.INT32, LogicalType.DATE),
        Field("l_commitdate", PhysicalType.INT32, LogicalType.DATE),
        Field("l_receiptdate", PhysicalType.INT32, LogicalType.DATE),
        Field("l_shipinstruct", PhysicalType.INT32),
        Field("l_shipmode", PhysicalType.INT32),
    ]
    if include_strings:
        fields.append(Field("l_comment", PhysicalType.BYTE_ARRAY,
                            LogicalType.STRING))
    return Schema(fields)


def orders_schema(include_strings: bool = True) -> Schema:
    fields = [
        Field("o_orderkey", PhysicalType.INT64),
        Field("o_custkey", PhysicalType.INT32),
        Field("o_orderstatus", PhysicalType.INT32),
        Field("o_totalprice", PhysicalType.FLOAT),
        Field("o_orderdate", PhysicalType.INT32, LogicalType.DATE),
        Field("o_orderpriority", PhysicalType.INT32),
        Field("o_shippriority", PhysicalType.INT32),
    ]
    if include_strings:
        fields.append(Field("o_comment", PhysicalType.BYTE_ARRAY,
                            LogicalType.STRING))
    return Schema(fields)


def _gen_orders_chunk(rng: np.random.Generator, key_start: int, n: int,
                      include_strings: bool) -> Table:
    cols: dict[str, object] = {
        "o_orderkey": np.arange(key_start, key_start + n, dtype=np.int64),
        "o_custkey": rng.integers(1, 150_000, n).astype(np.int32),
        "o_orderstatus": rng.integers(0, 3, n).astype(np.int32),
        "o_totalprice": (rng.random(n).astype(np.float32) * 400_000
                         + 1_000).round(2).astype(np.float32),
        "o_orderdate": rng.integers(0, 2405, n).astype(np.int32),
        "o_orderpriority": rng.integers(0, 5, n).astype(np.int32),
        "o_shippriority": np.zeros(n, dtype=np.int32),
    }
    if include_strings:
        cols["o_comment"] = _comments(rng, n)
    return Table(cols, orders_schema(include_strings))


def _gen_lineitem_chunk(rng: np.random.Generator, orders: Table,
                        include_strings: bool) -> Table:
    n_orders = orders.num_rows
    lines = rng.integers(1, 8, n_orders)
    n = int(lines.sum())
    okey = np.repeat(np.asarray(orders["o_orderkey"]), lines)
    odate = np.repeat(np.asarray(orders["o_orderdate"]), lines)
    linenumber = np.concatenate(
        [np.arange(1, k + 1, dtype=np.int32) for k in lines]) \
        if n_orders else np.zeros(0, np.int32)
    qty = rng.integers(1, 51, n).astype(np.float32)
    ship = (odate + rng.integers(1, 122, n)).astype(np.int32)
    cols: dict[str, object] = {
        "l_orderkey": okey.astype(np.int64),
        "l_partkey": rng.integers(1, 200_000, n).astype(np.int32),
        "l_suppkey": rng.integers(1, 10_000, n).astype(np.int32),
        "l_linenumber": linenumber,
        "l_quantity": qty,
        "l_extendedprice": (qty * (rng.random(n).astype(np.float32)
                                   * 2_000 + 900)).round(2
                                                        ).astype(np.float32),
        "l_discount": (rng.integers(0, 11, n) / 100.0).astype(np.float32),
        "l_tax": (rng.integers(0, 9, n) / 100.0).astype(np.float32),
        "l_returnflag": rng.integers(0, 3, n).astype(np.int32),
        "l_linestatus": rng.integers(0, 2, n).astype(np.int32),
        "l_shipdate": ship,
        "l_commitdate": (odate + rng.integers(30, 91, n)).astype(np.int32),
        "l_receiptdate": (ship + rng.integers(1, 31, n)).astype(np.int32),
        "l_shipinstruct": rng.integers(0, 4, n).astype(np.int32),
        "l_shipmode": rng.integers(0, len(SHIPMODES), n).astype(np.int32),
    }
    if include_strings:
        cols["l_comment"] = _comments(rng, n)
    return Table(cols, lineitem_schema(include_strings))


def generate_tables(sf: float = 0.01, seed: int = 0,
                    include_strings: bool = True
                    ) -> tuple[Table, Table]:
    """In-memory generation (small SFs — tests and CI)."""
    rng = np.random.default_rng(seed)
    n_orders = max(1, int(ORDERS_ROWS_PER_SF * sf))
    orders = _gen_orders_chunk(rng, 1, n_orders, include_strings)
    lineitem = _gen_lineitem_chunk(rng, orders, include_strings)
    return lineitem, orders


def tpch_chunks(sf: float, seed: int = 0, include_strings: bool = True,
                chunk_orders: int = 250_000) -> Iterator[tuple[Table, Table]]:
    """The seeded ``(orders, lineitem)`` chunk stream ``write_tpch``
    writes, in order.  Each lineitem chunk holds exactly the lines of its
    orders chunk, so per-chunk query oracles add up to the whole table's."""
    rng = np.random.default_rng(seed)
    remaining = max(1, int(ORDERS_ROWS_PER_SF * sf))
    key = 1
    while remaining > 0:
        k = min(chunk_orders, remaining)
        oc = _gen_orders_chunk(rng, key, k, include_strings)
        yield oc, _gen_lineitem_chunk(rng, oc, include_strings)
        key += k
        remaining -= k


def write_tpch(out_dir: str, sf: float, config: FileConfig, seed: int = 0,
               include_strings: bool = True, threads: int = 4,
               chunk_orders: int = 250_000
               ) -> dict[str, FileMeta]:
    """Streamed generation to ``out_dir/{lineitem,orders}.tab``."""
    os.makedirs(out_dir, exist_ok=True)
    lpath = os.path.join(out_dir, "lineitem.tab")
    opath = os.path.join(out_dir, "orders.tab")
    lw = TabFileWriter(lpath, config, threads).begin(
        lineitem_schema(include_strings))
    ow = TabFileWriter(opath, config, threads).begin(
        orders_schema(include_strings))

    def rg_stream(writer, tables_iter):
        pending, rows = [], 0
        for t in tables_iter:
            pending.append(t)
            rows += t.num_rows
            while rows >= config.rows_per_rg:
                buf = pending[0] if len(pending) == 1 else \
                    Table.concat(pending)
                writer.write_row_group(buf.slice(0, config.rows_per_rg))
                rest = buf.slice(config.rows_per_rg, buf.num_rows)
                pending = [rest] if rest.num_rows else []
                rows = rest.num_rows
        if rows:
            writer.write_row_group(pending[0] if len(pending) == 1
                                   else Table.concat(pending))

    ochunks, lchunks = [], []
    for oc, lc in tpch_chunks(sf, seed, include_strings, chunk_orders):
        ochunks.append(oc)
        lchunks.append(lc)
    rg_stream(ow, iter(ochunks))
    rg_stream(lw, iter(lchunks))
    ometa = ow.finish()
    lmeta = lw.finish()
    return {"lineitem": lmeta, "orders": ometa,
            "lineitem_path": lpath, "orders_path": opath}
