"""Seeded TPC-H ``orders``/``lineitem`` generator, frozen for the benchmark.

A copy of the program's generator (``repro.data.tpch.tpch_chunks`` and its
chunk generators) with the string comment columns left out, kept here so
that a change to the program cannot change the benchmark's data.  It
draws the same random numbers in the same order, so for one seed it
yields the same values; ``bench/tests/test_bench_yardstick.py`` pins that.
The line numbers are built without a Python loop (the program's loop is
most of its generation time at SF 10).

Columns are plain numpy arrays: dates are int32 days since 1992-01-01,
DECIMAL columns are float32, and the low-cardinality text columns
(flags, modes, priorities) are int32 codes.
"""

from __future__ import annotations

from collections.abc import Iterator

import numpy as np

LINEITEM_ROWS_PER_SF = 6_000_000
ORDERS_ROWS_PER_SF = 1_500_000
CHUNK_ORDERS = 250_000
N_SHIPMODES = 7

# (name, physical type, logical type) in file order: TPC-H's columns
# without l_comment, o_comment and o_clerk
LINEITEM = [
    ("l_orderkey", "INT64", "none"), ("l_partkey", "INT32", "none"),
    ("l_suppkey", "INT32", "none"), ("l_linenumber", "INT32", "none"),
    ("l_quantity", "FLOAT", "none"), ("l_extendedprice", "FLOAT", "none"),
    ("l_discount", "FLOAT", "none"), ("l_tax", "FLOAT", "none"),
    ("l_returnflag", "INT32", "none"), ("l_linestatus", "INT32", "none"),
    ("l_shipdate", "INT32", "date"), ("l_commitdate", "INT32", "date"),
    ("l_receiptdate", "INT32", "date"), ("l_shipinstruct", "INT32", "none"),
    ("l_shipmode", "INT32", "none"),
]
ORDERS = [
    ("o_orderkey", "INT64", "none"), ("o_custkey", "INT32", "none"),
    ("o_orderstatus", "INT32", "none"), ("o_totalprice", "FLOAT", "none"),
    ("o_orderdate", "INT32", "date"), ("o_orderpriority", "INT32", "none"),
    ("o_shippriority", "INT32", "none"),
]
TABLES = {"lineitem": LINEITEM, "orders": ORDERS}


def orders_chunk(rng: np.random.Generator, key_start: int,
                 n: int) -> dict[str, np.ndarray]:
    return {
        "o_orderkey": np.arange(key_start, key_start + n, dtype=np.int64),
        "o_custkey": rng.integers(1, 150_000, n).astype(np.int32),
        "o_orderstatus": rng.integers(0, 3, n).astype(np.int32),
        "o_totalprice": (rng.random(n).astype(np.float32) * 400_000
                         + 1_000).round(2).astype(np.float32),
        "o_orderdate": rng.integers(0, 2405, n).astype(np.int32),
        "o_orderpriority": rng.integers(0, 5, n).astype(np.int32),
        "o_shippriority": np.zeros(n, dtype=np.int32),
    }


def lineitem_chunk(rng: np.random.Generator,
                   orders: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    n_orders = orders["o_orderkey"].shape[0]
    lines = rng.integers(1, 8, n_orders)
    n = int(lines.sum())
    okey = np.repeat(orders["o_orderkey"], lines)
    odate = np.repeat(orders["o_orderdate"], lines)
    first = np.repeat(np.cumsum(lines) - lines, lines)
    linenumber = (np.arange(n) - first + 1).astype(np.int32)
    qty = rng.integers(1, 51, n).astype(np.float32)
    ship = (odate + rng.integers(1, 122, n)).astype(np.int32)
    return {
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
        "l_shipmode": rng.integers(0, N_SHIPMODES, n).astype(np.int32),
    }


def tpch_chunks(sf: float, seed: int) -> Iterator[dict[str, dict]]:
    """The seeded chunk stream: ``{"orders": ..., "lineitem": ...}`` per
    chunk of up to ``CHUNK_ORDERS`` orders, each lineitem chunk holding
    exactly the lines of its orders."""
    rng = np.random.default_rng(seed)
    remaining = max(1, int(ORDERS_ROWS_PER_SF * sf))
    key = 1
    while remaining > 0:
        k = min(CHUNK_ORDERS, remaining)
        oc = orders_chunk(rng, key, k)
        yield {"orders": oc, "lineitem": lineitem_chunk(rng, oc)}
        key += k
        remaining -= k


def capped_chunks(sf: float, seed: int,
                  rows: dict[str, int]) -> Iterator[dict[str, dict]]:
    """The seeded chunk stream cut to the first ``rows[table]`` rows of
    each table named in ``rows``, so that every seed gives the same row
    counts.  Raises ``ValueError`` if the stream holds fewer."""
    left = dict(rows)
    for chunk in tpch_chunks(sf, seed):
        out = {}
        for t in left:
            cols = chunk[t]
            n = min(left[t], len(next(iter(cols.values()))))
            left[t] -= n
            out[t] = {c: v[:n] for c, v in cols.items()}
        yield out
    short = {t: n for t, n in left.items() if n}
    if short:
        raise ValueError(f"seed {seed} generated too few rows: {short} "
                         "short")
