#!/usr/bin/env python3
"""Serve TPC-H Q6 and Q12 at TPC-H's own Parquet widths and report what
the program answers.

    python bench/widths.py --config tpch_sf1_gpu_aware --seeds 1 2 3

The cells serve TPC-H rows at the program's own widths: DECIMAL(15,2)
columns as FLOAT and CHAR columns as INT32 codes.  This script writes the
same rows (the capped stream a run writes, ``bench/tpch_gen.py``) with
the columns each query reads as a Parquet writer stores TPC-H:
DECIMAL(15,2) as INT64 scaled by 100 (logical DECIMAL, scale 2), CHAR(10)
``l_shipmode`` and CHAR(15) ``o_orderpriority`` as BYTE_ARRAY strings,
dates as INT32 days, keys as INT64.  It serves Q6 and Q12 over them once
each through ``QueryFrontEnd`` on pallas scanners and prints, per seed and
query, one JSON line: the program's answer or its error, the host-fallback
decodes, the exact reference over the decimals and strings, and, as a
witness, the float64 reference over the float32 form of the same rows.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import queries  # noqa: E402
from bench.control import tables  # noqa: E402

# TPC-H's ship modes and order priorities, indexed by the generator's codes
SHIPMODES = ["REG AIR", "AIR", "MAIL", "RAIL", "SHIP", "TRUCK", "FOB"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
DECIMALS = ("l_quantity", "l_extendedprice", "l_discount")
STRINGS = {"l_shipmode": SHIPMODES, "o_orderpriority": PRIORITIES}
assert SHIPMODES[queries.MAIL] == "MAIL" and SHIPMODES[queries.SHIP] == "SHIP"


def cents(v: np.ndarray) -> np.ndarray:
    return np.rint(v.astype(np.float64) * 100).astype(np.int64)


def strings(codes: np.ndarray, names: list[str]):
    from repro.core.table import StringColumn
    raw = [n.encode() for n in names]
    width = max(map(len, raw))
    grid = np.zeros((len(raw), width), np.uint8)
    used = np.zeros((len(raw), width), bool)
    for i, b in enumerate(raw):
        grid[i, :len(b)] = np.frombuffer(b, np.uint8)
        used[i, :len(b)] = True
    lengths = used.sum(1)[codes]
    offsets = np.zeros(len(codes) + 1, np.int64)
    np.cumsum(lengths, out=offsets[1:])
    return StringColumn(offsets, grid[codes][used[codes]])


def tpch_form(name: str, col: np.ndarray):
    """A column as TPC-H's Parquet form stores it: (field, data)."""
    from repro.core.schema import Field, LogicalType, PhysicalType
    if name in DECIMALS:
        return (Field(name, PhysicalType.INT64, LogicalType.DECIMAL, 2),
                cents(col))
    if name in STRINGS:
        return (Field(name, PhysicalType.BYTE_ARRAY, LogicalType.STRING),
                strings(col, STRINGS[name]))
    if name.endswith("date"):
        return Field(name, PhysicalType.INT32, LogicalType.DATE), col
    if name.endswith("orderkey"):
        return Field(name, PhysicalType.INT64), col.astype(np.int64)
    raise KeyError(name)


def write(path: str, fc, cols: dict[str, np.ndarray]) -> None:
    from repro.core.schema import Schema
    from repro.core.table import Table
    from repro.core.writer import TabFileWriter
    forms = {n: tpch_form(n, c) for n, c in cols.items()}
    table = Table({n: d for n, (_, d) in forms.items()},
                  Schema([f for f, _ in forms.values()]))
    w = TabFileWriter(path, fc, min(16, os.cpu_count() or 1)).begin(
        table.schema)
    for start in range(0, table.num_rows, fc.rows_per_rg):
        w.write_row_group(table.slice(
            start, min(start + fc.rows_per_rg, table.num_rows)))
    w.finish()


def q6_exact(li: dict[str, np.ndarray]) -> float:
    """Q6 over the decimals in integer cents: exact."""
    ship, disc, qty = li["l_shipdate"], cents(li["l_discount"]), \
        cents(li["l_quantity"])
    m = ((ship >= queries.D1994) & (ship < queries.D1995)
         & (disc >= 5) & (disc <= 7) & (qty < 2400))
    return int(np.sum(cents(li["l_extendedprice"])[m] * disc[m])) / 1e4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import run
    run.import_program()
    from repro.core.scan import open_scanner
    from repro.kernels.ops import host_fallback_counts
    from repro.serve.engine import QueryFrontEnd

    config = run._json(os.path.join(run.BENCH, "configs",
                                    f"{args.config}.json"))
    fc = run.file_config(config["file_layout"])
    needed = queries.columns_of({"q6", "q12"})
    workdir = os.path.join(run.CACHE, "widths")
    for seed in args.seeds:
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        t = tables(config, seed, needed)
        paths = {n: os.path.join(workdir, f"{n}.tab") for n in t}
        for n, cols in t.items():
            write(paths[n], fc, cols)
        want = {"q6": q6_exact(t["lineitem"]),
                "q12": queries.reference("q12", t)}
        witness = {"q6": queries.reference("q6", t),
                   "q12": queries.reference("q12", t)}
        fe = QueryFrontEnd()
        fe.register_tenant("t0")
        try:
            for q in ("q6", "q12"):
                src = tuple(open_scanner(paths[n], columns=cols,
                                         decode_backend="pallas")
                            for n, cols in queries.COLUMNS[q].items())
                before = host_fallback_counts()
                line = {"config": args.config, "seed": seed, "query": q,
                        "reference": want[q],
                        "witness_f32_form": witness[q]}
                try:
                    got, _ = fe.result(fe.submit(
                        "t0", q, src[0] if len(src) == 1 else src), 600)
                    line["program"] = queries.answer(q, got)
                    line["error"] = queries.error(q, line["program"],
                                                  want[q])
                except Exception as e:  # noqa: BLE001 — reported
                    line["program"] = None
                    line["error"] = repr(e)[:300]
                line["host_fallback"] = dict(host_fallback_counts() - before)
                line["witness_error"] = queries.error(q, witness[q], want[q])
                print(json.dumps(line), flush=True)
        finally:
            fe.shutdown()
            shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
