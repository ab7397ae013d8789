#!/usr/bin/env python3
"""Serve TPC-H Q6 and Q12 at TPC-H's own Parquet widths and report what
the program answers.

    python bench/widths.py --config tpch_sf1_gpu_aware --seeds 1 2 3
    python bench/widths.py --config tpch_sf10_gpu_aware --seeds 1 --queries q6

It writes the rows that a run of the configuration writes
(``run.write_tables``) with the configuration's ``widths`` set to
``"tpch"`` (``bench/forms.py``: DECIMAL(15,2) as INT64 cents, CHAR and
VARCHAR as BYTE_ARRAY strings, keys as INT64), only the tables the
queries read.  Per seed it prints one JSON line for the data: the seconds
the writing took and the stored bytes of each column.  Then it serves each
query once through ``QueryFrontEnd`` on pallas scanners and prints one
JSON line per query: the program's answer or its error, the host-fallback
decodes, the exact reference over the written columns, the control
(``queries.control``), Q6 in float64 throughout and, as a witness, the reference over the
program's float32/int32-code forms of the same rows, each compared with
the exact reference as a run compares.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import footer, queries  # noqa: E402
from bench.control import tables  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--queries", nargs="+", choices=list(queries.COLUMNS),
                    default=list(queries.COLUMNS))
    args = ap.parse_args(argv)
    from bench import run
    run.import_program()
    from repro.core.scan import open_scanner
    from repro.kernels.ops import host_fallback_counts
    from repro.serve.engine import QueryFrontEnd

    config = dict(run._json(os.path.join(run.BENCH, "configs",
                                         f"{args.config}.json")),
                  widths="tpch")
    needed = queries.columns_of(args.queries)
    workdir = os.path.join(run.CACHE, "widths")
    for seed in args.seeds:
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        t0 = time.perf_counter()
        paths, metas, kept = run.write_tables(config, seed, workdir, needed)
        print(json.dumps({
            "config": args.config, "seed": seed, "widths": "tpch",
            "write_s": time.perf_counter() - t0,
            "rows": {t: m["num_rows"] for t, m in metas.items()},
            "stored_bytes": {t: {f["name"]: footer.required_stored_bytes(
                                     m, [f["name"]], {}) for f in m["schema"]}
                             for t, m in metas.items()},
        }), flush=True)
        witness = tables(dict(config, widths="program"), seed, needed)
        fe = QueryFrontEnd()
        fe.register_tenant("t0")
        try:
            for q in args.queries:
                src = tuple(open_scanner(paths[n], columns=cols,
                                         decode_backend="pallas")
                            for n, cols in queries.COLUMNS[q].items())
                want = queries.reference(q, kept)
                seen = queries.reference(q, witness)
                before = host_fallback_counts()
                line = {"config": args.config, "seed": seed, "query": q,
                        "check": queries.CHECK["tpch"][q],
                        "reference": want, "witness_f32_form": seen}
                try:
                    got, _ = fe.result(fe.submit(
                        "t0", q, src[0] if len(src) == 1 else src), 600)
                    line["program"] = queries.answer(q, got)
                    line["error"] = queries.error(q, line["program"], want)
                except Exception as e:  # noqa: BLE001 — reported
                    line["program"] = None
                    line["error"] = repr(e)[:300]
                line["host_fallback"] = dict(host_fallback_counts() - before)
                line["control_error"] = queries.error(
                    q, queries.control(q, kept), want)
                if q == "q6":
                    line["float64_error"] = queries.error(
                        q, queries.q6_float(kept["lineitem"], np.float64), want)
                line["witness_error"] = queries.error(q, seen, want)
                print(json.dumps(line), flush=True)
        finally:
            fe.shutdown()
            shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
