#!/usr/bin/env python3
"""Read the control of each cell's compared number at the cell's own size.

    python bench/control.py --workload <cell> --seeds 1 2 3

For each seed it generates the columns the cell's runs serve (the same
capped stream that a run writes, ``bench/tpch_gen.py``, in the forms the
configuration's ``widths`` states, ``bench/forms.py``) and prints, per
query of the cell's mix, the compared number of the control
(``queries.control``: the reference in the precision below the
configuration's) against the reference, beside the cell's limit.  A limit has to lie below every control reading.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import forms, queries, tpch_gen  # noqa: E402
from bench.run import load_cell  # noqa: E402


def tables(config: dict, seed: int, needed: dict[str, list[str]]) -> dict:
    """The columns a run of this configuration and seed serves: the same
    capped stream that ``run.write_tables`` writes, in the same forms."""
    kept = {t: {c: [] for c in cols} for t, cols in needed.items()}
    for chunk in tpch_gen.capped_chunks(
            config["scale_factor"], seed,
            {t: config["rows"][t] for t in needed}):
        for t, cols in needed.items():
            stored = forms.convert({c: chunk[t][c] for c in cols},
                                   config["widths"])
            for c in cols:
                kept[t][c].append(stored[c])
    return {t: {c: np.concatenate(v) for c, v in cols.items()}
            for t, cols in kept.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, trace=False)
    mix = sorted({c["query"] for c in cell.traffic["clients"]})
    needed = queries.columns_of(mix)
    for seed in args.seeds:
        t = tables(cell.config, seed, needed)
        for q in mix:
            want = queries.reference(q, t)
            name = queries.CHECK[cell.config["widths"]][q]
            print(json.dumps({
                "workload": args.workload, "seed": seed, "check": name,
                "control": queries.error(q, queries.control(q, t), want),
                "limit": cell.limits[name]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
