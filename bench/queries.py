"""TPC-H Q6 and Q12 as the benchmark's plain numpy references.

Each query is defined here once, independently of the program: the
columns it reads, the zone-map intervals of its predicates (which pages
a scan may skip without changing the answer), the float64 reference,
its control in the precision below the configuration's, and the number
that compares a served answer with the reference.  Dates are int32 days
since 1992-01-01; ``D1994``/``D1995`` are 1994-01-01 and 1995-01-01.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

D1994, D1995 = 731, 1096
MAIL, SHIP = 2, 4            # l_shipmode codes
F32 = np.float32
BF16 = ml_dtypes.bfloat16


def _below(x: float) -> float:
    """The largest float32 under ``x``: the inclusive end of ``< x``."""
    return float(np.nextafter(F32(x), F32(-np.inf)))


# table -> columns read; table -> {column: (lo, hi)} inclusive intervals
# that every row passing the query's predicate lies in
COLUMNS = {
    "q6": {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice"]},
    "q12": {"lineitem": ["l_orderkey", "l_shipmode", "l_shipdate",
                         "l_commitdate", "l_receiptdate"],
            "orders": ["o_orderkey", "o_orderpriority"]},
}
ZONES = {
    "q6": {"lineitem": {"l_shipdate": (D1994, D1995 - 1),
                        "l_discount": (float(F32(0.05)), float(F32(0.07))),
                        "l_quantity": (-np.inf, _below(24.0))}},
    "q12": {"lineitem": {"l_receiptdate": (D1994, D1995 - 1),
                         "l_shipmode": (MAIL, SHIP)}},
}
Q12_GROUPS = ("MAIL_high", "MAIL_low", "SHIP_high", "SHIP_low")


def columns_of(mix) -> dict[str, list[str]]:
    """table -> the columns that the queries of ``mix`` read, in order."""
    needed: dict[str, list[str]] = {}
    for q in sorted(mix):
        for t, cols in COLUMNS[q].items():
            have = needed.setdefault(t, [])
            have += [c for c in cols if c not in have]
    return needed


def q6(li: dict[str, np.ndarray], dtype=F32) -> float:
    """sum(l_extendedprice * l_discount) over FY1994 shipments with
    discount in [0.05, 0.07] and quantity < 24, summed in float64.
    ``dtype`` is the precision the decimal columns and their products
    are held in: float32 as stored, bfloat16 for the control."""
    ship = li["l_shipdate"]
    disc = li["l_discount"].astype(dtype)
    qty = li["l_quantity"].astype(dtype)
    price = li["l_extendedprice"].astype(dtype)
    m = ((ship >= D1994) & (ship < D1995)
         & (disc >= dtype(0.05)) & (disc <= dtype(0.07)) & (qty < 24))
    if dtype is F32:
        prod = price[m].astype(np.float64) * disc[m].astype(np.float64)
    else:
        prod = (price[m] * disc[m]).astype(np.float64)
    return float(np.sum(prod))


def q12(li: dict[str, np.ndarray], orders: dict[str, np.ndarray],
        date_dtype=np.int32) -> dict[str, int]:
    """Lines shipped by MAIL or SHIP, received in 1994 after their commit
    date and committed after shipping, counted per ship mode and split
    into urgent/high (priority codes 0, 1) and other orders.  The dates
    are compared as ``date_dtype``: int32 as stored, bfloat16 for the
    control."""
    ship, commit, receipt = (li[c].astype(date_dtype) for c in
                             ("l_shipdate", "l_commitdate", "l_receiptdate"))
    mode = li["l_shipmode"]
    m = (((mode == MAIL) | (mode == SHIP)) & (commit < receipt)
         & (ship < commit) & (receipt >= date_dtype(D1994))
         & (receipt < date_dtype(D1995)))
    order = np.argsort(orders["o_orderkey"], kind="stable")
    keys = orders["o_orderkey"][order]
    prio = orders["o_orderpriority"][order]
    lkey = li["l_orderkey"][m]
    pos = np.searchsorted(keys, lkey)
    if pos.size and (pos.max() >= keys.size or (keys[pos] != lkey).any()):
        raise ValueError("a lineitem row has no order")
    high = prio[pos] <= 1
    lmode = mode[m]
    return {f"{name}_{band}": int(np.count_nonzero((lmode == code) & sel))
            for name, code in (("MAIL", MAIL), ("SHIP", SHIP))
            for band, sel in (("high", high), ("low", ~high))}


def answer(query: str, result):
    """The program's result as host values: Q6 a float, Q12 a dict of
    counts."""
    if query == "q6":
        return float(result)
    return {k: int(v) for k, v in result.items()}


def reference(query: str, tables: dict[str, dict]):
    if query == "q6":
        return q6(tables["lineitem"])
    return q12(tables["lineitem"], tables["orders"])


def control(query: str, tables: dict[str, dict]):
    """The reference in the precision below the configuration's: decimal
    columns and their products in bfloat16 for Q6 (the configuration
    states float32), dates compared in bfloat16 for Q12 (it states exact
    int32 days and exact counts)."""
    if query == "q6":
        return q6(tables["lineitem"], dtype=BF16)
    return q12(tables["lineitem"], tables["orders"], date_dtype=BF16)


# name of the compared number, per query
CHECK = {"q6": "q6_rel_err", "q12": "q12_count_err"}


def error(query: str, got, want) -> float:
    """Q6: |got - want| / |want|.  Q12: the largest count difference
    (a missing or extra group counts as infinitely wrong)."""
    if query == "q6":
        return abs(float(got) - want) / max(abs(want), 1e-300)
    if set(got) != set(want):
        return float("inf")
    return float(max(abs(int(got[k]) - want[k]) for k in want))
