"""TPC-H Q6 and Q12 as the benchmark's plain numpy references.

Each query is defined here once, independently of the program: the
columns it reads, the zone-map intervals of its predicates (which pages
a scan may skip without changing the answer), the reference, its control
in the precision below the configuration's, and the number that compares
a served answer with the reference.  Each reads the columns in the form
the configuration's ``widths`` wrote them (``bench/forms.py``): under
``"program"`` the decimals are float32 and Q6's reference is float64 over
them; under ``"tpch"`` they are int64 cents and Q6's reference is exact.
Q12 reads its text columns as int32 codes or as the strings themselves,
whichever the file holds.  Only the zones and the compared number's name
are keyed by ``widths``.
Dates are int32 days since 1992-01-01; ``D1994``/``D1995`` are 1994-01-01
and 1995-01-01.
"""

from __future__ import annotations

import math
from fractions import Fraction

import ml_dtypes
import numpy as np

D1994, D1995 = 731, 1096
MAIL, SHIP = 2, 4            # l_shipmode codes
HIGH = ((0, b"1-URGENT"), (1, b"2-HIGH"))   # o_orderpriority: code, text
F32 = np.float32
BF16 = ml_dtypes.bfloat16


def _below(x: float) -> float:
    """The largest float32 under ``x``: the inclusive end of ``< x``."""
    return float(np.nextafter(F32(x), F32(-np.inf)))


# table -> columns read; widths -> query -> table -> {column: (lo, hi)}
# inclusive intervals that every row passing the query's predicate lies in
# (a string page carries no range, so l_shipmode bounds nothing at "tpch")
COLUMNS = {
    "q6": {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice"]},
    "q12": {"lineitem": ["l_orderkey", "l_shipmode", "l_shipdate",
                         "l_commitdate", "l_receiptdate"],
            "orders": ["o_orderkey", "o_orderpriority"]},
}
ZONES = {
    "program": {
        "q6": {"lineitem": {"l_shipdate": (D1994, D1995 - 1),
                            "l_discount": (float(F32(0.05)),
                                           float(F32(0.07))),
                            "l_quantity": (-np.inf, _below(24.0))}},
        "q12": {"lineitem": {"l_receiptdate": (D1994, D1995 - 1),
                             "l_shipmode": (MAIL, SHIP)}},
    },
    "tpch": {
        "q6": {"lineitem": {"l_shipdate": (D1994, D1995 - 1),
                            "l_discount": (5, 7),
                            "l_quantity": (-np.inf, 2399)}},
        "q12": {"lineitem": {"l_receiptdate": (D1994, D1995 - 1)}},
    },
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
    discount in [0.05, 0.07] and quantity < 24, summed in float64, over
    float32 decimals.  ``dtype`` is the precision the decimal columns and
    their products are held in: float32 as stored, bfloat16 for the
    control."""
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


def _q6_rows(li: dict[str, np.ndarray]) -> np.ndarray:
    """Q6's predicate over DECIMAL(15,2) cents, exact."""
    ship, disc, qty = li["l_shipdate"], li["l_discount"], li["l_quantity"]
    return ((ship >= D1994) & (ship < D1995) & (disc >= 5) & (disc <= 7)
            & (qty < 2400))


def q6_units(li: dict[str, np.ndarray]) -> int:
    """Q6 over DECIMAL(15,2) cents, exact: the sum of price cents times
    discount cents, in units of 1e-4 (DECIMAL(.,4)).  A product is below
    2**27 and a sum of 2**36 of them fits int64."""
    m = _q6_rows(li)
    return int(np.sum(li["l_extendedprice"][m] * li["l_discount"][m]))


def q6_float(li: dict[str, np.ndarray], dtype=F32) -> float:
    """Q6 over DECIMAL(15,2) cents with the decimals, their products and
    the sum all held in ``dtype``: float32 is the control at TPC-H's
    widths."""
    m = _q6_rows(li)
    price = (li["l_extendedprice"][m] / 100).astype(dtype)
    disc = (li["l_discount"][m] / 100).astype(dtype)
    return float(np.sum(price * disc, dtype=dtype))


def _is(col: np.ndarray, code: int, text: bytes) -> np.ndarray:
    """Rows of a text column that hold one value: its code where the file
    holds codes, its bytes where it holds strings."""
    return col == (text if col.dtype.kind == "S" else code)


def q12(li: dict[str, np.ndarray], orders: dict[str, np.ndarray],
        date_dtype=np.int32) -> dict[str, int]:
    """Lines shipped by MAIL or SHIP, received in 1994 after their commit
    date and committed after shipping, counted per ship mode and split
    into 1-URGENT/2-HIGH and other orders.  The dates are compared as
    ``date_dtype``: int32 as stored, bfloat16 for the control."""
    ship, commit, receipt = (li[c].astype(date_dtype) for c in
                             ("l_shipdate", "l_commitdate", "l_receiptdate"))
    mode = li["l_shipmode"]
    modes = {"MAIL": _is(mode, MAIL, b"MAIL"),
             "SHIP": _is(mode, SHIP, b"SHIP")}
    m = ((modes["MAIL"] | modes["SHIP"]) & (commit < receipt)
         & (ship < commit) & (receipt >= date_dtype(D1994))
         & (receipt < date_dtype(D1995)))
    order = np.argsort(orders["o_orderkey"], kind="stable")
    keys = orders["o_orderkey"][order]
    prio = orders["o_orderpriority"][order]
    lkey = li["l_orderkey"][m]
    pos = np.searchsorted(keys, lkey)
    if pos.size and (pos.max() >= keys.size or (keys[pos] != lkey).any()):
        raise ValueError("a lineitem row has no order")
    lprio = prio[pos]
    high = np.zeros(lprio.shape, bool)
    for code, text in HIGH:
        high |= _is(lprio, code, text)
    return {f"{name}_{band}": int(np.count_nonzero(sel[m] & rows))
            for name, sel in modes.items()
            for band, rows in (("high", high), ("low", ~high))}


def answer(query: str, result):
    """The program's result as host values: Q6 a float, Q12 a dict of
    counts."""
    if query == "q6":
        return float(result)
    return {k: int(v) for k, v in result.items()}


def _cents(li: dict[str, np.ndarray]) -> bool:
    """Whether the file holds the decimals as DECIMAL(15,2) cents (int64,
    ``"tpch"``) rather than float32 (``"program"``)."""
    return li["l_extendedprice"].dtype.kind == "i"


def reference(query: str, tables: dict[str, dict]):
    """Q6 a float over float32 decimals, or exact units of 1e-4 (an int)
    over cents; Q12 a dict of counts."""
    if query == "q6":
        li = tables["lineitem"]
        return q6_units(li) if _cents(li) else q6(li)
    return q12(tables["lineitem"], tables["orders"])


def control(query: str, tables: dict[str, dict]):
    """The reference in the precision below the configuration's.  Q6 over
    float32 decimals: the decimal columns and their products in bfloat16
    (summed in float64); over cents, which state exact DECIMAL, float32
    throughout.  Q12 (which states exact int32 days and exact counts): the
    dates compared in bfloat16."""
    if query == "q6":
        li = tables["lineitem"]
        return q6_float(li) if _cents(li) else q6(li, dtype=BF16)
    return q12(tables["lineitem"], tables["orders"], date_dtype=BF16)


# widths -> query -> name of the compared number
CHECK = {"program": {"q6": "q6_rel_err", "q12": "q12_count_err"},
         "tpch": {"q6": "q6_units_err", "q12": "q12_count_err"}}


def error(query: str, got, want) -> float:
    """Q6 against a float reference: |got - want| / |want|.  Q6 against
    exact units (an int): |got - want| in units of 1e-4, exact (a float
    answer taken at its exact binary value); an answer that is no finite
    number is infinitely wrong.  Q12: the largest count difference (a missing or extra group
    counts as infinitely wrong)."""
    if query == "q6":
        if isinstance(want, int):
            got = float(got)
            if not math.isfinite(got):
                return float("inf")
            return float(abs(Fraction(got) * 10**4 - want))
        return abs(float(got) - want) / max(abs(want), 1e-300)
    if set(got) != set(want):
        return float("inf")
    return float(max(abs(int(got[k]) - want[k]) for k in want))
