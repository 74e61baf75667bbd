"""The plain reference: each query template, over the reference's own columns.

Straightforward NumPy and Python integers, one function per template, named
in the template's metadata (``templates/<t>.json``: ``"reference"``). It
imports nothing of the program and reads only ``refdata``'s columns and the
execution's substitution parameters. DECIMAL arithmetic is exact: values are
scaled integers, sums are int64 (the largest, SF1 Q1's ``sum_charge`` at
scale 6, is 5.6e16, under 2^63), and an average is the exact quotient rounded
half up to the column's scale, as SQL's DECIMAL division rounds.

``precision="float32"`` is the control (see ``compare.py``): the same
queries with money arithmetic and sums in float32, the step a later PR on a
chip without native int64 would be tempted by. It breaks the configuration's
guarantee of exact DECIMAL arithmetic and has to come out as not correct.
"""

from __future__ import annotations

import datetime
import json
from decimal import Decimal

import numpy as np

from benchmark import refdata

EPOCH = datetime.date(1992, 1, 1)


def days(iso_date: str) -> int:
    return (datetime.date.fromisoformat(iso_date) - EPOCH).days


def iso(day_offset: int) -> str:
    return (EPOCH + datetime.timedelta(days=int(day_offset))).isoformat()


def add_years(iso_date: str, years: int) -> str:
    d = datetime.date.fromisoformat(iso_date)
    return d.replace(year=d.year + years).isoformat()


def dec(scaled: int, scale: int) -> Decimal:
    return Decimal(int(scaled)).scaleb(-scale)


def scaled(text: str, scale: int) -> int:
    return int(Decimal(text).scaleb(scale).to_integral_exact())


def div_half_up(num: int, den: int) -> int:
    """num / den rounded half away from zero, for num >= 0 < den."""
    return (2 * num + den) // (2 * den)


class Arithmetic:
    """Exact int64 sums, or the control's float32 ones."""

    def __init__(self, precision: str):
        if precision not in ("exact", "float32"):
            raise ValueError(f"unknown precision {precision!r}")
        self.exact = precision == "exact"

    def values(self, a: np.ndarray) -> np.ndarray:
        return a.astype(np.int64 if self.exact else np.float32)

    def total(self, a: np.ndarray) -> int:
        if self.exact:
            return int(a.sum(dtype=np.int64))
        return int(np.rint(np.float64(a.sum(dtype=np.float32))))

    def grouped(self, a: np.ndarray, group: np.ndarray, n: int) -> list[int]:
        """Sums of ``a`` by ``group`` in 0..n-1. The exact ones go through
        ``np.bincount``, whose float64 weights hold a whole number under 2^53:
        each value is split at 2^24, so over 2^24 rows or fewer the low parts
        sum to under 2^48 and the high parts (of values under 2^48) likewise."""
        if self.exact:
            if len(a) > 1 << 24 or (len(a) and (int(a.max()) >= 1 << 48 or int(a.min()) < 0)):
                raise ValueError("exact grouped sum: rows or values out of range")
            low = np.bincount(group, weights=a & 0xFFFFFF, minlength=n)
            high = np.bincount(group, weights=a >> 24, minlength=n)
            return [(int(h) << 24) + int(lo) for h, lo in zip(high, low)]
        out = np.zeros(n, dtype=np.float32)
        np.add.at(out, group, a)
        return [int(np.rint(np.float64(v))) for v in out]


def _q1_values(li, ar: "Arithmetic") -> dict:
    """What Q1 sums, for every line: no parameter changes these."""
    price = ar.values(li["l_extendedprice"])
    disc = ar.values(li["l_discount"])
    disc_price = price * (100 - disc)  # scale 4
    return {
        "qty": ar.values(li["l_quantity"]), "price": price, "disc": disc,
        "disc_price": disc_price,
        "charge": disc_price * (100 + ar.values(li["l_tax"])),  # scale 6
    }


def q1(tables, params, precision="exact", kept=None):
    """TPC-H Q1 (2.4.1): pricing summary of lines shipped by 1998-12-01
    less DELTA days, by return flag and line status, in that order."""
    li = tables["lineitem"]
    ar = Arithmetic(precision)
    n_groups = len(refdata.RETURNFLAG) * len(refdata.LINESTATUS)
    keep = li["l_shipdate"] <= days("1998-12-01") - int(params["DELTA"])
    # a line the filter drops goes to a group of its own, past the real ones
    group = np.where(keep, li["l_returnflag"].astype(np.int64) * 2 + li["l_linestatus"], n_groups)
    kept = {} if kept is None else kept
    if "q1" not in kept:
        kept["q1"] = _q1_values(li, ar)
    count = np.bincount(group, minlength=n_groups + 1)
    sums = {name: ar.grouped(v, group, n_groups + 1) for name, v in kept["q1"].items()}
    rows = []
    for g in range(n_groups):
        n = int(count[g])
        if n == 0:
            continue
        rows.append((
            refdata.RETURNFLAG[g // 2], refdata.LINESTATUS[g % 2],
            dec(sums["qty"][g], 2), dec(sums["price"][g], 2),
            dec(sums["disc_price"][g], 4), dec(sums["charge"][g], 6),
            dec(div_half_up(sums["qty"][g], n), 2),
            dec(div_half_up(sums["price"][g], n), 2),
            dec(div_half_up(sums["disc"][g], n), 2),
            n,
        ))
    rows.sort(key=lambda r: (r[0], r[1]))
    return {"rows": rows, "tie_rows": []}


def q6(tables, params, precision="exact", kept=None):
    """TPC-H Q6 (2.4.6): revenue change forecast over one year of lines with
    DISCOUNT +- 0.01 and quantity under QUANTITY."""
    li = tables["lineitem"]
    ar = Arithmetic(precision)
    lo = days(params["DATE"])
    hi = days(add_years(params["DATE"], 1))
    d = scaled(params["DISCOUNT"], 2)
    keep = (
        (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
        & (li["l_discount"] >= d - 1) & (li["l_discount"] <= d + 1)
        & (li["l_quantity"] < scaled(params["QUANTITY"], 2))
    )
    if not keep.any():
        return {"rows": [(None,)], "tie_rows": []}
    revenue = ar.total(
        ar.values(li["l_extendedprice"][keep]) * ar.values(li["l_discount"][keep])
    )
    return {"rows": [(dec(revenue, 4),)], "tie_rows": []}


def q3(tables, params, precision="exact", kept=None):
    """TPC-H Q3 (2.4.3): the 10 unshipped orders of highest revenue of one
    market segment, ordered by revenue descending, then order date.

    Rows that tie with the tenth on both sort keys are returned apart, under
    ``tie_rows``: SQL leaves the choice among them open."""
    li, orders, cust = tables["lineitem"], tables["orders"], tables["customer"]
    ar = Arithmetic(precision)
    date = days(params["DATE"])
    segment = refdata.SEGMENTS.index(params["SEGMENT"])
    in_segment = np.zeros(int(cust["c_custkey"].max()) + 1, dtype=bool)
    in_segment[cust["c_custkey"][cust["c_mktsegment"] == segment]] = True
    order_ok = (orders["o_orderdate"] < date) & in_segment[orders["o_custkey"]]
    # orders come sorted by key, and every line's order exists
    line = np.nonzero(li["l_shipdate"] > date)[0]
    pos = np.searchsorted(orders["o_orderkey"], li["l_orderkey"][line])
    joined = order_ok[pos]
    line, pos = line[joined], pos[joined]
    value = ar.values(li["l_extendedprice"][line]) * (100 - ar.values(li["l_discount"][line]))
    order_pos, group = np.unique(pos, return_inverse=True)
    revenue = ar.grouped(value, group, len(order_pos))
    found = sorted(
        (-revenue[i], int(orders["o_orderdate"][p]), int(orders["o_orderkey"][p]),
         int(orders["o_shippriority"][p]))
        for i, p in enumerate(order_pos)
    )

    def row(f):
        return (f[2], dec(-f[0], 4), iso(f[1]), f[3])

    limit = int(params.get("LIMIT", 10))
    top = found[:limit]
    ties = []
    if len(found) > limit:
        last = top[-1][:2]
        ties = [row(f) for f in found if f[:2] == last]
        if len(ties) == sum(1 for f in top if f[:2] == last):
            ties = []
    return {"rows": [row(f) for f in top], "tie_rows": ties}


FUNCTIONS = {"q1": q1, "q3": q3, "q6": q6}


class Reference:
    """The reference over one set of tables, at one precision. It keeps each
    answer it has given, and what a template computes alike for every set of
    parameters (``kept``), so a window's answers cost less than the window."""

    def __init__(self, tables, precision: str = "exact"):
        self.tables = tables
        self.precision = precision
        self.kept: dict = {}
        self.answers: dict[str, dict] = {}

    def answer(self, name: str, params: dict) -> dict:
        key = json.dumps([name, params], sort_keys=True)
        if key not in self.answers:
            self.answers[key] = FUNCTIONS[name](self.tables, params, self.precision, self.kept)
        return self.answers[key]
