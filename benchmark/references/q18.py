"""TPC-H Q18 (2.4.18): the 100 orders of highest total price among those
whose lines add up to more than QUANTITY, each with its customer and that
sum, by total price descending, then order date.

Exact: quantities by order through ``np.bincount`` (SF1's 6.0M lines group
by 1.5M order keys; an order's quantities are whole numbers of hundredths
under 2^53 in all), HAVING, the order's customer. Rows that tie with the
hundredth on both sort keys are returned apart, under ``tie_rows``: SQL
leaves the choice among them open. Every sum is an order's seven lines at
the most, far under 2^24, so ``precision="float32"`` computes the same
answer: this query has no float32 control.
"""

import numpy as np

from benchmark.reference import dec, iso


def answer(tables, params, precision="exact", kept=None):
    li, orders, cust = tables["lineitem"], tables["orders"], tables["customer"]
    kept = {} if kept is None else kept
    if "q18" not in kept:
        # orders come sorted by key, and every line's order exists
        pos = np.searchsorted(orders["o_orderkey"], li["l_orderkey"])
        kept["q18"] = np.bincount(
            pos, weights=li["l_quantity"], minlength=len(orders["o_orderkey"])
        ).astype(np.int64)
    quantity = kept["q18"]
    large = np.nonzero(quantity > int(params["QUANTITY"]) * 100)[0]
    found = sorted(
        (-int(orders["o_totalprice"][p]), int(orders["o_orderdate"][p]),
         int(orders["o_orderkey"][p]), int(orders["o_custkey"][p]), int(quantity[p]))
        for p in large
    )
    # customers come sorted by key too
    name_at = np.searchsorted(cust["c_custkey"], [f[3] for f in found])

    def row(f, at):
        return (cust["c_name"][at].decode(), f[3], f[2], iso(f[1]), dec(-f[0], 2), dec(f[4], 2))

    rows = [row(f, at) for f, at in zip(found, name_at)]
    limit = int(params.get("LIMIT", 100))
    ties = []
    if len(found) > limit:
        last = found[limit - 1][:2]
        ties = [r for f, r in zip(found, rows) if f[:2] == last]
        if len(ties) == sum(1 for f in found[:limit] if f[:2] == last):
            ties = []
    return {"rows": rows[:limit], "tie_rows": ties}
