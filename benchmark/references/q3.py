"""TPC-H Q3 (2.4.3): the 10 unshipped orders of highest revenue of one
market segment, ordered by revenue descending, then order date.

Rows that tie with the tenth on both sort keys are returned apart, under
``tie_rows``: SQL leaves the choice among them open."""

import numpy as np

from benchmark.reference import Arithmetic, days, dec, iso


def answer(tables, params, precision="exact", kept=None):
    li, orders, cust = tables["lineitem"], tables["orders"], tables["customer"]
    ar = Arithmetic(precision)
    date = days(params["DATE"])
    segment = tables.labels["customer"]["c_mktsegment"].index(params["SEGMENT"])
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
