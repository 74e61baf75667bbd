"""TPC-H Q5 (2.4.5): the revenue of the lines whose customer and supplier
are of the same nation, for the nations of one REGION, over the orders of
the year from DATE; one row a nation that has such lines, by revenue
descending.

Exact: a line's revenue is its extended price times (100 - discount), at
scale 4, summed by nation in Python integers (``Arithmetic.grouped``). Rows
that tie on revenue may come in any order (``compare.py``); there is no
LIMIT, so ``tie_rows`` is empty."""

import numpy as np

from benchmark.reference import Arithmetic, add_years, days, dec


def _by_key(keys, values):
    """``values`` looked up by a dense positive key: ``out[key] = value``."""
    out = np.full(int(keys.max()) + 1, -1, dtype=np.int64)
    out[keys] = values
    return out


def answer(tables, params, precision="exact", kept=None):
    li, orders = tables["lineitem"], tables["orders"]
    cust, supp = tables["customer"], tables["supplier"]
    nation, region = tables["nation"], tables["region"]
    ar = Arithmetic(precision)
    code = tables.labels["region"]["r_name"].index(params["REGION"])
    regions = region["r_regionkey"][region["r_name"] == code]
    in_region = _by_key(nation["n_nationkey"], np.isin(nation["n_regionkey"], regions)) == 1
    first, last = days(params["DATE"]), days(add_years(params["DATE"], 1))
    order_ok = (orders["o_orderdate"] >= first) & (orders["o_orderdate"] < last)
    # orders come sorted by key, and every line's order exists
    pos = np.searchsorted(orders["o_orderkey"], li["l_orderkey"])
    line = np.nonzero(order_ok[pos])[0]
    customer_nation = _by_key(cust["c_custkey"], cust["c_nationkey"])
    supplier_nation = _by_key(supp["s_suppkey"], supp["s_nationkey"])
    cn = customer_nation[orders["o_custkey"][pos[line]]]
    sn = supplier_nation[li["l_suppkey"][line]]
    local = (cn == sn) & in_region[sn]
    line, group = line[local], sn[local]
    value = ar.values(li["l_extendedprice"][line]) * (100 - ar.values(li["l_discount"][line]))
    n = len(in_region)
    revenue = ar.grouped(value, group, n)
    lines = np.bincount(group, minlength=n)
    names = tables.labels["nation"]["n_name"]
    name_of = _by_key(nation["n_nationkey"], nation["n_name"])
    found = sorted((-revenue[k], names[name_of[k]]) for k in range(n) if lines[k])
    return {"rows": [(name, dec(-neg, 4)) for neg, name in found], "tie_rows": []}
