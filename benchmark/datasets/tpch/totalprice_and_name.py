"""TPC-H ``orders.o_totalprice`` and ``customer.c_name`` (clause 4.2.3).

``o_totalprice`` is the sum over the order's lines of
``l_extendedprice * (1 + l_tax) * (1 - l_discount)``, which dbgen works out
in whole cents, a line at a time: the extended price times (100 - discount),
divided by 100 and truncated, then times (100 + tax), divided by 100 and
truncated. ``c_name`` is the text ``Customer#`` and the key in nine digits
(bytes: ``numpy`` keeps them as plain arrays).
"""

from __future__ import annotations

import numpy as np

GIVES = {
    "orders": {"o_totalprice": {
        "orders": ["o_orderkey"],
        "lineitem": ["l_orderkey", "l_extendedprice", "l_discount", "l_tax"],
    }},
    "customer": {"c_name": {"customer": ["c_custkey"]}},
}


def generate(scale_factor: float, wanted: dict, have: dict) -> dict:
    made: dict = {t: {} for t in wanted}
    if "o_totalprice" in wanted.get("orders", ()):
        li, keys = have["lineitem"], have["orders"]["o_orderkey"]
        charge = li["l_extendedprice"] * (100 - li["l_discount"].astype(np.int64)) // 100
        charge = charge * (100 + li["l_tax"].astype(np.int64)) // 100
        # orders come sorted by key; a line's charge is under 2^24 and an
        # order has seven lines at the most, so float64 weights are exact
        order = np.searchsorted(keys, li["l_orderkey"])
        total = np.bincount(order, weights=charge, minlength=len(keys))
        made["orders"]["o_totalprice"] = total.astype(np.int64)
    if "c_name" in wanted.get("customer", ()):
        digits = np.char.zfill(have["customer"]["c_custkey"].astype("S"), 9)
        made["customer"]["c_name"] = np.char.add(b"Customer#", digits)
    return made
