"""TPC-H ``lineitem.l_suppkey``, ``customer.c_nationkey``, ``supplier``'s key
and nation, and ``nation`` and ``region`` whole (clause 4.2.3).

A line's supplier is the specification's bridge from its part to one of the
part's four suppliers (4.2.3: ``PART_SUPP_BRIDGE``), drawn from the line's
part key and its supplier number; both are per-line draws of the orders
generator, made here a block of orders at a time with the helpers of
``orders_lines_customers.py``. A customer's and a supplier's nation are one
draw each in 0..24. The 25 nations and 5 regions are fixed: a name is a code
(its key), with the letters under ``LABELS``.
"""

from __future__ import annotations

import numpy as np

from benchmark.datasets.tpch.orders_lines_customers import (
    BLOCK_ORDERS,
    CUSTOMER_BASE,
    LINES_PER_ORDER_MAX,
    ORDER_BASE,
    PART_BASE,
    S_LINE_PART_KEY,
    _Orders,
)
from benchmark.streams import Powers, blocks, bounded

SUPPLIER_BASE = 10_000
SUPPLIERS_PER_PART = 4

S_SUPPLIER_NUMBER = 2095021727
S_CUST_NATION = 1489529863
S_SUPP_NATION = 110356601

#: (name, region key) of each nation, in key order
NATIONS = (
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
)
REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")

GIVES = {
    "lineitem": {"l_suppkey": {}},
    "customer": {"c_nationkey": {}},
    "supplier": dict.fromkeys(("s_suppkey", "s_nationkey"), {}),
    "nation": dict.fromkeys(("n_nationkey", "n_name", "n_regionkey"), {}),
    "region": dict.fromkeys(("r_regionkey", "r_name"), {}),
}

LABELS = {
    "nation": {"n_name": tuple(name for name, _ in NATIONS)},
    "region": {"r_name": REGIONS},
}


def part_supplier(part_key: np.ndarray, number: np.ndarray, suppliers: int) -> np.ndarray:
    """The supplier key of a part's ``number``-th supplier (0..3)."""
    return (part_key + number * (suppliers // 4 + (part_key - 1) // suppliers)) % suppliers + 1


def _line_suppliers(powers: Powers, n_orders: int, n_parts: int, n_suppliers: int,
                    block_orders: int) -> np.ndarray:
    made = []
    for first, count in blocks(n_orders, block_orders):
        block = _Orders(powers, first, count, 1, n_parts)
        part_key = block.per_line(S_LINE_PART_KEY, 1, n_parts)
        number = block.per_line(S_SUPPLIER_NUMBER, 0, SUPPLIERS_PER_PART - 1)
        made.append(block.lines_of(part_supplier(part_key, number, n_suppliers), np.int64))
    return np.concatenate(made)


def _nations_of(powers: Powers, seed0: int, rows: int) -> np.ndarray:
    return bounded(powers.draws(seed0, 1, 0, rows, 1)[:, 0], 0, len(NATIONS) - 1)


def generate(scale_factor: float, wanted: dict, have: dict, block_orders: int = BLOCK_ORDERS) -> dict:
    """The ``wanted`` columns (``{table: [column, ...]}``) at ``scale_factor``."""
    n_orders = max(1, round(ORDER_BASE * scale_factor))
    n_customers = max(1, round(CUSTOMER_BASE * scale_factor))
    n_parts = max(1, round(PART_BASE * scale_factor))
    n_suppliers = max(1, round(SUPPLIER_BASE * scale_factor))
    powers = Powers(max((block_orders + 1) * LINES_PER_ORDER_MAX, n_customers + 1))
    made: dict = {t: {} for t in wanted}
    if "l_suppkey" in wanted.get("lineitem", ()):
        made["lineitem"]["l_suppkey"] = _line_suppliers(
            powers, n_orders, n_parts, n_suppliers, block_orders)
    if "c_nationkey" in wanted.get("customer", ()):
        made["customer"]["c_nationkey"] = _nations_of(powers, S_CUST_NATION, n_customers)
    supplier = {
        "s_suppkey": lambda: np.arange(1, n_suppliers + 1, dtype=np.int64),
        "s_nationkey": lambda: _nations_of(powers, S_SUPP_NATION, n_suppliers),
    }
    keys = np.arange(len(NATIONS), dtype=np.int64)
    nation = {
        "n_nationkey": lambda: keys,
        "n_name": lambda: keys.copy(),
        "n_regionkey": lambda: np.array([r for _, r in NATIONS], dtype=np.int64),
    }
    region = {
        "r_regionkey": lambda: np.arange(len(REGIONS), dtype=np.int64),
        "r_name": lambda: np.arange(len(REGIONS), dtype=np.int64),
    }
    for table, columns in (("supplier", supplier), ("nation", nation), ("region", region)):
        for c in wanted.get(table, ()):
            made[table][c] = columns[c]()
    return made
