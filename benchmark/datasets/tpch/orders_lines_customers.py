"""TPC-H ``orders``, ``lineitem`` and ``customer``: the columns the first
three templates read, from the specification's generator.

The plain reference may take nothing the program has made, so it does not
read the program's connector: this provider generates its columns by the
specification's dbgen algorithm (clause 4.2; ``benchmark/streams.py``). The
copy is tied to the specification, not to the program, by
``tests/test_reference.py``: the reference over these columns reproduces the
published SF1 answers of Q1, Q3 and Q6.

Only numbers are generated. Decimals are scaled integers (quantity and
money in hundredths, discount and tax in hundredths), dates are days since
1992-01-01, flags are small integers with their letters under ``LABELS``.
Orders are made a block at a time (a line's draws depend on its order's
alone), and only what the asked columns need is drawn.
"""

from __future__ import annotations

from functools import cached_property

import numpy as np

from benchmark.streams import Powers, blocks, bounded

CUSTOMER_BASE = 150_000
ORDER_BASE = 1_500_000
PART_BASE = 200_000
CUSTOMER_MORTALITY = 3
ORDER_DATE_RANGE = 2_557 - 151
CURRENT_DATE_OFFSET = 1_263  # 1995-06-17, in days since 1992-01-01
LINES_PER_ORDER_MAX = 7
BLOCK_ORDERS = 1 << 17

S_ORDER_DATE = 1066728069
S_LINE_COUNT = 1434868289
S_CUST_KEY = 851767375
S_QUANTITY = 209208115
S_DISCOUNT = 554590007
S_TAX = 721958466
S_LINE_PART_KEY = 1808217256
S_SHIP_DATE = 1769349045
S_RECEIPT_DATE = 373135028
S_RETURN_FLAG = 717419739
S_CUST_SEGMENT = 1140279430

#: table -> column -> the columns of other providers it is made from (none)
GIVES = {
    "lineitem": dict.fromkeys((
        "l_orderkey", "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate",
    ), {}),
    "orders": dict.fromkeys(("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"), {}),
    "customer": dict.fromkeys(("c_custkey", "c_mktsegment"), {}),
}

LABELS = {
    "lineitem": {"l_returnflag": ("R", "A", "N"), "l_linestatus": ("F", "O")},
    "customer": {
        "c_mktsegment": ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"),
    },
}


def _order_key(index: np.ndarray) -> np.ndarray:
    """Sparse order keys: 8 keys in every block of 32."""
    return ((index >> 3) << 5) | (index & 7)


def _part_price(part_key: np.ndarray) -> np.ndarray:
    return 90_000 + (part_key // 10) % 20_001 + 100 * (part_key % 1_000)


def _live_customer(ck: np.ndarray, max_key: int) -> np.ndarray:
    """A customer key divisible by 3 places no order: dbgen moves it up by
    one, then down by one."""
    ck = ck.copy()
    dead = ck % CUSTOMER_MORTALITY == 0
    ck[dead] = np.minimum(ck[dead] + 1, max_key)
    dead = ck % CUSTOMER_MORTALITY == 0
    ck[dead] -= 1
    return ck


class _Orders:
    """The 0-based orders ``first .. first + count - 1`` and their lines;
    each draw is made when a column first asks for it."""

    def __init__(self, powers: Powers, first: int, count: int, n_customers: int, n_parts: int):
        self.powers, self.first, self.count = powers, first, count
        self.n_customers, self.n_parts = n_customers, n_parts

    def per_order(self, seed0, lo, hi):
        return bounded(self.powers.draws(seed0, 1, self.first, self.count, 1)[:, 0], lo, hi)

    def line_seeds(self, seed0):
        lines = LINES_PER_ORDER_MAX
        return self.powers.draws(seed0, lines, self.first, self.count, lines)

    def per_line(self, seed0, lo, hi):
        return bounded(self.line_seeds(seed0), lo, hi)

    def lines_of(self, matrix, dtype):
        return matrix.reshape(-1)[self.flat].astype(dtype)

    @cached_property
    def live(self):
        line_counts = self.per_order(S_LINE_COUNT, 1, LINES_PER_ORDER_MAX)
        return np.arange(LINES_PER_ORDER_MAX)[None, :] < line_counts[:, None]

    @cached_property
    def flat(self):
        return np.nonzero(self.live.reshape(-1))[0]

    @cached_property
    def orderkey(self):
        return _order_key(np.arange(self.first + 1, self.first + self.count + 1, dtype=np.int64))

    @cached_property
    def orderdate(self):
        return self.per_order(S_ORDER_DATE, 0, ORDER_DATE_RANGE - 1)

    @cached_property
    def quantity(self):
        return self.per_line(S_QUANTITY, 1, 50)

    @cached_property
    def ship(self):
        return self.orderdate[:, None] + self.per_line(S_SHIP_DATE, 1, 121)

    def o_orderkey(self):
        return self.orderkey

    def o_custkey(self):
        return _live_customer(self.per_order(S_CUST_KEY, 1, self.n_customers), self.n_customers)

    def o_orderdate(self):
        return self.orderdate.astype(np.int32)

    def o_shippriority(self):
        return np.zeros(self.count, dtype=np.int32)

    def l_orderkey(self):
        return np.repeat(self.orderkey, LINES_PER_ORDER_MAX)[self.flat]

    def l_quantity(self):
        return self.lines_of(self.quantity * 100, np.int32)

    def l_extendedprice(self):
        part_key = self.per_line(S_LINE_PART_KEY, 1, self.n_parts)
        return self.lines_of(self.quantity * _part_price(part_key), np.int64)

    def l_discount(self):
        return self.lines_of(self.per_line(S_DISCOUNT, 0, 10), np.int32)

    def l_tax(self):
        return self.lines_of(self.per_line(S_TAX, 0, 8), np.int32)

    def l_returnflag(self):
        lines = LINES_PER_ORDER_MAX
        receipt = self.ship + self.per_line(S_RECEIPT_DATE, 1, 30)
        # the return flag is drawn only for lines received by the current date,
        # so a line's draw is the count of such lines before it in its order
        past = (receipt <= CURRENT_DATE_OFFSET) & self.live
        draw = np.clip(np.cumsum(past, axis=1) - 1, 0, lines - 1)
        flag_seeds = np.take_along_axis(self.line_seeds(S_RETURN_FLAG), draw, axis=1)
        return self.lines_of(np.where(past, bounded(flag_seeds, 0, 1), 2), np.int8)

    def l_linestatus(self):
        return self.lines_of(self.ship > CURRENT_DATE_OFFSET, np.int8)

    def l_shipdate(self):
        return self.lines_of(self.ship, np.int32)

    def column(self, name: str) -> np.ndarray:
        return getattr(self, name)()


def _customers(powers: Powers, first: int, count: int, name: str) -> np.ndarray:
    if name == "c_custkey":
        return np.arange(first + 1, first + count + 1, dtype=np.int64)
    return bounded(powers.draws(S_CUST_SEGMENT, 1, first, count, 1)[:, 0], 0, 4).astype(np.int8)


def generate(scale_factor: float, wanted: dict, have: dict, block_orders: int = BLOCK_ORDERS) -> dict:
    """The ``wanted`` columns (``{table: [column, ...]}``) at ``scale_factor``."""
    n_orders = max(1, round(ORDER_BASE * scale_factor))
    n_customers = max(1, round(CUSTOMER_BASE * scale_factor))
    n_parts = max(1, round(PART_BASE * scale_factor))
    powers = Powers((block_orders + 1) * LINES_PER_ORDER_MAX)
    parts: dict = {t: {c: [] for c in cols} for t, cols in wanted.items()}
    by_order = [(t, c) for t in ("orders", "lineitem") for c in wanted.get(t, ())]
    if by_order:
        for first, count in blocks(n_orders, block_orders):
            block = _Orders(powers, first, count, n_customers, n_parts)
            for t, c in by_order:
                parts[t][c].append(block.column(c))
    for c in wanted.get("customer", ()):
        for first, count in blocks(n_customers, block_orders * LINES_PER_ORDER_MAX):
            parts["customer"][c].append(_customers(powers, first, count, c))
    return {t: {c: np.concatenate(made) for c, made in cols.items()} for t, cols in parts.items()}
