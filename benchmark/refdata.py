"""The reference's own TPC-H columns, from the specification's generator.

The plain reference may take nothing the program has made, so it does not
read the program's connector: this module generates the columns that the
benchmark's query templates read, by the specification's dbgen algorithm
(clause 4.2: per-column Lehmer streams, seed' = seed * 16807 mod 2^31-1,
fixed starting seeds and a fixed number of draws per row). The stream
arithmetic and the seed constants are a copy of what
``trino_tpu/connectors/dbgen.py`` holds (``PERF.md`` lists the original
under Open questions); the copy is tied to the specification, not to the
program, by ``tests/test_reference.py``: the reference over these columns
reproduces the published SF1 answers of Q1, Q3 and Q6.

Only numbers are generated. Decimals are scaled integers (quantity and
money in hundredths, discount and tax in hundredths), dates are days since
1992-01-01, flags are small integers with their letters in ``RETURNFLAG``,
``LINESTATUS`` and ``SEGMENTS``.
"""

from __future__ import annotations

import os

import numpy as np

M = 2147483647  # 2^31 - 1
A = 16807

CUSTOMER_BASE = 150_000
ORDER_BASE = 1_500_000
PART_BASE = 200_000
CUSTOMER_MORTALITY = 3
ORDER_DATE_RANGE = 2_557 - 151
CURRENT_DATE_OFFSET = 1_263  # 1995-06-17, in days since 1992-01-01
LINES_PER_ORDER_MAX = 7

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

RETURNFLAG = ("R", "A", "N")
LINESTATUS = ("F", "O")
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")

#: columns each table of the reference holds; a template may read no other
COLUMNS = {
    "lineitem": (
        "l_orderkey", "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate",
    ),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"),
    "customer": ("c_custkey", "c_mktsegment"),
}


def _pow_table(n: int) -> np.ndarray:
    """P[k] = 16807^k mod M for k in [0, n], by doubling; both factors stay
    under 2^31, so no int64 product overflows."""
    p = np.empty(n + 1, dtype=np.int64)
    p[0] = 1
    if n:
        p[1] = A
    filled = 1
    while filled < n:
        step = min(filled, n - filled)
        p[filled + 1 : filled + step + 1] = (p[1 : step + 1] * p[filled]) % M
        filled += step
    return p


class _Powers:
    """One table of powers shared by every stream of a generation."""

    def __init__(self, n: int):
        self.table = _pow_table(n)

    def draws(self, seed0: int, per_row: int, n_rows: int, uses: int) -> np.ndarray:
        """Seeds of draws (row, j), shape (n_rows, uses): draw j of 0-based
        row r is the stream's (r * per_row + j + 1)-th value."""
        i = np.arange(n_rows, dtype=np.int64)[:, None]
        j = np.arange(uses, dtype=np.int64)[None, :]
        return ((seed0 % M) * self.table[i * per_row + j + 1]) % M


def _bounded(seeds: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """dbgen's UnifInt: lo + trunc(seed / M * range), in float64 as dbgen
    computes it."""
    return lo + ((seeds.astype(np.float64) / M) * (hi - lo + 1)).astype(np.int64)


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


def generate(scale_factor: float) -> dict[str, dict[str, np.ndarray]]:
    """Every column of ``COLUMNS`` at ``scale_factor``."""
    n_orders = max(1, round(ORDER_BASE * scale_factor))
    n_customers = max(1, round(CUSTOMER_BASE * scale_factor))
    n_parts = max(1, round(PART_BASE * scale_factor))
    lines = LINES_PER_ORDER_MAX
    powers = _Powers(max(n_orders * lines + lines, n_customers + 1))

    def per_order(seed0, lo, hi):
        return _bounded(powers.draws(seed0, 1, n_orders, 1)[:, 0], lo, hi)

    def per_line(seed0, lo, hi):
        return _bounded(powers.draws(seed0, lines, n_orders, lines), lo, hi)

    index = np.arange(1, n_orders + 1, dtype=np.int64)
    o_orderkey = _order_key(index)
    line_counts = per_order(S_LINE_COUNT, 1, lines)
    live = np.arange(lines)[None, :] < line_counts[:, None]
    o_custkey = _live_customer(per_order(S_CUST_KEY, 1, n_customers), n_customers)
    o_orderdate = per_order(S_ORDER_DATE, 0, ORDER_DATE_RANGE - 1)

    quantity = per_line(S_QUANTITY, 1, 50)
    discount = per_line(S_DISCOUNT, 0, 10)
    tax = per_line(S_TAX, 0, 8)
    part_key = per_line(S_LINE_PART_KEY, 1, n_parts)
    ship = o_orderdate[:, None] + per_line(S_SHIP_DATE, 1, 121)
    receipt = ship + per_line(S_RECEIPT_DATE, 1, 30)
    # the return flag is drawn only for lines received by the current date,
    # so a line's draw is the count of such lines before it in its order
    past = (receipt <= CURRENT_DATE_OFFSET) & live
    draw = np.clip(np.cumsum(past, axis=1) - 1, 0, lines - 1)
    flag_seeds = np.take_along_axis(
        powers.draws(S_RETURN_FLAG, lines, n_orders, lines), draw, axis=1
    )
    returnflag = np.where(past, _bounded(flag_seeds, 0, 1), 2)

    flat = np.nonzero(live.reshape(-1))[0]

    def lines_of(matrix, dtype):
        return matrix.reshape(-1)[flat].astype(dtype)

    segment = _bounded(powers.draws(S_CUST_SEGMENT, 1, n_customers, 1)[:, 0], 0, 4)
    return {
        "lineitem": {
            "l_orderkey": np.repeat(o_orderkey, lines)[flat],
            "l_quantity": lines_of(quantity * 100, np.int32),
            "l_extendedprice": lines_of(quantity * _part_price(part_key), np.int64),
            "l_discount": lines_of(discount, np.int32),
            "l_tax": lines_of(tax, np.int32),
            "l_returnflag": lines_of(returnflag, np.int8),
            "l_linestatus": lines_of(ship > CURRENT_DATE_OFFSET, np.int8),
            "l_shipdate": lines_of(ship, np.int32),
        },
        "orders": {
            "o_orderkey": o_orderkey,
            "o_custkey": o_custkey,
            "o_orderdate": o_orderdate.astype(np.int32),
            "o_shippriority": np.zeros(n_orders, dtype=np.int32),
        },
        "customer": {
            "c_custkey": np.arange(1, n_customers + 1, dtype=np.int64),
            "c_mktsegment": segment.astype(np.int8),
        },
    }


def load(scale_factor: float, tables, cache_dir: str | None):
    """The named tables' columns, generated once per checkout: a run after
    the first reads them back from ``cache_dir`` (plain arrays, no pickle)."""
    path = None
    if cache_dir is not None:
        path = os.path.join(cache_dir, f"refdata-sf{scale_factor:g}.npz")
        if os.path.exists(path):
            with np.load(path, allow_pickle=False) as z:
                return {
                    t: {c: z[f"{t}.{c}"] for c in COLUMNS[t]} for t in tables
                }
    data = generate(scale_factor)
    if path is not None:
        os.makedirs(cache_dir, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp.npz"
        np.savez(tmp, **{f"{t}.{c}": a for t, cols in data.items() for c, a in cols.items()})
        os.replace(tmp, path)
    return {t: data[t] for t in tables}
