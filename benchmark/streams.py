"""dbgen's random streams (TPC-H clause 4.2: one Lehmer stream a column,
seed' = seed * 16807 mod 2^31-1, a fixed starting seed and a fixed number of
draws a row), as arrays. Draw ``k`` of a stream is ``seed0 * 16807^k``, so a
block of rows anywhere in the table is made from a jump (``pow`` of Python)
and one table of powers as long as the block: what a provider of TPC-H
columns (``datasets/tpch/``) holds at once does not grow with the scale
factor. The arithmetic is a copy of what ``trino_tpu/connectors/dbgen.py``
holds (``PERF.md`` lists the original under Open questions).
"""

from __future__ import annotations

import numpy as np

M = 2147483647  # 2^31 - 1
A = 16807


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


class Powers:
    """One table of powers shared by every stream of a generation: enough
    for ``draws`` of any ``n_rows * per_row + uses <= n``."""

    def __init__(self, n: int):
        self.table = _pow_table(n)

    def draws(self, seed0: int, per_row: int, first_row: int, n_rows: int, uses: int) -> np.ndarray:
        """Seeds of draws (row, j), shape (n_rows, uses), of the 0-based rows
        from ``first_row``: draw j of row r is the stream's
        (r * per_row + j + 1)-th value."""
        start = (seed0 % M) * pow(A, first_row * per_row, M) % M
        i = np.arange(n_rows, dtype=np.int64)[:, None]
        j = np.arange(uses, dtype=np.int64)[None, :]
        return (start * self.table[i * per_row + j + 1]) % M


def bounded(seeds: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """dbgen's UnifInt: lo + trunc(seed / M * range), in float64 as dbgen
    computes it."""
    return lo + ((seeds.astype(np.float64) / M) * (hi - lo + 1)).astype(np.int64)


def blocks(n_rows: int, block: int):
    """(first row, rows) of each block of at most ``block`` rows."""
    for first in range(0, n_rows, block):
        yield first, min(block, n_rows - first)
