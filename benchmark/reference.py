"""The plain reference: what every query's reference function shares.

A template names its reference function (``templates/<t>.json``:
``"reference": "<name>"``), and ``references/<name>.py`` holds it:

    def answer(tables, params, precision="exact", kept=None)
        -> {"rows": [...], "tie_rows": [...]}

straightforward NumPy and Python integers over the reference's own columns
(``refdata.Tables``) and the execution's substitution parameters, importing
nothing of the program. A later PR brings a query's reference by adding such
a file; no query is named here. DECIMAL arithmetic is exact: values are
scaled integers, sums are Python integers past int64's reach, and an average
is the exact quotient rounded half up to the column's scale, as SQL's
DECIMAL division rounds.

``precision="float32"`` is the control (see ``compare.py``): the same
queries with money arithmetic and sums in float32, the step a later PR on a
chip without native int64 would be tempted by. It breaks the configuration's
guarantee of exact DECIMAL arithmetic and has to come out as not correct.
"""

from __future__ import annotations

import datetime
import json
import os
from decimal import Decimal

import numpy as np

from benchmark.files import Refused, load_module

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


#: rows a block of an exact grouped sum holds at the most
GROUPED_BLOCK = 1 << 24


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
        """Sums of ``a`` by ``group`` in 0..n-1, over any number of rows. The
        exact ones go through ``np.bincount``, whose float64 weights hold a
        whole number under 2^53: each value is split at 2^24, so over a block
        of 2^24 rows or fewer the low parts sum to under 2^48 and the high
        parts (of values under 2^48) likewise; the blocks' sums are added as
        Python integers."""
        if self.exact:
            if len(a) and (int(a.max()) >= 1 << 48 or int(a.min()) < 0):
                raise ValueError("exact grouped sum: values out of range")
            sums = [0] * n
            for first in range(0, len(a), GROUPED_BLOCK):
                part, g = a[first:first + GROUPED_BLOCK], group[first:first + GROUPED_BLOCK]
                low = np.bincount(g, weights=part & 0xFFFFFF, minlength=n)
                high = np.bincount(g, weights=part >> 24, minlength=n)
                for i in range(n):
                    sums[i] += (int(high[i]) << 24) + int(low[i])
            return sums
        out = np.zeros(n, dtype=np.float32)
        np.add.at(out, group, a)
        return [int(np.rint(np.float64(v))) for v in out]


#: where the committed reference functions are: beside this file
DATA_ROOT = os.path.dirname(os.path.abspath(__file__))


def load_function(data_root: str, name: str):
    """``answer`` of ``references/<name>.py``; a ``Refused`` where it is not."""
    path = os.path.join(data_root, "references", name + ".py")
    answer = getattr(load_module(path, f"reference function {name!r}"), "answer", None)
    if not callable(answer):
        raise Refused(f"{path} is no reference function: it needs answer()")
    return answer


class Reference:
    """The reference over one set of tables, at one precision. It keeps each
    answer it has given, and what a template computes alike for every set of
    parameters (``kept``), so a window's answers cost less than the window."""

    def __init__(self, tables, precision: str = "exact", data_root: str = DATA_ROOT):
        self.tables = tables
        self.precision = precision
        self.data_root = data_root
        self.functions: dict = {}
        self.kept: dict = {}
        self.answers: dict[str, dict] = {}

    def answer(self, name: str, params: dict) -> dict:
        key = json.dumps([name, params], sort_keys=True)
        if key not in self.answers:
            if name not in self.functions:
                self.functions[name] = load_function(self.data_root, name)
            self.answers[key] = self.functions[name](self.tables, params, self.precision, self.kept)
        return self.answers[key]
