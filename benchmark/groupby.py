"""What the reference functions of grouped sums and averages share
(``references/g1q*.py``): sums by a group code, exact or the control's.

Exact sums go through ``np.bincount`` once, over all rows. Its weights are
float64, which holds every whole number under 2^53, and a bin's running sum
never passes its group's total, which is at most the largest group's rows
times the largest value: where that product is under 2^53 every addition is
exact, and it is checked (G1 at 1e8 rows: 1,002,101 rows of a value under
1e8 in the largest group of q4, 1.0e14; a million groups of about 100 rows
in q3 and q5). ``precision="float32"`` is the control (``control.py``):
values and running sums in float32, the step a chip without native int64
tempts; it comes out as not correct wherever a value or a group's sum
passes 2^24.
"""

from __future__ import annotations

import numpy as np


def counts(group: np.ndarray, n: int) -> np.ndarray:
    return np.bincount(group, minlength=n)


def sums(values: np.ndarray, group: np.ndarray, count: np.ndarray,
         precision: str = "exact") -> list[int]:
    """Sums of ``values`` (whole numbers from 0) by ``group``, as Python
    integers; ``count`` is ``counts(group, n)`` and gives n."""
    n = len(count)
    if precision == "float32":
        out = np.zeros(n, dtype=np.float32)
        np.add.at(out, group, values.astype(np.float32))
        return [int(v) for v in np.rint(out.astype(np.float64))]
    if precision != "exact":
        raise ValueError(f"unknown precision {precision!r}")
    if len(values) and (int(values.min()) < 0
                        or int(count.max()) * int(values.max()) >= 1 << 53):
        raise ValueError("exact grouped sum: a group's sum may pass 2^53")
    return np.bincount(group, weights=values, minlength=n).astype(np.int64).tolist()

