"""The benchmark's own arithmetic on samples (a copy of what it uses of
``trino_tpu/obs/metrics.py::percentile``; ``PERF.md`` lists the original)."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile, q in [0, 100]; None of no values."""
    if not values:
        return None
    vs = sorted(values)
    if len(vs) == 1:
        return float(vs[0])
    rank = (q / 100.0) * (len(vs) - 1)
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(vs) - 1)
    return float(vs[lo] + (vs[hi] - vs[lo]) * (rank - lo))
