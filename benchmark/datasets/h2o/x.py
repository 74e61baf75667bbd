"""h2oai db-benchmark, groupby task: the nine columns of ``x`` (data set
``G1_<N>_<K>_0_0``: N rows, K = 100, 0% NA, unsorted), made from nothing.

The source (``_data/groupby-datagen.R``) draws with R's ``sample()`` under
``set.seed(108)``, which nothing here can reproduce; the configuration's
``assumed`` states the stream used instead, and this is it, written out
again in NumPy (it imports nothing of the program). For row ``i`` (from 0)
and column ordinal ``c`` (0..8: id1, id2, id3, id4, id5, id6, v1, v2, v3):

    z     = splitmix64(108 * 2**40 + 16 * i + c)                  (uint64)
    value = 1 + (((z >> 32) * range) >> 32)

with ``range`` K, K, N/K, K, K, N/K, 5, 15; ``v3`` is the scaled integer
``((z >> 32) * 100_000_000) >> 32`` of a DECIMAL(9,6) (0.000000..99.999999).
``splitmix64(x)`` is SplitMix64's output for the state ``x``: ``z = x +
0x9E3779B97F4A7C15; z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9; z = (z ^ (z >>
27)) * 0x94D049BB133111EB; z ^ (z >> 31)``, all modulo 2**64.

``id1``..``id3`` are coded: the value less 1, with ``LABELS`` giving a
code's text (``id001``.., ``id0000000001``..). ``scale_factor`` carries N;
``columns`` takes another K for the small sizes of the tests.
"""

from __future__ import annotations

import numpy as np

NAMES = ("id1", "id2", "id3", "id4", "id5", "id6", "v1", "v2", "v3")
K = 100
GIVES = {"x": {name: {} for name in NAMES}}
#: rows made at once: the uint64 temporaries stay in the host's cache
BLOCK = 1 << 20


class Ids:
    """``fmt % (code + 1)`` for any code: the text of a coded id column."""

    def __init__(self, fmt: str):
        self.fmt = fmt

    def __getitem__(self, code) -> str:
        return self.fmt % (int(code) + 1)


LABELS = {"x": {"id1": Ids("id%03d"), "id2": Ids("id%03d"), "id3": Ids("id%010d")}}


def splitmix64(x: np.ndarray) -> np.ndarray:
    z = x + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def column(name: str, n: int, k: int) -> np.ndarray:
    c = NAMES.index(name)
    span = (k, k, n // k, k, k, n // k, 5, 15, 100_000_000)[c]
    coded = name in LABELS["x"]
    out = np.empty(n, dtype=np.int32 if coded else np.int64)
    for first in range(0, n, BLOCK):
        i = np.arange(first, min(n, first + BLOCK), dtype=np.uint64)
        z = splitmix64(np.uint64((108 << 40) + c) + np.uint64(16) * i)
        draw = ((z >> np.uint64(32)) * np.uint64(span)) >> np.uint64(32)
        out[first:first + len(i)] = draw.astype(np.int64) + (0 if coded or name == "v3" else 1)
    return out


def columns(n: int, k: int, names) -> dict:
    if n % k:
        raise ValueError(f"K = {k} does not divide N = {n}")
    return {name: column(name, n, k) for name in names}


def generate(scale_factor: float, wanted: dict, have: dict) -> dict:
    return {"x": columns(int(scale_factor), K, wanted["x"])}
