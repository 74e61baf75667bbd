"""h2oai db-benchmark, groupby task: the one table ``x`` of data set G1.

Source: github.com/h2oai/db-benchmark, ``_data/groupby-datagen.R`` (later
kept up by DuckDB Labs): ``G1_<N>_<K>_0_0`` is N rows, K the cardinality
factor, 0% NA, unsorted, nine columns:

    id1, id2   VARCHAR  ``id001``..``id<K>``  (``sprintf("id%03d", 1..K)``)
    id3        VARCHAR  ``id0000000001``..    (``"id%010d"``, N/K distinct)
    id4, id5   BIGINT   1..K
    id6        BIGINT   1..N/K
    v1, v2     BIGINT   1..5, 1..15
    v3         DECIMAL(9,6)  0.000000..99.999999

Schemas are the source's sizes by name: ``g1_1e8`` and ``g1_1e7`` with K =
100, and any ``g1_<N>_<K>`` (``g1_2e5_1e1`` is the CPU tests').

The source draws with R's ``sample()`` under ``set.seed(108)``, which
nothing here can reproduce. Instead, a counter-based stream with the same
marginals (uniform, with replacement, unsorted): for row ``i`` (from 0) and
column ordinal ``c`` (0..8, in the order above),

    z     = splitmix64(108 * 2**40 + 16 * i + c)                  (uint64)
    value = 1 + (((z >> 32) * range) >> 32)

with ``range`` K, K, N/K, K, K, N/K, 5, 15, and for ``v3`` the scaled
integer ``((z >> 32) * 100_000_000) >> 32`` at scale 6 (the source has a
double rounded to 6 places). ``splitmix64(x)`` is SplitMix64's output for
the state ``x``:

    z = x + 0x9E3779B97F4A7C15
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9
    z = (z ^ (z >> 27)) * 0x94D049BB133111EB
    z ^ (z >> 31)                                   (all modulo 2**64)

A value depends on its row and column alone, so any split of any columns is
made directly. Nothing is kept on disk: ``DbgenDiskCache``'s bound (1 GiB)
is under one question's columns at 1e8 rows (3.2 GB), and writing them would
push the tpch tables out. Columns a streamed query reads are made once, a
column at a time, and stay in HBM (``device_slab``); they are never computed
inside the query's program.
"""

from __future__ import annotations

import os
import re
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import numpy as np

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column, Dictionary
from trino_tpu.connectors.api import (
    ColumnSchema,
    ColumnStats,
    Connector,
    Split,
    TableSchema,
    TableStats,
    slab_bytes_estimate,
    slab_padded_rows,
)

SEED = 108
V3 = T.decimal(9, 6)
COLUMNS: list[tuple[str, T.SqlType]] = [
    ("id1", T.VARCHAR), ("id2", T.VARCHAR), ("id3", T.VARCHAR),
    ("id4", T.BIGINT), ("id5", T.BIGINT), ("id6", T.BIGINT),
    ("v1", T.BIGINT), ("v2", T.BIGINT), ("v3", V3),
]
_ORDINAL = {name: c for c, (name, _) in enumerate(COLUMNS)}
_TYPE = dict(COLUMNS)
_SCHEMA = re.compile(r"g1_(\d+e\d+)(?:_(\d+e\d+))?$")
#: rows made at once: the uint64 temporaries stay in the host's cache
_BLOCK = 1 << 20


def sizes(schema: str) -> tuple[int, int]:
    """(N, K) of a schema name; KeyError for any other name."""
    m = _SCHEMA.match(schema)
    if m is None:
        raise KeyError(f"unknown h2o schema: {schema}")
    n, k = int(float(m.group(1))), int(float(m.group(2) or "1e2"))
    if not 0 < k <= n <= 10**9 or n % k:
        raise KeyError(f"unknown h2o schema: {schema}")
    return n, k


def ranges(n: int, k: int) -> list[int]:
    """``range`` of each column, by ordinal (``v3``: its scaled values)."""
    return [k, k, n // k, k, k, n // k, 5, 15, 100_000_000]


def splitmix64(x: np.ndarray) -> np.ndarray:
    z = x + np.uint64(0x9E3779B97F4A7C15)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def draws(ordinal: int, span: int, first_row: int, rows: int) -> np.ndarray:
    """``((z >> 32) * span) >> 32`` of the rows from ``first_row``: 0..span-1."""
    i = np.arange(first_row, first_row + rows, dtype=np.uint64)
    z = splitmix64(np.uint64((SEED << 40) + ordinal) + np.uint64(16) * i)
    return (((z >> np.uint64(32)) * np.uint64(span)) >> np.uint64(32)).astype(np.int64)


class H2oConnector(Connector):
    name = "h2o"

    def __init__(self, split_rows: int = 1 << 20):
        self.split_rows = split_rows
        self._dicts: dict[tuple, Dictionary] = {}
        # device-resident columns, a column an entry, oldest use first:
        # (schema, column, padded rows) -> device array
        self._device_columns: dict[tuple, object] = {}

    # --- metadata --------------------------------------------------------
    def list_schemas(self):
        return ["g1_1e7", "g1_1e8", "g1_2e5_1e1"]

    def list_tables(self, schema):
        sizes(schema)
        return ["x"]

    def get_table(self, schema, table):
        try:
            sizes(schema)
        except KeyError:
            return None
        if table != "x":
            return None
        return TableSchema("x", tuple(ColumnSchema(n, t) for n, t in COLUMNS))

    def estimate_rows(self, schema, table):
        return sizes(schema)[0]

    def apply_aggregation_count(self, schema, table):
        return sizes(schema)[0]

    def table_stats(self, schema, table):
        n, k = sizes(schema)
        cols = {}
        for (name, _), span in zip(COLUMNS, ranges(n, k)):
            lo, hi = (0, span - 1) if name == "v3" else (1, span)
            if T.is_string(_TYPE[name]):
                cols[name] = ColumnStats(float(span), 0.0)
            else:
                cols[name] = ColumnStats(float(min(span, n)), 0.0, lo, hi)
        return TableStats(row_count=float(n), columns=cols)

    # --- splits + data ---------------------------------------------------
    def get_splits(self, schema, table, target_splits, constraint=None):
        rows = sizes(schema)[0]
        n = max(1, min(target_splits, -(-rows // self.split_rows)))
        return [Split(table, i, n) for i in range(n)]

    def _dictionary(self, name: str, span: int) -> Dictionary:
        key = (name, span)
        if key not in self._dicts:
            fmt = "id%010d" if name == "id3" else "id%03d"
            self._dicts[key] = Dictionary([fmt % v for v in range(1, span + 1)])
        return self._dicts[key]

    def _fill(self, out: np.ndarray, schema: str, name: str, first_row: int) -> None:
        """Column ``name`` of the rows from ``first_row`` into ``out``, in its
        storage form: dictionary codes (the value less 1) for ``id1``..``id3``,
        the scaled integer for ``v3``, the value itself elsewhere."""
        n, k = sizes(schema)
        c = _ORDINAL[name]
        base = 0 if T.is_string(_TYPE[name]) or name == "v3" else 1

        def block(lo: int) -> None:
            part = out[lo:lo + _BLOCK]
            part[:] = draws(c, ranges(n, k)[c], first_row + lo, len(part)) + base

        if len(out) <= _BLOCK:
            block(0)
            return
        # NumPy's passes run without the interpreter's lock: 1e8 rows of a
        # column take 3.9 s on one thread of this sandbox, 2.3 s on eight
        with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool:
            list(pool.map(block, range(0, len(out), _BLOCK)))

    def _column(self, schema: str, name: str, data) -> Column:
        n, k = sizes(schema)
        t = _TYPE[name]
        d = self._dictionary(name, ranges(n, k)[_ORDINAL[name]]) if T.is_string(t) else None
        return Column(t, data, None, d)

    def read_split(self, schema, table, columns: Sequence[str], split: Split) -> Batch:
        rows = sizes(schema)[0]
        per = -(-rows // split.total)
        lo = split.index * per
        hi = min(rows, lo + per)
        cols = []
        for name in columns:
            data = np.empty(max(hi - lo, 0), dtype=_TYPE[name].storage_dtype)
            self._fill(data, schema, name, lo)
            cols.append(self._column(schema, name, data))
        return Batch(cols, max(hi - lo, 0))

    def device_slab(
        self, schema, table, columns, cap: int, max_bytes: int,
        stats: Optional[dict] = None, mesh=None,
    ):
        """The table's ``columns`` in HBM, each made once on the host (a
        column at a time, so the host holds one) and kept on the device
        for every later query that reads it, padded as
        ``stage_device_slab`` pads. Columns no query of the moment reads
        leave, oldest use first, where the resident ones would pass
        ``max_bytes``; None where these columns alone would, and for a
        ``mesh`` of several devices (the columns live on one; the stream
        then reads the splits on the host)."""
        import jax

        if mesh is not None:
            return None

        from trino_tpu.obs.metrics import get_registry

        rows = sizes(schema)[0]
        if slab_bytes_estimate([_TYPE[c] for c in columns], rows, cap) > max_bytes:
            return None
        padded = slab_padded_rows(rows, cap)
        keys = [(schema, name, padded) for name in columns]
        resident = self._device_columns

        def nbytes(key):
            return key[2] * _TYPE[key[1]].storage_dtype.itemsize

        missing = [key for key in dict.fromkeys(keys) if key not in resident]
        room = max_bytes - sum(nbytes(key) for key in missing)
        for key in [k for k in resident if k not in keys]:
            if sum(nbytes(k) for k in resident) <= room:
                break
            del resident[key]
        made = 0
        for key in missing:
            data = np.zeros(padded, dtype=_TYPE[key[1]].storage_dtype)
            self._fill(data[:rows], schema, key[1], 0)
            resident[key] = jax.device_put(data)
            made += data.nbytes
        for key in keys:
            resident[key] = resident.pop(key)  # the newest use
        if made:
            get_registry().counter("trino_tpu_ingest_h2d_bytes_total").inc(made)
        if stats is not None:
            stats["h2d_bytes"] = stats.get("h2d_bytes", 0) + made
        cols = [self._column(schema, key[1], resident[key]) for key in keys]
        return Batch(cols, padded), rows
