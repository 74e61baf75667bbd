"""Columnar batch model: the TPU-native analog of Trino's Page/Block.

Reference: ``core/trino-spi/src/main/java/io/trino/spi/Page.java:53-85`` and
the 14 Block implementations under ``spi/block/``.

Design (TPU-first):
- A :class:`Column` is a fixed-width device array plus an optional validity
  mask. Strings carry a host-side :class:`Dictionary` (int32 codes on device).
- A :class:`Batch` is a list of equal-capacity columns plus a *selection*
  mask. Filters AND into the selection instead of compacting (static shapes
  for XLA); compaction happens at exchange/output boundaries where we are on
  the host anyway.
- Batches are registered as JAX pytrees so whole batches flow through
  ``jax.jit`` boundaries; dictionaries/types are static aux data.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T

_trace_tls = threading.local()


class Dictionary:
    """Host-side string dictionary. Code i <-> string values[i].

    Codes are dense int32. ``sorted_ranks`` supports order comparisons on
    codes (rank[code] preserves lexicographic order) without device strings.

    A *trace log* (opened per-thread via :meth:`begin_trace_log`, since
    jax traces on the calling thread and worker tasks trace concurrently)
    records which dictionaries contributed *growth-sensitive* constants to
    a trace: rank tables, and equality encodes that missed. Streaming uses
    this to decide whether appending values to a dictionary mid-stream
    would invalidate an already-compiled step (see ``exec/streaming.py``).
    """

    __slots__ = ("values", "_index", "_ranks")

    @staticmethod
    def begin_trace_log():
        """Open a fresh per-thread log; returns the previous one to restore."""
        prev = getattr(_trace_tls, "log", None)
        _trace_tls.log = {}
        return prev

    @staticmethod
    def end_trace_log(prev) -> dict:
        """Close the current per-thread log (restoring ``prev``) and return it."""
        log = getattr(_trace_tls, "log", None)
        _trace_tls.log = prev
        return log or {}

    def __init__(self, values: Sequence[str]):
        self.values: list[str] = list(values)
        self._index: dict[str, int] | None = None
        self._ranks: np.ndarray | None = None

    def __len__(self) -> int:
        return len(self.values)

    def decode(self, code: int) -> str | None:
        if code < 0:
            return None
        return self.values[code]

    def index(self) -> dict[str, int]:
        if self._index is None:
            self._index = {v: i for i, v in enumerate(self.values)}
        return self._index

    def encode(self, value: str) -> int:
        """Code for value, or -1 if absent (useful for predicates)."""
        code = self.index().get(value, -1)
        log = getattr(_trace_tls, "log", None)
        if code < 0 and log is not None:
            # a miss traced as the constant -1 stops being correct if this
            # dictionary later absorbs the value
            log.setdefault("growth_sensitive", set()).add(id(self))
        return code

    def ranks(self) -> np.ndarray:
        """rank[code] gives the lexicographic rank of each dictionary entry."""
        log = getattr(_trace_tls, "log", None)
        if log is not None:
            log.setdefault("growth_sensitive", set()).add(id(self))
        if self._ranks is None:
            order = np.argsort(np.asarray(self.values, dtype=object), kind="stable")
            ranks = np.empty(len(self.values), dtype=np.int32)
            ranks[order] = np.arange(len(self.values), dtype=np.int32)
            self._ranks = ranks
        return self._ranks

    def absorb(self, other: "Dictionary") -> tuple[np.ndarray | None, bool]:
        """Merge ``other``'s values into *this* dictionary in place
        (append-only: existing codes stay valid, so programs already traced
        against this object keep working unless they embedded
        growth-sensitive constants — see ``trace_log``).

        Returns (remap, grew): ``remap[other_code] -> my code`` (None when
        the dictionaries already agree code-for-code), and whether new
        values were appended (invalidates cached ranks)."""
        if other is self:
            return None, False
        index = self.index()
        remap = np.empty(len(other.values), dtype=np.int32)
        grew = False
        identical = len(other.values) <= len(self.values)
        for i, v in enumerate(other.values):
            code = index.get(v)
            if code is None:
                code = len(self.values)
                self.values.append(v)
                index[v] = code
                grew = True
                identical = False
            elif code != i:
                identical = False
            remap[i] = code
        if grew:
            self._ranks = None
        return (None if identical else remap), grew

    @staticmethod
    def from_strings(strings: Iterable[str]) -> tuple["Dictionary", np.ndarray]:
        """Build a dictionary and the code array for a string sequence.
        Hot host loop — uses the native hash table (native/columnar.cpp
        tt_dict_encode) when built, with a Python fallback inside."""
        from trino_tpu.native import dict_encode

        strings = strings if isinstance(strings, list) else list(strings)
        codes, values = dict_encode(strings)
        return Dictionary(values), codes

    def merged(self, other: "Dictionary") -> tuple["Dictionary", np.ndarray]:
        """Merge other into a new dictionary; returns (merged, remap) where
        remap[old_other_code] = new code."""
        values = list(self.values)
        index = dict(self.index())
        remap = np.empty(len(other.values), dtype=np.int32)
        for i, v in enumerate(other.values):
            code = index.get(v)
            if code is None:
                code = len(values)
                index[v] = code
                values.append(v)
            remap[i] = code
        d = Dictionary(values)
        d._index = index
        return d, remap


@dataclasses.dataclass
class Column:
    """One column: device data + optional validity + optional dictionary."""

    type: T.SqlType
    data: jax.Array | np.ndarray
    valid: jax.Array | np.ndarray | None = None  # None = all valid
    dictionary: Dictionary | None = None

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def valid_mask(self) -> jax.Array:
        if self.valid is None:
            return jnp.ones(self.data.shape[0], dtype=jnp.bool_)
        return self.valid

    def to_numpy(self) -> tuple[np.ndarray, np.ndarray]:
        data = np.asarray(self.data)
        valid = (
            np.ones(data.shape[0], dtype=np.bool_)
            if self.valid is None
            else np.asarray(self.valid)
        )
        return data, valid

    @staticmethod
    def from_values(type_: T.SqlType, values: Sequence[Any]) -> "Column":
        """Build a column from Python values (None = NULL). Test/glue path."""
        n = len(values)
        valid = np.asarray([v is not None for v in values], dtype=np.bool_)
        if T.is_string(type_):
            strings = [v if v is not None else "" for v in values]
            dictionary, codes = Dictionary.from_strings(strings)
            codes = np.where(valid, codes, -1).astype(np.int32)
            return Column(type_, codes, None if valid.all() else valid, dictionary)
        dtype = type_.storage_dtype
        if isinstance(type_, T.DecimalType):
            from decimal import Decimal

            # exact: go through Decimal, not float (float loses >2^53)
            filled = [
                int(Decimal(str(v)).scaleb(type_.scale).to_integral_value())
                if v is not None
                else 0
                for v in values
            ]
        elif isinstance(type_, T.DateType):
            import datetime

            epoch = datetime.date(1970, 1, 1)
            filled = [
                (datetime.date.fromisoformat(v) - epoch).days
                if isinstance(v, str)
                else (0 if v is None else int(v))
                for v in values
            ]
        else:
            filled = [0 if v is None else v for v in values]
        data = np.asarray(filled, dtype=dtype)
        return Column(type_, data, None if valid.all() else valid, None)


@dataclasses.dataclass
class Batch:
    """A batch of rows: equal-capacity columns + selection mask + row count.

    ``num_rows`` is the count of *physical* rows (leading); rows past it are
    padding. ``sel`` (optional, shape (capacity,)) marks rows surviving
    filters. Logical rows = first num_rows AND sel.
    """

    columns: list[Column]
    num_rows: int
    sel: jax.Array | np.ndarray | None = None

    @property
    def capacity(self) -> int:
        if self.columns:
            return self.columns[0].capacity
        if self.sel is not None:
            return int(self.sel.shape[0])
        return self.num_rows

    @property
    def width(self) -> int:
        return len(self.columns)

    def selection_mask(self) -> jax.Array:
        """Full boolean mask over capacity combining num_rows and sel."""
        base = jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows
        if self.sel is not None:
            base = base & self.sel
        return base

    def count_rows(self) -> int:
        """Logical row count (host sync if sel is set)."""
        if self.sel is None:
            return self.num_rows
        return int(np.asarray(self.selection_mask()).sum())

    def to_host(self, extras: Sequence | None = None):
        """Pull every device array to host in ONE packed D2H transfer.

        Device→host transfers pay a large fixed latency per transfer (the
        TPU runtime round-trip dwarfs the bytes for result-sized arrays),
        so pulling a batch column-by-column costs ``(2·width+1)`` latencies.
        Instead, every packable array becomes uint32 words (int64 as lo/hi
        word lanes — TPU x64 rewriting forbids 64-bit bitcasts), one
        device-side concatenate, one transfer, host views back.

        ``extras`` (optional device arrays, e.g. deferred overflow flags)
        ride the same transfer; when given, returns (batch, extra_values).
        """
        bufs: list = []  # (kind, col_idx) aligned with `arrays`
        arrays: list = []

        def note(kind, idx, a):
            if isinstance(a, jax.Array) and _packable(a.dtype):
                arrays.append(a)
                bufs.append((kind, idx))
                return None
            return np.asarray(a) if isinstance(a, jax.Array) else a

        host_data = [note("data", j, c.data) for j, c in enumerate(self.columns)]
        host_valid = [
            None if c.valid is None else note("valid", j, c.valid)
            for j, c in enumerate(self.columns)
        ]
        host_sel = None if self.sel is None else note("sel", -1, self.sel)
        host_extras = [
            note("extra", j, a) for j, a in enumerate(extras or ())
        ]
        if arrays:
            views = _unpack_words(np.asarray(_PACK_WORDS(arrays)), arrays)
            for (kind, idx), v in zip(bufs, views):
                if kind == "data":
                    host_data[idx] = v
                elif kind == "valid":
                    host_valid[idx] = v
                elif kind == "extra":
                    host_extras[idx] = v
                else:
                    host_sel = v
        cols = [
            Column(c.type, host_data[j], host_valid[j], c.dictionary)
            for j, c in enumerate(self.columns)
        ]
        out = Batch(cols, self.num_rows, host_sel)
        if extras is None:
            return out
        return out, host_extras

    def compact(self) -> "Batch":
        """Materialize selection: gather surviving rows to the front (host)."""
        if self.sel is None and all(c.capacity == self.num_rows for c in self.columns):
            return self
        b = self.to_host()
        # host-side mask: selection_mask() would rebuild it as a device
        # array and pay another device->host round trip
        mask = np.arange(b.capacity) < b.num_rows
        if b.sel is not None:
            mask &= np.asarray(b.sel)
        idx = np.nonzero(mask)[0]
        cols = []
        for c in b.columns:
            data, valid = c.to_numpy()
            cols.append(
                Column(c.type, data[idx], None if valid[idx].all() else valid[idx], c.dictionary)
            )
        return Batch(cols, len(idx), None)

    def to_pylist(self) -> list[tuple]:
        """Rows as Python tuples (client output/testing)."""
        b = self.compact()
        out_cols = []
        for c in b.columns:
            data, valid = c.to_numpy()
            valid = valid[: b.num_rows]
            if valid.all():
                col = c.type.to_python_list(data[: b.num_rows], c.dictionary)
            else:
                col = [
                    c.type.to_python(data[i], c.dictionary) if valid[i] else None
                    for i in range(b.num_rows)
                ]
            out_cols.append(col)
        return list(zip(*out_cols)) if out_cols else [()] * b.num_rows

    @staticmethod
    def from_pylist(schema: Sequence[tuple[str, T.SqlType]], rows: Sequence[Sequence[Any]]):
        """Build (names, Batch) from row-major Python data."""
        cols = []
        for j, (_, t) in enumerate(schema):
            cols.append(Column.from_values(t, [r[j] for r in rows]))
        return Batch(cols, len(rows), None)


def _packable(dtype) -> bool:
    return np.dtype(dtype) in (
        np.dtype(np.bool_),
        np.dtype(np.int32),
        np.dtype(np.uint32),
        np.dtype(np.float32),
        np.dtype(np.int64),
        np.dtype(np.uint64),
    )


def _pack_words(arrays):
    """Traced: flatten each array into uint32 word lanes and concatenate."""
    segs = []
    for a in arrays:
        x = jnp.ravel(a)
        dt = np.dtype(a.dtype)
        if dt == np.dtype(np.bool_):
            segs.append(x.astype(jnp.uint32))
        elif dt in (np.dtype(np.int64), np.dtype(np.uint64)):
            segs.append(x.astype(jnp.uint32))  # low word (mod 2^32)
            segs.append((x >> 32).astype(jnp.uint32))  # high word
        else:
            segs.append(jax.lax.bitcast_convert_type(x, jnp.uint32))
    return jnp.concatenate(segs) if segs else jnp.zeros(0, jnp.uint32)


_PACK_WORDS = jax.jit(_pack_words)


def _unpack_words(packed: np.ndarray, arrays) -> list[np.ndarray]:
    """Rebuild host arrays from the packed uint32 word stream."""
    out = []
    off = 0
    for a in arrays:
        dt = np.dtype(a.dtype)
        n = int(np.prod(a.shape, dtype=np.int64))
        if dt == np.dtype(np.bool_):
            out.append(packed[off : off + n].astype(np.bool_).reshape(a.shape))
            off += n
        elif dt in (np.dtype(np.int64), np.dtype(np.uint64)):
            lo = packed[off : off + n].astype(np.uint64)
            hi = packed[off + n : off + 2 * n].astype(np.uint64)
            out.append(((hi << np.uint64(32)) | lo).view(dt).reshape(a.shape))
            off += 2 * n
        else:
            out.append(packed[off : off + n].view(dt).reshape(a.shape))
            off += n
    return out


def concat_batches(batches: Sequence[Batch]) -> Batch:
    """Host-side concatenation (compacting). Used at stage boundaries."""
    if not batches:
        raise ValueError("concat of zero batches")
    batches = [b.compact() for b in batches]
    nonempty = [b for b in batches if b.num_rows > 0]
    batches = nonempty or batches[:1]
    if len(batches) == 1:
        return batches[0]
    width = batches[0].width
    cols = []
    for j in range(width):
        parts = [b.columns[j] for b in batches]
        t = parts[0].type
        dictionary = None
        if T.is_string(t):
            dictionary = parts[0].dictionary or Dictionary([])
            datas = []
            valids = []
            for p in parts:
                data, valid = p.to_numpy()
                if p.dictionary is not None and p.dictionary is not dictionary:
                    dictionary, remap = dictionary.merged(p.dictionary)
                    data = np.where(data >= 0, remap[np.maximum(data, 0)], -1).astype(np.int32)
                datas.append(data)
                valids.append(valid)
            data = np.concatenate(datas)
            valid = np.concatenate(valids)
        else:
            pairs = [p.to_numpy() for p in parts]
            data = np.concatenate([d for d, _ in pairs])
            valid = np.concatenate([v for _, v in pairs])
        cols.append(Column(t, data, None if valid.all() else valid, dictionary))
    return Batch(cols, sum(b.num_rows for b in batches), None)


def pad_batch(batch: Batch, capacity: int) -> Batch:
    """Pad physical rows up to capacity (power-of-two bucketing lives above)."""
    b = batch
    if b.capacity == capacity:
        return b
    if b.capacity > capacity:
        raise ValueError(f"batch capacity {b.capacity} > target {capacity}")
    pad = capacity - b.capacity
    cols = []
    for c in b.columns:
        data = np.asarray(c.data)
        pad_shape = (pad,) + data.shape[1:]  # wide decimals are (n, 2)
        data = np.concatenate([data, np.zeros(pad_shape, dtype=data.dtype)])
        if c.valid is not None:
            valid = np.concatenate([np.asarray(c.valid), np.zeros(pad, dtype=np.bool_)])
        else:
            valid = None
        cols.append(Column(c.type, data, valid, c.dictionary))
    sel = batch.sel
    if sel is not None:
        sel = np.concatenate([np.asarray(sel), np.zeros(pad, dtype=np.bool_)])
    return Batch(cols, b.num_rows, sel)


def bucket_capacity(n: int, minimum: int = 1024) -> int:
    """Round up to a power of two (recompile-avoidance shape bucketing)."""
    cap = minimum
    while cap < n:
        cap <<= 1
    return cap


# --- pytree registration ---------------------------------------------------
# Columns/Batches cross jit boundaries with (type, dictionary) static.


def _column_flatten(c: Column):
    return (c.data, c.valid), (c.type, c.dictionary)


def _column_unflatten(aux, children):
    t, dictionary = aux
    data, valid = children
    return Column(t, data, valid, dictionary)


def _batch_flatten(b: Batch):
    return (b.columns, b.sel), (b.num_rows,)


def _batch_unflatten(aux, children):
    (num_rows,) = aux
    columns, sel = children
    return Batch(list(columns), num_rows, sel)


jax.tree_util.register_pytree_node(Column, _column_flatten, _column_unflatten)
jax.tree_util.register_pytree_node(Batch, _batch_flatten, _batch_unflatten)
