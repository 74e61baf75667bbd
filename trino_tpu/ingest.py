"""Device-resident columnar ingest tier.

After whole-pipeline fusion the kernels are no longer the bottleneck —
feeding them is: BENCH_r04 measured 37.7M rows/s in-kernel vs 0.9M rows/s
once host->device transfer is included, with a ~150us DMA latency floor
per transfer. This module closes that gap from three directions:

- **Coalesced H2D** (:func:`shard_batch_coalesced`): instead of one
  ``device_put`` per column per device (``parallel/mesh.py::_global``),
  every packable buffer of a shard — column data, validity lanes, the
  selection mask — is packed into ONE contiguous uint32 staging arena
  (native hot loop ``tt_pack_arena``, numpy fallback) and moved with a
  single transfer per device, then sliced back into columns *on device*
  by a cached shard_map program. One DMA latency amortizes across all
  columns, and the transfer dispatches async so it rides under compute.
  int64 moves as interleaved lo/hi uint32 word lanes (TPU x64 rewriting
  forbids 64-bit bitcasts) and is reconstructed exactly on device;
  float64 columns fall back to per-column placement.

- **Double-buffered decode** (:class:`SplitPrefetcher`): a two-slot
  pipeline where a background thread decodes split k+1 (Parquet/ORC
  chunk -> host columnar batch, the C hot loops in native/columnar.cpp)
  while the device executes over split k.

- **Device table cache** (:class:`DeviceTableCache`): the table-serving
  analogue of the cross-query program cache. Scanned tables stay
  HBM-resident keyed by (catalog, schema, table, data version,
  projection, split fingerprint, mesh), with a byte-budget LRU whose
  admission consults the device profiler's peak-HBM accounting — a warm
  repeat scan issues zero H2D bytes.

Reference: Trino keeps hot pages pinned in the worker heap
(``MemoryPool`` / ``PageCache``); HBM plays that role here.
"""

from __future__ import annotations

import hashlib
import math
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu.columnar import Batch, Column
from trino_tpu.parallel.mesh import (
    AXIS,
    _global,
    prepare_shards,
    row_sharding,
    smap,
)
from jax.sharding import Mesh, PartitionSpec as PS

# === arena layout ===========================================================
#
# A segment is one host buffer's image in the arena: raw little-endian
# bytes at a word-aligned offset (zero tail padding). The device unpack
# program rebuilds each array from its word span:
#   - 4-byte dtypes: 32-bit bitcast (allowed on TPU)
#   - sub-word dtypes (bool/int8/int16): bitcast to lanes, slice to n
#   - 8-byte ints: interleaved (lo, hi) word pairs -> hi*2^32 + lo
# float64 has no TPU-legal reconstruction (64-bit bitcast is forbidden
# and arithmetic reassembly is inexact), so DOUBLE columns bypass the
# arena via per-column device_put.

_PACKABLE = {
    np.dtype(np.bool_),
    np.dtype(np.int8),
    np.dtype(np.uint8),
    np.dtype(np.int16),
    np.dtype(np.uint16),
    np.dtype(np.int32),
    np.dtype(np.uint32),
    np.dtype(np.float32),
    np.dtype(np.int64),
    np.dtype(np.uint64),
}


def packable(dtype) -> bool:
    return np.dtype(dtype) in _PACKABLE


def _segment_words(dtype, shape) -> int:
    nbytes = math.prod(shape) * np.dtype(dtype).itemsize
    return (nbytes + 3) // 4


def _unpack_segment(words, off: int, dtype, shape):
    """Rebuild one array from its word span (traced, runs on device)."""
    dt = np.dtype(dtype)
    n = math.prod(shape)
    w = _segment_words(dt, shape)
    seg = jax.lax.slice_in_dim(words, off, off + w)
    if dt.itemsize == 8:
        pair = seg.reshape(n, 2)  # interleaved (lo, hi), little-endian
        lo = pair[:, 0]
        if dt == np.dtype(np.uint64):
            out = (pair[:, 1].astype(jnp.uint64) << 32) | lo.astype(jnp.uint64)
        else:
            hi = jax.lax.bitcast_convert_type(pair[:, 1], jnp.int32)
            # exact two's-complement reassembly: sign-extended high word
            # times 2^32 plus zero-extended low word
            out = hi.astype(jnp.int64) * jnp.int64(1 << 32) + lo.astype(
                jnp.int64
            )
    elif dt.itemsize == 4:
        out = jax.lax.bitcast_convert_type(seg, dt)
    else:
        lane_dt = np.dtype(np.uint8) if dt == np.dtype(np.bool_) else dt
        lanes = jax.lax.bitcast_convert_type(seg, lane_dt)
        out = lanes.reshape(-1)[:n]
        if dt == np.dtype(np.bool_):
            out = out.astype(jnp.bool_)
    return out.reshape(shape), off + w


# one compiled unpack program per (mesh, segment signature); bounded so
# pathological shape churn cannot leak programs
_UNPACK_CACHE: "OrderedDict[tuple, Any]" = OrderedDict()
_UNPACK_CACHE_MAX = 64
_UNPACK_LOCK = threading.Lock()


def _unpack_program(mesh, signature: tuple):
    key = (mesh, signature)
    with _UNPACK_LOCK:
        fn = _UNPACK_CACHE.get(key)
        if fn is not None:
            _UNPACK_CACHE.move_to_end(key)
            return fn

    def unpack_shard(words):
        outs = []
        off = 0
        for dtype, shape in signature:
            arr, off = _unpack_segment(words, off, dtype, shape)
            outs.append(arr)
        return tuple(outs)

    fn = jax.jit(
        smap(
            unpack_shard,
            mesh=mesh,
            in_specs=PS(AXIS),
            out_specs=tuple(PS(AXIS) for _ in signature),
        )
    )
    with _UNPACK_LOCK:
        _UNPACK_CACHE[key] = fn
        while len(_UNPACK_CACHE) > _UNPACK_CACHE_MAX:
            _UNPACK_CACHE.popitem(last=False)
    return fn


# Below this many total bytes the coalescing can't pay for itself even
# on a real chip: a cold scan is unpack-program-cold too, so a handful
# of per-column transfers at the ~150us DMA floor costs less than the
# first-touch XLA compile of the unpack program (warm repeats skip H2D
# entirely via the table cache, so only cold scans ever face this
# trade). Small scans take the per-column path, H2D still accounted.
# Session property `coalesce_min_bytes` overrides per query.
COALESCE_MIN_BYTES = 1 << 23


def _batch_buffer_bytes(parts: Sequence[Batch]) -> tuple[int, int]:
    """(total bytes, buffer count) across all column/validity/capacity
    buffers — the transfer volume estimate gating coalescing."""
    total = 0
    bufs = 0
    for p in parts:
        for c in p.columns:
            total += c.data.nbytes
            bufs += 1
            if c.valid is not None:
                total += np.asarray(c.valid).nbytes
                bufs += 1
    return total, bufs


def shard_batch_coalesced(
    mesh,
    parts: Sequence[Batch],
    use_native: bool = True,
    stats: Optional[dict] = None,
    min_bytes: int = COALESCE_MIN_BYTES,
) -> Batch:
    """Assemble per-shard host batches into one globally-sharded Batch
    with ONE coalesced H2D transfer per device.

    Bit-identical to ``parallel/mesh.py::shard_batch`` (both build on
    :func:`prepare_shards`); only the transport differs. ``stats`` (the
    executor's ingest counters) receives h2d byte/transfer accounting.
    Scans under ``min_bytes`` delegate to the per-column path — the
    arena only wins once the transfer volume amortizes the unpack
    program's compile.
    """
    from trino_tpu.native import pack_arena

    n = mesh.devices.size
    est_bytes, est_bufs = _batch_buffer_bytes(parts)
    if est_bytes < min_bytes:
        from trino_tpu.obs.metrics import get_registry
        from trino_tpu.parallel.mesh import shard_batch

        batch = shard_batch(mesh, parts)
        get_registry().counter("trino_tpu_ingest_h2d_bytes_total").inc(
            est_bytes
        )
        if stats is not None:
            stats["h2d_bytes"] = stats.get("h2d_bytes", 0) + est_bytes
            stats["h2d_transfers"] = (
                stats.get("h2d_transfers", 0) + est_bufs
            )
        return batch

    cap, sels, columns = prepare_shards(mesh, parts)
    sharding = row_sharding(mesh)

    # split buffers into arena segments vs per-column fallbacks
    signature: list[tuple] = []  # (dtype, per-shard shape)
    slots: list[tuple] = []  # ("sel",) | ("data", j) | ("valid", j)
    per_part: list[list[np.ndarray]] = [[] for _ in range(n)]

    def add_segment(slot, arrays):
        signature.append((arrays[0].dtype, arrays[0].shape))
        slots.append(slot)
        for i, a in enumerate(arrays):
            per_part[i].append(a)

    if sels is not None:
        add_segment(("sel",), sels)
    fallback: list[tuple] = []  # (slot, arrays)
    for j, (t, d, datas, valids) in enumerate(columns):
        if packable(datas[0].dtype):
            add_segment(("data", j), datas)
        else:
            fallback.append((("data", j), datas))
        if valids is not None:
            add_segment(("valid", j), valids)

    if not signature:
        # nothing packable (e.g. all-DOUBLE projection): plain path
        from trino_tpu.parallel.mesh import shard_batch

        return shard_batch(mesh, parts)

    from trino_tpu.obs.metrics import get_registry
    from trino_tpu.obs.trace import get_tracer

    t0 = time.perf_counter()
    with get_tracer().span("ingest.h2d") as span:
        arenas = [pack_arena(bufs, use_native=use_native) for bufs in per_part]
        words = arenas[0].size
        arena_g = _global(mesh, sharding, arenas)
        outs = _unpack_program(mesh, tuple(signature))(arena_g)

        # per-column device_put for non-arena dtypes (float64)
        results: dict[tuple, Any] = dict(zip(slots, outs))
        fallback_bytes = 0
        for slot, arrays in fallback:
            results[slot] = _global(mesh, sharding, arrays)
            fallback_bytes += sum(a.nbytes for a in arrays)

        total_bytes = n * words * 4 + fallback_bytes
        span.set("bytes", total_bytes)
        span.set("transfers", n + len(fallback) * n)
    h2d_ms = (time.perf_counter() - t0) * 1000.0
    get_registry().counter("trino_tpu_ingest_h2d_bytes_total").inc(
        total_bytes
    )
    if stats is not None:
        stats["h2d_bytes"] = stats.get("h2d_bytes", 0) + total_bytes
        stats["h2d_transfers"] = (
            stats.get("h2d_transfers", 0) + n + len(fallback) * n
        )
        stats["coalesced_columns"] = stats.get("coalesced_columns", 0) + len(
            columns
        ) - len(fallback)
        stats["fallback_columns"] = stats.get("fallback_columns", 0) + len(
            fallback
        )
        stats["h2d_ms"] = round(stats.get("h2d_ms", 0.0) + h2d_ms, 3)

    cols: list[Column] = []
    for j, (t, d, _datas, valids) in enumerate(columns):
        data_g = results[("data", j)]
        valid_g = None if valids is None else results[("valid", j)]
        cols.append(Column(t, data_g, valid_g, d))
    sel = None if sels is None else results[("sel",)]
    return Batch(cols, cap * n, sel)


def put_batch(batch: Batch, stats: Optional[dict] = None) -> tuple[Batch, int]:
    """A host batch on the default device, one ``jax.device_put`` a column
    (data, validity, selection); dictionaries stay host objects. Returns
    (device batch, bytes put), the bytes counted into ``stats["h2d_bytes"]``
    as a sharded scan's are. The single-device counterpart of
    :func:`shard_batch_coalesced`: nothing to coalesce, since a resident
    table pays this once."""
    from trino_tpu.obs.metrics import get_registry
    from trino_tpu.obs.trace import get_tracer

    nbytes, bufs = _batch_buffer_bytes([batch])
    if batch.sel is not None:
        nbytes += np.asarray(batch.sel).nbytes
        bufs += 1
    with get_tracer().span(
        "ingest.h2d", attrs={"bytes": nbytes, "transfers": bufs}
    ):
        cols = [
            Column(
                c.type,
                jax.device_put(c.data),
                None if c.valid is None else jax.device_put(c.valid),
                c.dictionary,
            )
            for c in batch.columns
        ]
        sel = None if batch.sel is None else jax.device_put(batch.sel)
        jax.block_until_ready((cols, sel))  # the span holds the transfers
    get_registry().counter("trino_tpu_ingest_h2d_bytes_total").inc(nbytes)
    if stats is not None:
        stats["h2d_bytes"] = stats.get("h2d_bytes", 0) + nbytes
        stats["h2d_transfers"] = stats.get("h2d_transfers", 0) + bufs
    return Batch(cols, batch.num_rows, sel), nbytes


# === double-buffered split decode ===========================================


class SplitPrefetcher:
    """Two-slot decode pipeline: a background thread decodes split k+1
    while the caller consumes split k, so host-side Parquet/ORC decode
    overlaps device execution instead of serializing ahead of it.

    Exactly two staging slots are live at any time (one being consumed,
    one being filled) — the bounded queue is the double buffer. Decode
    exceptions propagate to the consumer in order.
    """

    _SENTINEL = object()

    def __init__(
        self,
        fn: Callable[[Any], Any],
        items: Sequence[Any],
        enabled: bool = True,
        stats: Optional[dict] = None,
    ):
        self._fn = fn
        self._items = list(items)
        self._enabled = enabled and len(self._items) > 1
        self._stats = stats

    def _decode(self, item):
        t0 = time.perf_counter()
        out = self._fn(item)
        ms = (time.perf_counter() - t0) * 1000.0
        if self._stats is not None:
            self._stats["decode_ms"] = round(
                self._stats.get("decode_ms", 0.0) + ms, 3
            )
            self._stats["splits_decoded"] = (
                self._stats.get("splits_decoded", 0) + 1
            )
        from trino_tpu.obs.metrics import get_registry

        get_registry().histogram("trino_tpu_ingest_decode_ms").observe(ms)
        return out

    def __iter__(self):
        if not self._enabled:
            for item in self._items:
                yield self._decode(item)
            return
        import queue

        q: "queue.Queue" = queue.Queue(maxsize=1)
        stop = threading.Event()

        def worker():
            try:
                for item in self._items:
                    if stop.is_set():
                        break  # consumer bailed (limit): skip the tail
                    q.put(("ok", self._decode(item)))
            except BaseException as e:  # noqa: BLE001 — re-raised by consumer
                q.put(("err", e))
            finally:
                q.put((None, self._SENTINEL))

        t = threading.Thread(
            target=worker, name="tt-ingest-decode", daemon=True
        )
        t.start()
        try:
            while True:
                kind, payload = q.get()
                if payload is self._SENTINEL:
                    break
                if kind == "err":
                    raise payload
                yield payload
        finally:
            # unblock the producer if the consumer stops early (limit hint)
            stop.set()
            while t.is_alive():
                try:
                    q.get_nowait()
                except queue.Empty:
                    pass
                t.join(timeout=0.05)


# === device-resident table cache ============================================


def hbm_headroom_ok(
    nbytes: int, peak_hbm_hint: int = 0, fraction: float = 0.9
) -> bool:
    """Admission check against real device memory: would pinning
    ``nbytes`` more HBM (on top of current use plus the profiler's peak
    program footprint) exceed ``fraction`` of the device limit? Backends
    without memory_stats (CPU meshes) admit — the byte budget still
    bounds the cache."""
    try:
        ms = jax.local_devices()[0].memory_stats() or {}
        limit = int(ms.get("bytes_limit") or 0)
        in_use = int(ms.get("bytes_in_use") or 0)
        if limit:
            return in_use + nbytes + peak_hbm_hint <= fraction * limit
    except Exception:  # noqa: BLE001 — accounting must never fail a query
        pass
    return True


def device_hbm_limit() -> int:
    """The device's reported HBM byte limit, or 0 when the backend has no
    memory accounting (CPU meshes) — callers treat 0 as "gate inert"."""
    try:
        ms = jax.local_devices()[0].memory_stats() or {}
        return int(ms.get("bytes_limit") or 0)
    except Exception:  # noqa: BLE001
        return 0


def splits_fingerprint(splits: Sequence) -> str:
    """Stable identity of a split list. File-backed connectors encode
    (path, chunk) pairs in split info, so INSERT-appended part files
    change the fingerprint and naturally invalidate cached tables."""
    blob = repr([(s.index, s.total, s.info) for s in splits])
    return hashlib.sha1(blob.encode()).hexdigest()[:16]


def parts_delta(old_parts, new_parts):
    """Classify a part-version transition (pairs from connector
    ``data_versions()``): ``("same", [])`` when identical,
    ``("append", new_ids)`` when every old ``(id, token)`` pair survives
    untouched and only new ids were added, else ``("changed", [])``.
    Drives the result cache's maintain-vs-invalidate decision; anything
    ambiguous (duplicate ids, removed or re-tokened parts) is "changed"."""
    old = dict(old_parts)
    new = dict(new_parts)
    if len(old) != len(old_parts) or len(new) != len(new_parts):
        return "changed", []
    if old == new:
        return "same", []
    appended = [pid for pid, _ in new_parts if pid not in old]
    if not appended or len(new) != len(old) + len(appended):
        return "changed", []
    for pid, tok in old_parts:
        if new.get(pid) != tok:
            return "changed", []
    return "append", appended


class DeviceTableCache:
    """Byte-budget LRU of HBM-resident scanned tables.

    Keys carry the catalog's data version and the split-list fingerprint,
    so mutation (memory-connector ``_version`` bump, part-file append)
    misses naturally instead of serving stale rows. Admission consults
    :func:`hbm_headroom_ok` with the device profiler's peak-HBM hint so a
    cached table cannot crowd out the programs that read it.
    """

    def __init__(self):
        self._entries: "OrderedDict[tuple, tuple[Batch, int]]" = OrderedDict()
        self._lock = threading.Lock()
        self.total_bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.rejections = 0

    def lookup(self, key: tuple) -> Optional[Batch]:
        from trino_tpu.obs.metrics import get_registry

        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                self.misses += 1
                get_registry().counter(
                    "trino_tpu_table_cache_misses_total"
                ).inc()
                return None
            self._entries.move_to_end(key)
            self.hits += 1
        get_registry().counter("trino_tpu_table_cache_hits_total").inc()
        return ent[0]

    def admit(
        self,
        key: tuple,
        batch: Batch,
        nbytes: int,
        max_bytes: int,
        peak_hbm_hint: int = 0,
    ) -> bool:
        if nbytes > max_bytes or not hbm_headroom_ok(nbytes, peak_hbm_hint):
            with self._lock:
                self.rejections += 1
            return False
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self.total_bytes -= old[1]
            while self._entries and self.total_bytes + nbytes > max_bytes:
                _, (_b, nb) = self._entries.popitem(last=False)
                self.total_bytes -= nb
                self.evictions += 1
            self._entries[key] = (batch, nbytes)
            self.total_bytes += nbytes
        return True

    def invalidate(self, catalog: Optional[str] = None) -> int:
        """Drop entries (all, or one catalog's). Version/fingerprint keys
        already make stale entries unreachable; this frees their HBM."""
        with self._lock:
            if catalog is None:
                dropped = len(self._entries)
                self._entries.clear()
                self.total_bytes = 0
                return dropped
            doomed = [k for k in self._entries if k[0] == catalog]
            for k in doomed:
                _b, nb = self._entries.pop(k)
                self.total_bytes -= nb
            return len(doomed)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "bytes": self.total_bytes,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "rejections": self.rejections,
            }


def table_cache_key(
    catalog: str,
    schema: str,
    table: str,
    version: Any,
    column_names: Iterable[str],
    splits: Sequence,
    placement,
) -> tuple:
    """``placement``: where the batch lives and in what shape: the mesh a
    sharded scan spreads it over (capacity-padded, with a selection), or
    the local executor's one device (the table's rows as they are). The
    two never serve each other, on a one-device mesh either."""
    if isinstance(placement, Mesh):
        mesh_fp = ("mesh", *(str(d) for d in placement.devices.flat))
    else:
        mesh_fp = ("local", str(placement))
    return (
        catalog,
        schema,
        table,
        version,
        tuple(column_names),
        splits_fingerprint(splits),
        mesh_fp,
    )
