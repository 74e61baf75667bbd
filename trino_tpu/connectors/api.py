"""Connector SPI.

Reference interfaces: ``spi/connector/Connector.java:28-90`` (metadata,
split manager, page source provider), ``spi/connector/ConnectorSplitManager.java:23``,
``spi/connector/ConnectorPageSource.java:47``.

TPU-first simplification: a connector reads a (table, split, columns)
triple into one host :class:`Batch`; the executor moves it to device and
pads. Splits are the unit of scan parallelism (reference §2.6 item 3).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

from trino_tpu import types as T
from trino_tpu.columnar import Batch
from trino_tpu.predicate import TupleDomain


@dataclasses.dataclass(frozen=True)
class ColumnStats:
    """Per-column statistics (reference: ``spi/statistics/ColumnStatistics``)."""

    distinct_count: Optional[float] = None
    null_fraction: Optional[float] = None
    min_value: Any = None
    max_value: Any = None


@dataclasses.dataclass(frozen=True)
class TableStats:
    """Reference: ``spi/statistics/TableStatistics`` — drives the CBO."""

    row_count: Optional[float] = None
    columns: dict[str, ColumnStats] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class ColumnSchema:
    name: str
    type: T.SqlType


@dataclasses.dataclass(frozen=True)
class TableSchema:
    name: str
    columns: tuple[ColumnSchema, ...]

    def column_names(self) -> list[str]:
        return [c.name for c in self.columns]

    def column(self, name: str) -> ColumnSchema | None:
        for c in self.columns:
            if c.name == name:
                return c
        return None


@dataclasses.dataclass(frozen=True)
class Split:
    """Opaque unit of scan work (reference: ``spi/connector/ConnectorSplit``)."""

    table: str
    index: int
    total: int
    info: Any = None


class Connector:
    name: str = "connector"
    # True when concurrent inserts from several NODES are safe (shared
    # storage): enables scaled-writer dispatch (ScaledWriterScheduler)
    supports_distributed_writes: bool = False
    # False for connectors whose reads reflect live process state rather
    # than versioned table data (system tables): the coordinator result
    # cache (trino_tpu/cache) refuses to cache queries touching them
    supports_result_caching: bool = True

    # --- metadata --------------------------------------------------------
    def list_schemas(self) -> list[str]:
        raise NotImplementedError

    def list_tables(self, schema: str) -> list[str]:
        raise NotImplementedError

    def get_table(self, schema: str, table: str) -> Optional[TableSchema]:
        raise NotImplementedError

    # --- optimizer pushdown hooks ----------------------------------------
    # Reference: ``spi/connector/ConnectorMetadata.java`` applyLimit
    # (:1064), applyTopN (:1090), applyAggregation (:932); applyFilter's
    # analog is the constraint/prune_splits path below.

    def apply_limit(self, schema: str, table: str, count: int) -> bool:
        """True if the connector will honor a read-at-most-``count`` hint
        on its scans (guarantee-free: the engine still enforces LIMIT)."""
        return False

    def apply_topn(
        self, schema: str, table: str, keys: list, count: int
    ) -> bool:
        """True ONLY if this connector's ``get_splits_with_hints`` orders
        scans by ``keys`` ([(column, ascending)]) well enough that the
        first ``count`` rows read contain the true top-N — the engine
        stops reading splits at the limit when this returns True (the
        TopN node above still sorts/cuts what was read)."""
        return False

    def get_splits_with_hints(
        self,
        schema: str,
        table: str,
        target_splits: int,
        constraint=None,
        limit: Optional[int] = None,
        topn: Optional[list] = None,
    ) -> list["Split"]:
        """Split enumeration with the optimizer's pushed limit/topn hints.

        Default ignores the hints (safe: the engine only trusts them when
        the connector's apply_limit/apply_topn accepted). Connectors that
        accept override this to cap or order their splits."""
        return self.get_splits(schema, table, target_splits, constraint)

    def apply_aggregation_count(self, schema: str, table: str):
        """Exact total row count, or None when the connector cannot answer
        without scanning. ONLY return a value that is exactly correct —
        the optimizer replaces a global count(*) with it."""
        return None

    # --- splits + data ---------------------------------------------------
    def get_splits(
        self,
        schema: str,
        table: str,
        target_splits: int,
        constraint: Optional[TupleDomain] = None,
    ) -> list[Split]:
        return self.prune_splits(schema, table, [Split(table, 0, 1)], constraint)

    def prune_splits(
        self,
        schema: str,
        table: str,
        splits: list[Split],
        constraint: Optional[TupleDomain],
    ) -> list[Split]:
        """Drop splits whose min/max stats cannot satisfy ``constraint``
        (reference: stripe/row-group pruning,
        ``lib/trino-orc/.../TupleDomainOrcPredicate.java:74,92``)."""
        if constraint is None or constraint.is_all():
            return splits
        if constraint.is_none():
            return []
        out = []
        for s in splits:
            stats = self.split_stats(schema, table, s)
            if stats is None or constraint.overlaps_stats(stats):
                out.append(s)
        return out

    def split_stats(
        self, schema: str, table: str, split: Split
    ) -> Optional[dict[str, tuple[Any, Any, bool]]]:
        """column -> (min, max, has_null) for this split, or None if unknown."""
        return None

    def read_split(
        self, schema: str, table: str, columns: Sequence[str], split: Split
    ) -> Batch:
        raise NotImplementedError

    def data_version(self, schema: str, table: str) -> Any:
        """Monotone token that changes whenever the table's data changes;
        keys the device table cache (trino_tpu/ingest.py), so mutation
        invalidates cached HBM columns by making their keys unreachable.
        Mutable connectors bump ``_version``; file-backed connectors
        override with a (file list, mtime) digest."""
        return getattr(self, "_version", 0)

    def data_versions(self, schema: str, table: str) -> Optional[list]:
        """Part-level version enumeration: ordered ``(part_id, token)``
        pairs, one per immutable storage part, or None when the connector
        cannot enumerate parts (the result cache then falls back to the
        coarse :meth:`data_version` token, where ANY change invalidates).

        Contract: a part's token never changes while its id is live; an
        APPEND adds new ids and leaves every old pair intact; any other
        mutation (rewrite, delete, truncate) removes or changes at least
        one old pair. This is what lets the result cache distinguish
        "maintain incrementally over the new parts" from "invalidate"."""
        return None

    def splits_for_parts(self, schema: str, table, part_ids) -> list["Split"]:
        """Splits covering exactly the parts named by ``part_ids`` (ids
        from :meth:`data_versions`) — the delta scan for incremental
        aggregate maintenance. Required when data_versions is implemented."""
        raise NotImplementedError(f"{self.name}: part-level splits not supported")

    # --- optional stats (drives join distribution / sizing) -------------
    def estimate_rows(self, schema: str, table: str) -> Optional[int]:
        return None

    def table_stats(self, schema: str, table: str) -> Optional[TableStats]:
        """Reference: ``ConnectorMetadata.getTableStatistics`` — CBO input."""
        rows = self.estimate_rows(schema, table)
        return TableStats(row_count=rows) if rows is not None else None

    # --- optional write path --------------------------------------------
    def create_table(self, schema: str, table: str, schema_def: TableSchema) -> None:
        raise NotImplementedError(f"{self.name}: CREATE TABLE not supported")

    def insert(self, schema: str, table: str, batch: Batch) -> int:
        raise NotImplementedError(f"{self.name}: INSERT not supported")

    def drop_table(self, schema: str, table: str) -> None:
        raise NotImplementedError(f"{self.name}: DROP TABLE not supported")


class CatalogManager:
    """Catalog name -> connector instance (reference:
    ``metadata/MetadataManager.java:184`` catalog routing)."""

    def __init__(self):
        self._catalogs: dict[str, Connector] = {}

    def register(self, name: str, connector: Connector) -> None:
        self._catalogs[name] = connector

    def get(self, name: str) -> Connector:
        if name not in self._catalogs:
            raise KeyError(f"catalog not found: {name}")
        return self._catalogs[name]

    def names(self) -> list[str]:
        return sorted(self._catalogs)


# staging quantum: slabs are padded to a multiple of this row count, so
# any power-of-two chunk size up to the quantum can dynamic_slice them —
# one staged copy serves every chunk-size setting
SLAB_PAD_QUANTUM = 1 << 22


def slab_padded_rows(rows: int, cap: int) -> int:
    """Rows a staged slab actually allocates (quantum padding)."""
    quantum = max(cap, SLAB_PAD_QUANTUM)
    return ((rows + quantum - 1) // quantum) * quantum


def slab_shard_rows(rows: int, shards: int):
    """Rows each shard of a slab staged over ``shards`` devices holds: equal
    runs of the table's rows in order, the last ones short (int64, one a
    shard; the first is the largest)."""
    import numpy as np

    run = (rows + shards - 1) // shards
    return np.clip(rows - run * np.arange(shards, dtype=np.int64), 0, run)


def slab_bytes_estimate(
    types: Sequence, rows: int, cap: int, shards: int = 1
) -> int:
    """Bytes needed to stage ``rows`` of these column types in HBM, on the
    fullest of ``shards`` devices — measured at the PADDED allocation (wide
    DECIMALs store (n, 2) int64 lanes; +1 byte/row validity), so admission
    bounds reflect reality."""
    import numpy as np

    padded = slab_padded_rows(int(slab_shard_rows(rows, shards)[0]), cap)
    nbytes = 0
    for t in types:
        width = np.dtype(t.storage_dtype).itemsize
        if getattr(t, "wide", False):
            width *= 2
        nbytes += padded * (width + 1)
    return nbytes


def stage_device_slab(
    host_batches: Sequence[Batch], cap: int, stats: Optional[dict] = None,
    mesh=None,
):
    """Stage host batches into device HBM as ONE slab padded to a
    multiple of ``cap`` rows (so a compiled streaming step can
    ``dynamic_slice`` any chunk without clamping). Per-part dictionaries
    are unified during the concat. Returns (slab_batch, num_rows). The
    bytes put on the device count into ``stats["h2d_bytes"]`` (the
    query's ``ingestStats``), as a scan's do.

    With a ``mesh`` of several devices the slab is row-sharded over it:
    shard ``s`` holds its run of the rows (``slab_shard_rows``) from its
    first row on and is padded like a slab of its own, so every device
    steps through its rows with the offsets of the others.

    Shared by connectors whose data can live device-resident (memory
    pages, generated tpch splits): HBM plays the role the reference's
    worker heap plays for pinned pages."""
    import jax
    import numpy as np

    from trino_tpu.columnar import Column, concat_batches

    host = concat_batches(list(host_batches))
    total_rows = host.num_rows
    shards = 1 if mesh is None else int(mesh.devices.size)
    runs = slab_shard_rows(total_rows, shards)
    held = slab_padded_rows(int(runs[0]), cap)  # rows a shard allocates
    sharding = None
    if shards > 1:
        from trino_tpu.parallel.mesh import row_sharding

        sharding = row_sharding(mesh)

    def padded(a):
        a = np.asarray(a)[:total_rows]
        out = np.zeros((shards * held,) + a.shape[1:], dtype=a.dtype)
        for s, run in enumerate(runs):
            lo = s * int(runs[0])
            out[s * held : s * held + run] = a[lo : lo + run]
        return out

    cols = []
    nbytes = 0
    for c in host.columns:
        data = padded(c.data)
        valid = None if c.valid is None else padded(c.valid)
        dev = jax.device_put(data, sharding)
        dvalid = None if valid is None else jax.device_put(valid, sharding)
        nbytes += data.nbytes + (0 if valid is None else valid.nbytes)
        cols.append(Column(c.type, dev, dvalid, c.dictionary))
    from trino_tpu.obs.metrics import get_registry

    get_registry().counter("trino_tpu_ingest_h2d_bytes_total").inc(nbytes)
    if stats is not None:
        stats["h2d_bytes"] = stats.get("h2d_bytes", 0) + nbytes
    return Batch(cols, shards * held), total_rows


def batch_column_stats(columns, batch) -> dict:
    """Per-column (min, max, has_null) for a compacted batch — shared by
    stats-collecting connectors (the stripe-footer computation)."""
    out: dict[str, tuple] = {}
    for cs, col in zip(columns, batch.columns):
        if T.is_string(cs.type) or batch.num_rows == 0:
            continue
        data, valid = col.to_numpy()
        data = data[: batch.num_rows]
        valid = valid[: batch.num_rows]
        live = data[valid]
        has_null = bool((~valid).any())
        if live.size:
            out[cs.name] = (live.min().item(), live.max().item(), has_null)
        else:
            out[cs.name] = (None, None, has_null)
    return out


def register_catalog_spec(manager: CatalogManager, spec: str) -> None:
    """Register a connector from a ``name=kind[:arg]`` spec string.

    The ``etc/catalog/*.properties`` analog (reference:
    ``server/PluginManager.java`` / ``connector/ConnectorManager.java``):
    servers take ``--catalog data=parquet:/shared/path`` so every node of
    a cluster mounts the same catalogs at boot.
    """
    name, _, rest = spec.partition("=")
    kind, _, arg = rest.partition(":")
    name, kind = name.strip(), kind.strip()
    if kind == "memory":
        from trino_tpu.connectors.memory import MemoryConnector

        manager.register(name, MemoryConnector())
    elif kind == "blackhole":
        from trino_tpu.connectors.blackhole import BlackHoleConnector

        manager.register(name, BlackHoleConnector())
    elif kind == "file":
        from trino_tpu.connectors.file import FileConnector

        manager.register(name, FileConnector(arg))
    elif kind == "parquet":
        from trino_tpu.connectors.parquet import ParquetConnector

        manager.register(name, ParquetConnector(arg))
    elif kind == "orc":
        from trino_tpu.connectors.orc import OrcConnector

        manager.register(name, OrcConnector(arg))
    elif kind == "tpch":
        from trino_tpu.connectors.tpch import TpchConnector

        manager.register(name, TpchConnector())
    elif kind == "h2o":
        from trino_tpu.connectors.h2o import H2oConnector

        manager.register(name, H2oConnector())
    else:
        raise ValueError(f"unknown catalog kind in spec: {spec!r}")
