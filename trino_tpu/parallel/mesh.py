"""Device mesh + sharded batch construction.

The engine uses a 1-D mesh axis ``"shards"`` for inter-chip partitioned
parallelism (Trino's FIXED_HASH_DISTRIBUTION analog). Batches are global
``jax.Array``s sharded on the row axis; padding makes per-shard row counts
equal (selection masks carry validity).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from trino_tpu.columnar import Batch, Column

AXIS = "shards"


def smap(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` over ``mesh`` without the replication check."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    devices = jax.devices()
    n = n_devices or len(devices)
    if n > len(devices):
        raise ValueError(f"requested {n} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n]), (AXIS,))


def make_local_mesh() -> Mesh:
    """Mesh over this process's devices only. Inside a jax.distributed
    group, per-task execution must not span processes (its collectives
    would wait on programs the other processes never launch)."""
    return Mesh(np.asarray(jax.local_devices()), (AXIS,))


def row_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def prepare_shards(mesh: Mesh, parts: Sequence[Batch]):
    """Host-side shard assembly: pad per-device parts to one capacity,
    build selection masks, unify dictionaries, remap codes.

    Shared by :func:`shard_batch` (per-column device_put) and the
    coalesced-arena ingest path (``trino_tpu/ingest.py``), so both
    produce bit-identical device batches. Returns
    ``(cap, sels, columns)`` where ``sels`` is None or per-device bool
    arrays and ``columns`` is ``[(type, dictionary, datas, valids)]``
    with ``valids`` None when every part is full-capacity all-valid.
    """
    n = mesh.devices.size
    assert len(parts) == n, f"need {n} parts, got {len(parts)}"
    cap = max(1, max(p.capacity for p in parts))
    width = parts[0].width
    # full parts with no selection need no mask — skipping it avoids the
    # host->device mask bytes entirely for full streaming chunks
    if all(p.sel is None and p.num_rows == cap == p.capacity for p in parts):
        sels = None
    else:
        sels = []
        for p in parts:
            mask = np.zeros(cap, dtype=np.bool_)
            mask[: p.num_rows] = True
            if p.sel is not None:
                local = np.zeros(cap, dtype=np.bool_)
                local[: p.capacity] = np.asarray(p.sel)
                mask &= local
            sels.append(mask)
    dictionaries = _unify_part_dictionaries(parts)
    columns = []
    for j in range(width):
        t = parts[0].columns[j].type  # same schema across parts
        datas, valids = [], []
        no_valid = all(
            p.columns[j].valid is None and p.columns[j].capacity == cap
            for p in parts
        )
        for pi, p in enumerate(parts):
            c = p.columns[j]
            data = np.asarray(c.data)
            if dictionaries[j] is not None and c.dictionary is not None:
                remap = dictionaries[j][1][pi]
                if remap is not None:
                    data = np.where(data >= 0, remap[np.maximum(data, 0)], -1).astype(
                        np.int32
                    )
            if data.shape[0] < cap:
                # wide DECIMAL columns carry (N, 2) hi/lo lanes — pad rows,
                # keep trailing dims
                pad_shape = (cap - data.shape[0],) + data.shape[1:]
                data = np.concatenate(
                    [data, np.zeros(pad_shape, dtype=data.dtype)]
                )
            datas.append(data)
            if not no_valid:
                valid = np.ones(cap, dtype=np.bool_)
                if c.valid is not None:
                    v = np.asarray(c.valid)
                    valid[: v.shape[0]] = v
                    valid[v.shape[0]:] = False
                valids.append(valid)
        d = dictionaries[j][0] if dictionaries[j] is not None else None
        columns.append((t, d, datas, None if no_valid else valids))
    return cap, sels, columns


def shard_batch(mesh: Mesh, parts: Sequence[Batch]) -> Batch:
    """Assemble per-shard host batches into one globally-sharded Batch.

    ``parts`` has one Batch per mesh device (same schema). Rows are padded
    to the max per-shard capacity; the result's ``sel`` masks padding.
    """
    n = mesh.devices.size
    cap, sels, columns = prepare_shards(mesh, parts)
    sharding = row_sharding(mesh)
    sel = None if sels is None else _global(mesh, sharding, sels)
    cols: list[Column] = []
    for t, d, datas, valids in columns:
        data_g = _global(mesh, sharding, datas)
        valid_g = None if valids is None else _global(mesh, sharding, valids)
        cols.append(Column(t, data_g, valid_g, d))
    return Batch(cols, cap * n, sel)


def _unify_part_dictionaries(parts: Sequence[Batch]):
    """Per column: merge per-part dictionaries into one; remap tables."""
    out = []
    width = parts[0].width
    for j in range(width):
        dicts = [p.columns[j].dictionary for p in parts]
        if all(d is None for d in dicts):
            out.append(None)
            continue
        base = None
        remaps = []
        for d in dicts:
            if d is None:
                remaps.append(None)
                continue
            if base is None:
                base = d
                remaps.append(None)
            elif d is base:
                remaps.append(None)
            else:
                base, remap = base.merged(d)
                remaps.append(remap)
        out.append((base, remaps))
    return out


def _global(mesh: Mesh, sharding: NamedSharding, arrs: list[np.ndarray]) -> jax.Array:
    """Build a global sharded array from per-device host shards.

    Multi-host: each process device_puts only the shards of its own
    addressable devices; the global shape covers all of them (every
    process computes identical ``arrs``, see SpmdRunner)."""
    me = jax.process_index()
    singles = [
        jax.device_put(a, d)
        for a, d in zip(arrs, list(mesh.devices.flat))
        if d.process_index == me
    ]
    shape = (sum(a.shape[0] for a in arrs),) + arrs[0].shape[1:]
    return jax.make_array_from_single_device_arrays(shape, sharding, singles)
