"""Dense equi-join tier: device-resident open-addressing build table.

The sort tier (``ops/join.py``) pays ``lax.sort``s on every join; this
tier replaces them with a static-shape open-addressing table — the TPU
translation of Trino's ``PagesHash`` linear-probe table — built and
probed with fully vectorized rounds. On the chip that trade loses: the
rounds are random gathers over every probe row and output slot, and
one TPU v5 lite read sort-merge at 656-782 ms where this tier took
2.9-6.2 s (``exec/fragments.py::_join_strategy``, PR 36), so ``auto``
answers ``sort`` and this tier runs only under ``join_strategy=dense``
or ``matmul``:

1. Each build row proposes itself for the slots ``base+0 .. base+W-1``
   (``W = PROBE_WINDOW``), one displacement per round.  A round is one
   masked ``scatter-min`` of row ids: vacant slots keep the smallest
   proposing row id, occupied slots are untouched (any occupant id is
   smaller than the ``EMPTY`` sentinel).  Rows whose id appears in the
   table after a round stop proposing.
2. Probing gathers the same W slots per probe row and filters on build
   hash equality — two static W-round passes produce exactly the
   ``probe_join`` contract ``(probe_pos, build_pos, out_sel, total,
   overflow)``, so ``verify_equal`` and every downstream consumer are
   shared with the sort tier unchanged.
3. Rows that fail to place within W rounds raise the table-overflow
   flag; the executor's retry ladder re-hashes the whole build side at
   doubled capacity (``densejoin@…`` capacity sites) instead of
   dropping the fragment to the interpreter's partitioned spill — the
   graceful-overflow contract.  Duplicate-key chains longer than W can
   never place regardless of capacity (same key ⇒ same probe sequence);
   the executor demotes such a site back to the sort strategy after a
   few fruitless growths (see ``_Caps.demoted``).

Ordering guarantee (bit-identity with the sort tier after row sorting):
among build rows with equal hash, round r of the min-id scatter places
the r-th smallest unplaced row id, so matches of one probe row emit in
ascending build-row order — the same set the sorted tier emits, and the
exactness pass (``verify_equal``) ANDs out hash collisions identically.

The ``matmul`` tier bins instead of hashing: when the build key domain
is small and dense, ``slot_base_binned`` addresses
the table by ``key - kmin`` directly (identity binning == perfect
hashing — zero probe collisions when the domain fits the capacity).
The per-probe match-count contraction ``counts = onehot(bins) @ hist``
is MXU-shaped; ``matmul_join_counts`` computes it as a real chunked
``jnp.dot`` for join-project shapes, while the traced tier uses
the gather lowering of the same contraction (no n×C one-hot resident).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from trino_tpu.ops.join import MISSING, probe_join, slot_owner

# vacant-slot sentinel: int32 max, deliberately equal to join.MISSING —
# row ids are always < capacity < 2^31 so no live entry collides with it
EMPTY = jnp.iinfo(jnp.int32).max

# static displacement window: max open-addressing chain per slot base.
# Capacity growth thins hash clusters past it; duplicate-key chains
# longer than this demote the site to the sort tier (see module doc).
PROBE_WINDOW = 16


def slot_base_hash(key_hash: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """Dense tier: table slot base from the mix64 key hash."""
    return (
        key_hash.astype(jnp.uint64) & jnp.uint64(capacity - 1)
    ).astype(jnp.int32)


def slot_base_binned(
    key: jnp.ndarray, kmin: jnp.ndarray, capacity: int
) -> jnp.ndarray:
    """Matmul tier: identity binning ``key - kmin`` onto the table —
    collision-free (perfect hashing) while the key domain fits the
    capacity; wider domains wrap and degrade to ordinary probing."""
    return (
        (key.astype(jnp.int64) - kmin).astype(jnp.uint64)
        & jnp.uint64(capacity - 1)
    ).astype(jnp.int32)


def build_table(
    slot_base: jnp.ndarray,
    valid: jnp.ndarray,
    sel: jnp.ndarray,
    capacity: int,
    window: int = PROBE_WINDOW,
):
    """Insert build rows into an open-addressing table of row ids.

    Returns ``(table int32[capacity], overflow bool)`` — ``overflow``
    set when any live row failed to place within ``window`` rounds (the
    executor re-hashes at doubled capacity).
    """
    n = slot_base.shape[0]
    window = min(window, capacity)
    use = valid & sel
    ids = jnp.arange(n, dtype=jnp.int32)
    mask = jnp.int32(capacity - 1)
    table0 = jnp.full((capacity,), EMPTY, dtype=jnp.int32)

    def round_body(d, st):
        table, placed = st
        prop = (slot_base + d) & mask
        vacant = table[prop] == EMPTY
        cand = jnp.where(~placed & vacant, ids, EMPTY)
        table = table.at[prop].min(cand)
        placed = placed | (table[prop] == ids)
        return table, placed

    table, placed = jax.lax.fori_loop(
        0, window, round_body, (table0, ~use)
    )
    return table, jnp.any(~placed)


def probe_table(
    table: jnp.ndarray,
    build_hash: jnp.ndarray,
    probe_base: jnp.ndarray,
    probe_hash: jnp.ndarray,
    probe_valid: jnp.ndarray,
    probe_sel: jnp.ndarray,
    out_capacity: int,
    join_type: str = "inner",
    window: int = PROBE_WINDOW,
):
    """Expand probe × table matches into fixed-capacity gather indices.

    Same contract as ``join.probe_join``: ``(probe_pos, build_pos,
    out_sel, total, overflow)`` with ``build_pos == MISSING`` for outer
    rows; the caller runs ``verify_equal`` for hash-collision exactness.
    """
    capacity = table.shape[0]
    window = min(window, capacity)
    use = probe_valid & probe_sel
    if probe_hash.shape[0] == 0 or build_hash.shape[0] == 0:
        # statically empty side: defer to the sort tier's guard logic,
        # which already covers LEFT-over-empty-build row emission
        empty_keys = jnp.zeros((0,), dtype=jnp.int64)
        empty_idx = jnp.zeros((0,), dtype=jnp.int32)
        return probe_join(
            empty_keys, empty_idx, jnp.int32(0), probe_hash,
            probe_valid, probe_sel, out_capacity, join_type,
        )
    nb = build_hash.shape[0]
    mask = jnp.int32(capacity - 1)

    def count_body(d, counts):
        e = table[(probe_base + d) & mask]
        eh = build_hash[jnp.clip(e, 0, nb - 1)]
        m = (e != EMPTY) & (eh == probe_hash) & use
        return counts + m.astype(jnp.int32)

    counts = jax.lax.fori_loop(
        0, window, count_body,
        jnp.zeros(probe_hash.shape[0], dtype=jnp.int32),
    )
    if join_type == "left":
        emit = jnp.where(probe_sel, jnp.maximum(counts, 1), 0)
    elif join_type == "inner":
        emit = counts
    else:
        raise NotImplementedError(join_type)
    from trino_tpu.ops.aggregation import _prefix_sum

    offsets = _prefix_sum(emit) - emit  # exclusive prefix
    total = offsets[-1] + emit[-1]
    overflow = total > out_capacity

    t = jnp.arange(out_capacity, dtype=emit.dtype)
    probe_pos = slot_owner(offsets, emit, out_capacity)
    j = t - offsets[probe_pos]

    # second W-round pass: per output slot, the j-th matching window
    # entry of its owning probe row ((out_capacity,)-sized arrays only —
    # the (n, W) match matrix is never materialized)
    o_base = probe_base[probe_pos]
    o_hash = probe_hash[probe_pos]
    o_use = use[probe_pos]

    def pick_body(d, st):
        bpos, r = st
        e = table[(o_base + d) & mask]
        eh = build_hash[jnp.clip(e, 0, nb - 1)]
        m = (e != EMPTY) & (eh == o_hash) & o_use
        bpos = jnp.where(m & (r == j), e, bpos)
        return bpos, r + m.astype(j.dtype)

    build_pos, _ = jax.lax.fori_loop(
        0, window, pick_body,
        (
            jnp.full(out_capacity, MISSING, dtype=jnp.int32),
            jnp.zeros(out_capacity, dtype=j.dtype),
        ),
    )
    out_sel = t < total
    return probe_pos, build_pos, out_sel, total, overflow


def matmul_join_counts(
    probe_bins: jnp.ndarray,
    build_bins: jnp.ndarray,
    probe_use: jnp.ndarray,
    build_use: jnp.ndarray,
    domain: int,
    chunk: int = 2048,
):
    """Per-probe match counts as a real MXU contraction.

    ``counts[i] = Σ_g 1[probe_bin_i = g] · hist_g`` — the join-as-matmul
    count kernel for join-project shapes, computed as chunked
    ``onehot @ hist`` dots (a chunk of probe rows against the build
    histogram at a time).  Equal to the gather lowering ``hist[probe_bins]`` (asserted
    by the unit tests); the traced tier uses the gather form to avoid a
    resident n×domain one-hot.
    """
    hist = (
        jnp.zeros((domain,), jnp.float32)
        .at[jnp.where(build_use, build_bins, domain - 1)]
        .add(build_use.astype(jnp.float32))
    )
    n = probe_bins.shape[0]
    pad = (-n) % chunk
    bins_p = jnp.pad(probe_bins, (0, pad))
    use_p = jnp.pad(probe_use, (0, pad))
    nch = bins_p.shape[0] // chunk
    g = jnp.arange(domain, dtype=jnp.int32)

    def chunk_body(c, out):
        b = jax.lax.dynamic_slice(bins_p, (c * chunk,), (chunk,))
        u = jax.lax.dynamic_slice(use_p, (c * chunk,), (chunk,))
        onehot = ((b[:, None] == g[None, :]) & u[:, None]).astype(
            jnp.float32
        )
        cc = jnp.dot(onehot, hist, preferred_element_type=jnp.float32)
        return jax.lax.dynamic_update_slice(out, cc, (c * chunk,))

    out = jax.lax.fori_loop(
        0, nch, chunk_body, jnp.zeros(bins_p.shape[0], jnp.float32)
    )
    return out[:n].astype(jnp.int32)
