"""Equi-join kernels: sort build side + sort-merge probe.

Reference: Trino's hash join — ``operator/HashBuilderOperator.java:51``,
``operator/PagesHash.java:34`` (linear-probe table over synthetic addresses),
``operator/LookupJoinOperator.java:71``.

TPU-first design: no pointer-chasing hash table. Instead:
1. Hash each side's key columns into one int64 key (mix64 per column,
   combined), with NULL keys mapped to a never-matching sentinel.
2. Sort the build side by hashed key (``lax.sort`` — fast bitonic on TPU).
3. Rank the probe keys among the build keys by sort-merge (``merge_rank``):
   one sort of both sides' keys together, a prefix count of the build
   elements and a reverse running minimum over the runs of equal keys give
   every probe row its match range ``[lo, hi)``; a second sort, on
   position, brings the ranges back to probe order. A binary search
   (``jnp.searchsorted``) is ~22 dependent random gathers a row on the
   chip; a whole sort costs what 1-2 such rounds do.
4. Expand matches into a fixed output capacity via cumsum offsets: each
   emitting probe row scatters its number to its first output slot and a
   running maximum fills the slots between (``slot_owner``) — static
   shapes, one scatter and one gather or two a row. Where the build key is
   unique among the build's live rows, a probe row matches one build row at
   the most and there is nothing to expand (``lookup_join``): output row
   ``i`` is probe row ``i``, so the probe's columns pass through as they
   are and only the build's are gathered; a probe row whose hash matches
   two build rows raises the kernel's flag, and the caller asks again
   through the expansion.
5. Exactness: hashing may collide, so after expansion the caller re-checks
   the real key columns and ANDs mismatches out of the selection. This makes
   the kernel exact without needing perfect packing (Trino's 8-bit raw-hash
   prefilter + full key compare, taken to its vectorized conclusion).

Overflow: if total matches exceed capacity, the kernel reports it; the
executor retries with a larger bucket (shape-bucketed recompile).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from trino_tpu.columnar import bucket_capacity
from trino_tpu.ops.aggregation import _prefix_max, _prefix_sum

MISSING = jnp.iinfo(jnp.int32).max  # build position marking "no match" (left join)


def mix64(x: jnp.ndarray) -> jnp.ndarray:
    """splitmix64 finalizer — good avalanche, cheap on VPU."""
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> 27)) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> 31)
    return x


def hash_keys(keys, null_sentinel: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Combine key columns [(data, valid), ...] into (hash int64, all_valid)."""
    acc = jnp.zeros(keys[0][0].shape[0], dtype=jnp.uint64)
    all_valid = None
    for data, valid in keys:
        h = mix64(data.astype(jnp.int64))
        acc = mix64(acc ^ h)
        all_valid = valid if all_valid is None else (all_valid & valid)
    return acc.astype(jnp.int64), all_valid


def build_side(key_hash: jnp.ndarray, valid: jnp.ndarray, sel: jnp.ndarray):
    """Sort build rows by hashed key; invalid/unselected rows pushed to +inf.

    Returns (sorted_keys, sorted_row_indices, build_count).
    """
    n = key_hash.shape[0]
    use = valid & sel
    maxv = jnp.iinfo(jnp.int64).max
    keyed = jnp.where(use, key_hash, maxv)
    idx = jnp.arange(n, dtype=jnp.int32)
    # idx as a second sort KEY (not payload): deterministic tie order
    # without is_stable, which doubles XLA:TPU sort compile time
    sorted_keys, sorted_idx = jax.lax.sort(
        (keyed, idx), num_keys=2, is_stable=False
    )
    count = jnp.sum(use.astype(jnp.int32))
    return sorted_keys, sorted_idx, count


def unique_keys(keys, sel: jnp.ndarray) -> jnp.ndarray:
    """True where no two rows of ``sel`` whose key columns
    ``[(data, valid), ...]`` hold no NULL share a key hash. The sort-merge
    probe matches by hash, so then a probe row matches one such row at the
    most; distinct hashes are distinct keys (a collision only says False)."""
    key_hash, valid = hash_keys(keys)
    use = sel & valid
    maxv = jnp.iinfo(jnp.int64).max
    keyed = jax.lax.sort(jnp.where(use, key_hash, maxv))
    count = jnp.sum(use.astype(jnp.int32))
    # the used rows sort first: their neighbours below ``count``
    pos = jnp.arange(1, keyed.shape[0], dtype=jnp.int32)
    return ~jnp.any((keyed[1:] == keyed[:-1]) & (pos < count))


def unique_key_columns(columns, sel: jnp.ndarray) -> jnp.ndarray:
    """``unique_keys`` over key columns ``[(data, valid), ...]``, where a wide
    column's ``data`` (``[rows, 2]``) gives a key lane a half, each under the
    column's validity."""
    lanes = []
    for data, valid in columns:
        data = data if data.ndim == 1 else data.T
        lanes.extend((lane, valid) for lane in jnp.atleast_2d(data))
    return unique_keys(lanes, sel)


def merge_rank(sorted_build_keys: jnp.ndarray, keys: jnp.ndarray):
    """``searchsorted(sorted_build_keys, keys)`` for side "left" and
    "right" at once, as ``(lo, hi)`` int32, by sort-merge.

    One sort of the probe keys laid before the build keys, by (key,
    position): a probe then stands before the builds equal to it, so the
    builds before its place are ``lo`` and those up to the end of its run
    of equal keys are ``hi``. A second sort, on position, brings both back
    to probe order. The length is padded to ``bucket_capacity``, a power
    of two: the chip's compiler takes about 1.4x as long over a sort of an
    odd length (120 s against 83 s for the two sorts at 8.1 M rows)."""
    n_probe, n_build = keys.shape[0], sorted_build_keys.shape[0]
    n = bucket_capacity(n_probe + n_build)
    maxv = jnp.iinfo(jnp.int64).max
    merged = jnp.concatenate(
        [keys, sorted_build_keys,
         jnp.full(n - n_probe - n_build, maxv, dtype=jnp.int64)]
    )
    pos = jnp.arange(n, dtype=jnp.int32)
    # pos as a second sort KEY, as in build_side (no is_stable)
    skey, spos = jax.lax.sort((merged, pos), num_keys=2, is_stable=False)
    is_build = ((spos >= n_probe) & (spos < n_probe + n_build)).astype(jnp.int32)
    upto = _prefix_sum(is_build)
    run_end = jnp.concatenate([skey[1:] != skey[:-1], jnp.ones(1, jnp.bool_)])
    # upto at the end of each element's run: a reverse running minimum
    # over the run ends, as the running maximum of the negated reverse
    minv = jnp.iinfo(jnp.int32).min
    hi = -_prefix_max(jnp.where(run_end, -upto, minv)[::-1])[::-1]
    _, lo, hi = jax.lax.sort(
        (spos, upto - is_build, hi), num_keys=1, is_stable=False
    )
    return lo[:n_probe], hi[:n_probe]


def slot_owner(offsets: jnp.ndarray, emit: jnp.ndarray, out_capacity: int):
    """For each output slot ``t`` the row that owns it: the last row ``p``
    with ``emit[p] > 0`` and ``offsets[p] <= t`` (row 0 before the first).

    ``offsets`` is the exclusive prefix sum of ``emit``, so the emitting
    rows' offsets are distinct: each writes its number at its offset
    (those past ``out_capacity`` drop), and a running maximum fills the
    slots between."""
    rows = jnp.arange(emit.shape[0], dtype=jnp.int32)
    at = jnp.where(emit > 0, offsets, out_capacity)
    heads = jnp.zeros(out_capacity, dtype=jnp.int32).at[at].set(
        rows, mode="drop"
    )
    return _prefix_max(heads)


def match_ranges(sorted_build_keys, build_count, probe_hash, use):
    """Each probe row's range ``[lo, hi)`` of equal live build keys
    (``merge_rank``), empty where ``use`` is false."""
    maxv = jnp.iinfo(jnp.int64).max
    # never matches the build's sentinel maxv
    lo, hi = merge_rank(sorted_build_keys, jnp.where(use, probe_hash, maxv - 1))
    # build_count is a 64-bit sum: left so, every array below is 64-bit
    # too, and the chip gathers and scans those at half the rate
    hi = jnp.minimum(hi, build_count.astype(jnp.int32))
    return jnp.minimum(lo, hi), hi


@partial(jax.jit, static_argnames=("out_capacity", "join_type"))
def probe_join(
    sorted_build_keys: jnp.ndarray,
    sorted_build_idx: jnp.ndarray,
    build_count: jnp.ndarray,
    probe_hash: jnp.ndarray,
    probe_valid: jnp.ndarray,
    probe_sel: jnp.ndarray,
    out_capacity: int,
    join_type: str = "inner",
):
    """Expand probe x build matches into fixed-capacity gather indices.

    One jitted program for each (shapes, ``out_capacity``, ``join_type``).
    Returns (probe_pos, build_pos, out_sel, total, overflow):
      probe_pos/build_pos: (out_capacity,) int32 gather indices into the
        original (unsorted) batches; build_pos == MISSING for outer rows.
      out_sel: (out_capacity,) bool — which output slots are live.
      total: int64 scalar — true number of output rows.
      overflow: bool — total > out_capacity.
    """
    if join_type not in ("inner", "left"):
        raise NotImplementedError(join_type)
    n_probe, n_build = probe_hash.shape[0], sorted_build_idx.shape[0]
    if n_probe == 0:
        # statically empty probe: nothing to emit
        return (
            jnp.zeros(out_capacity, dtype=jnp.int32),
            jnp.full(out_capacity, MISSING, dtype=jnp.int32),
            jnp.zeros(out_capacity, dtype=jnp.bool_),
            jnp.int32(0),
            jnp.asarray(False),
        )
    use = probe_valid & probe_sel
    if n_build == 0:
        # statically empty build: no matches; LEFT still emits probe rows
        lo = counts = jnp.zeros(n_probe, dtype=jnp.int32)
    else:
        lo, hi = match_ranges(sorted_build_keys, build_count, probe_hash, use)
        counts = jnp.where(use, hi - lo, 0)
    if join_type == "left":
        emit = jnp.where(probe_sel, jnp.maximum(counts, 1), 0)
    else:
        emit = counts
    offsets = _prefix_sum(emit) - emit  # exclusive prefix
    # exact past 2^31, where the 32-bit offsets wrap: the caller then sees
    # the overflow and asks again
    total = jnp.sum(emit, dtype=jnp.int64)
    overflow = total > out_capacity

    t = jnp.arange(out_capacity, dtype=jnp.int32)
    probe_pos = slot_owner(offsets, emit, out_capacity)
    if n_build == 0:
        build_pos = jnp.full(out_capacity, MISSING, dtype=jnp.int32)
    else:
        # slot t of row p holds sorted build slot lo[p] + (t - offsets[p]):
        # one gather of lo - offsets, with "no match" as its least value
        unmatched = jnp.iinfo(jnp.int32).min
        shift = jnp.where(counts > 0, lo - offsets, unmatched)[probe_pos]
        build_pos = jnp.where(
            shift != unmatched,
            sorted_build_idx[jnp.clip(shift + t, 0, n_build - 1)],
            MISSING,
        ).astype(jnp.int32)
    out_sel = t < total
    return probe_pos, build_pos, out_sel, total, overflow


@partial(jax.jit, static_argnames=("join_type",))
def lookup_join(
    sorted_build_keys: jnp.ndarray,
    sorted_build_idx: jnp.ndarray,
    build_count: jnp.ndarray,
    probe_hash: jnp.ndarray,
    probe_valid: jnp.ndarray,
    probe_sel: jnp.ndarray,
    join_type: str = "inner",
):
    """``probe_join`` where no probe row matches two build rows: output row
    ``i`` is probe row ``i``, so there are no probe positions to return.

    Returns (build_pos, out_sel, dup):
      build_pos: (n_probe,) int32 gather indices into the original build;
        MISSING where the row's hash matched nothing.
      out_sel: (n_probe,) bool — the matched selected rows (INNER), every
        selected row (LEFT).
      dup: bool — some selected probe row matched two or more build rows,
        whose pairs this kernel cannot hold (the caller's overflow).
    """
    if join_type not in ("inner", "left"):
        raise NotImplementedError(join_type)
    n_probe, n_build = probe_hash.shape[0], sorted_build_idx.shape[0]
    use = probe_valid & probe_sel
    if n_probe == 0 or n_build == 0:
        # statically empty side: no matches; LEFT still keeps probe rows
        build_pos = jnp.full(n_probe, MISSING, dtype=jnp.int32)
        matched, dup = jnp.zeros(n_probe, dtype=jnp.bool_), jnp.asarray(False)
    else:
        lo, hi = match_ranges(sorted_build_keys, build_count, probe_hash, use)
        matched = use & (hi > lo)
        build_pos = jnp.where(
            matched, sorted_build_idx[jnp.minimum(lo, n_build - 1)], MISSING
        ).astype(jnp.int32)
        dup = jnp.any(use & (hi - lo > 1))
    out_sel = probe_sel if join_type == "left" else matched
    return build_pos, out_sel, dup


def verify_equal(probe_keys, build_keys, probe_pos, build_pos, out_sel):
    """Exactness pass: re-check real key equality after hash-based expansion.

    probe_keys/build_keys: [(data, valid), ...] original (unsorted) columns.
    ``probe_pos`` None: output row ``i`` is probe row ``i`` (``lookup_join``).
    Rows where build_pos == MISSING (left-outer padding) are kept.
    """
    ok = jnp.ones(build_pos.shape[0], dtype=jnp.bool_)
    is_outer = build_pos == MISSING
    safe_build = jnp.where(is_outer, 0, build_pos)
    for (pd, pv), (bd, bv) in zip(probe_keys, build_keys):
        if pd.shape[0] == 0 or bd.shape[0] == 0:
            # statically empty side: no equality can hold
            return out_sel & is_outer
        p_d, p_v = (pd, pv) if probe_pos is None else (pd[probe_pos], pv[probe_pos])
        b_d = bd[safe_build]
        b_v = bv[safe_build]
        ok = ok & (p_d == b_d) & p_v & b_v
    return out_sel & (ok | is_outer)
