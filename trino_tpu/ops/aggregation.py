"""Group-by aggregation: a sort path and, for small static key domains, a
domain path that bins rows by slot and reduces.

Which path runs is decided per ``group_aggregate`` call, from what the call
can observe (``domain_slots``): the **domain path** when the caller hands a
static domain for every key (``key_domains``: a dictionary's length, 2 for a
boolean), every key is one integer or boolean lane, the slot count is at most
``DOMAIN_MAX_SLOTS`` and ``max_groups``, and no ``sum``/``avg`` input is
floating point; the **sort path**, described below, in every other case, and
whenever ``key_domains`` is None (the default). Both give the same groups in
the same order, bit for bit.

Reference semantics: ``operator/HashAggregationOperator.java:49`` +
``operator/MultiChannelGroupByHash.java:55`` (open-addressing hash group-by)
and the aggregation function triple input/combine/output
(``operator/aggregation/LongSumAggregation.java:29-55``).

TPU-first design: instead of a linear-probing hash table (scatter-heavy,
serial), we lexicographically sort rows by the group keys with ``lax.sort``
(TPU has a fast bitonic sort), mark group boundaries, assign dense group ids
with a cumulative sum, and reduce over the *sorted* segments — all
MXU/VPU-friendly, fully static shapes.

Scatter-free: XLA scatter (``segment_sum`` / ``.at[].set``) lowers to a
serialized update loop on TPU (~80ms per 1M rows measured vs ~1ms for a
cumsum). Because rows are already sorted by group, every reduction is
expressible without scatter:
- segment boundary positions compact to the front of one cheap
  ``(bool, int32)`` sort (see :class:`_SortedSegments`);
- integer sums are exclusive-cumsum differences at the boundaries (exact:
  int64 wraparound is modular, so boundary differences recover any
  segment sum that itself fits in 64 bits);
- min/max re-sort ``(group_id, masked value)`` — bitonic sort is ~40x
  cheaper than scatter here — and gather the first/last row per segment;
- group keys gather the first row of each segment.
Float sums keep ``segment_sum`` (a global cumsum would change rounding).

Domain path: a row's group is arithmetic on its key codes (slot id = sum of
``(code + 1) * stride``, a null key in a slot of its own), and each integer
reduction is one masked reduction over ``slot == d`` for the D slots, compare,
select and reduce fused so that no (D, n) array exists. No sort, no
permutation, no gather, no prefix scan; the whole call is one jitted program.

Partial/final split: the same kernel serves both; COUNT partials re-aggregate
with SUM, AVG decomposes into SUM+COUNT (exactly Trino's
input/combine/output contract for distributed aggregation).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T

# Supported aggregate kinds and their (partial, final-combine) decomposition.
# sum128 / sum128w are the exact 128-bit accumulation variants for wide
# DECIMAL results (narrow int64 input / wide (n,2) input respectively) —
# see trino_tpu.ops.decimal128 (UnscaledDecimal128Arithmetic semantics).
AGG_KINDS = ("sum", "count", "count_star", "min", "max", "avg")


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregate: kind + input channel (None for count(*))."""

    kind: str
    input_dtype: object | None = None  # storage dtype of the input


def group_aggregate(
    keys: Sequence[tuple[jnp.ndarray, jnp.ndarray]],
    sel: jnp.ndarray,
    agg_inputs: Sequence[tuple[jnp.ndarray, jnp.ndarray] | None],
    agg_specs: Sequence[AggSpec],
    max_groups: int,
    key_domains: Sequence[int | None] | None = None,
):
    """Grouped aggregation (sort path, or domain path: module docstring).

    Args:
      keys: per key column (data, valid), each shape (n,).
      sel: bool (n,) — rows participating.
      agg_inputs: per agg (data, valid) or None for count(*).
      agg_specs: kinds aligned with agg_inputs.
      max_groups: static output capacity (groups beyond are dropped —
        caller must size from stats; overflow is reported).
      key_domains: per key the static number of codes it can take (the
        dictionary's length for codes in [-1, len); 2 for a boolean), None
        where unknown. The caller vouches for it; None (the default) keeps
        the sort path.

    Returns:
      (group_key_data, group_key_valid): lists of (max_groups,) arrays
      agg_results: list of result arrays (max_groups,) —
        for 'avg' returns (sum, count) pair folded by caller
      num_groups: int32 scalar
      overflow: bool scalar (true if groups were dropped)
    """
    if domain_slots(keys, agg_inputs, agg_specs, max_groups, key_domains):
        return _domain_aggregate(
            tuple(keys), sel, tuple(agg_inputs), tuple(agg_specs),
            tuple(key_domains), max_groups,
        )
    n = sel.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    # ONE narrow sort: all key columns (plus selection/validity bits) are
    # bit-packed into 1-3 integer lanes (ops/keypack.py), sorted unstably.
    # The v5e compiler's time for a lax.sort grows with every operand
    # (tens of seconds each at these sizes, keys and payload alike): with
    # Q1's 17 aggregate-input lanes riding this sort as payload, its cold
    # run on the chip had not ended when the call was cut at 1,800 s
    # (PR 24). So the only payload is the row index, and aggregate inputs
    # are gathered through that permutation.
    # Group-key outputs are recovered by G-sized bit extraction from the
    # packed lanes (KeyPlan).
    from trino_tpu.ops import keypack as KP

    plan = KP.KeyPlan(keys, sel_present=True)
    fields, native = plan.build_fields(keys, sel)
    packed = KP.pack(fields)
    n_packed = len(packed)
    key_ops = packed + list(native)
    nkey_ops = len(key_ops)
    *s_lanes, perm = jax.lax.sort(
        tuple(key_ops) + (idx,), num_keys=nkey_ops, is_stable=False
    )
    s_sel = plan.sel_bit(s_lanes[0])

    gathered: dict[int, jnp.ndarray] = {}  # one gather per distinct lane

    def _sorted(lane):
        if id(lane) not in gathered:
            gathered[id(lane)] = lane[perm]
        return gathered[id(lane)]

    def _sorted_pair(pair):
        data, valid = pair
        return _sorted(data), None if valid is None else _sorted(valid)

    # boundary: first row, or any sorted key lane changed vs previous row
    changed = idx == 0
    for k in s_lanes:
        prev = jnp.concatenate([k[:1], k[:-1]])
        changed = changed | (k != prev)
    changed = changed & s_sel
    group_id = jnp.cumsum(changed.astype(jnp.int32)) - 1
    # unselected rows sort past selected ones -> monotonic out-of-range id
    group_id = jnp.where(s_sel, group_id, max_groups)
    num_groups = jnp.sum(changed.astype(jnp.int32))
    overflow = num_groups > max_groups

    seg = _SortedSegments(changed, s_sel, group_id, num_groups, max_groups, n)

    # group key output: gather the packed lanes at each segment's first
    # sorted row (G-sized gathers) and bit-extract the key fields back
    lanes_at = [seg.first(ln) for ln in s_lanes[:n_packed]]
    native_at = [seg.first(ln) for ln in s_lanes[n_packed:]]
    out_key_data, out_key_valid = [], []
    for ki, (data, valid) in enumerate(keys):
        g, kv = plan.key_output(keys, lanes_at, native_at, ki)
        kv = seg.nonempty if kv is None else (kv & seg.nonempty)
        zero = jnp.zeros((), data.dtype)
        if getattr(data, "ndim", 1) == 2:
            out_key_data.append(jnp.where(kv[:, None], g, zero).astype(data.dtype))
        else:
            out_key_data.append(jnp.where(kv, g, zero).astype(data.dtype))
        out_key_valid.append(kv)

    results = _reduce_segments(seg, agg_specs, agg_inputs, _sorted_pair)
    return (out_key_data, out_key_valid), results, num_groups, overflow


def _reduce_segments(seg, agg_specs, agg_inputs, in_segment_order):
    """Every aggregate of a call over its segments: the one loop both paths
    run. ``seg`` reduces rows that lie in its own order (``_SortedSegments``:
    sorted by group; ``_SlotSegments``: as they came) and
    ``in_segment_order`` brings a ``(data, valid)`` input into that order."""
    results = []
    for spec, pair in zip(agg_specs, agg_inputs):
        if spec.kind == "count_star":
            results.append(seg.sizes.astype(jnp.int64))
            continue
        s_data, s_valid = in_segment_order(pair)

        def vcount():
            if s_valid is None:
                return seg.sizes.astype(jnp.int64)
            return seg.sum(s_valid.astype(jnp.int64))

        if spec.kind in ("sum128", "sum128w"):
            from trino_tpu.ops import decimal128 as D

            cnt = vcount()
            if spec.kind == "sum128":
                limbs = D.narrow_limb_sums(s_data, s_valid, seg.sum)
            else:
                limbs = D.wide_limb_sums(
                    s_data[:, 0], s_data[:, 1], s_valid, seg.sum
                )
            results.append((limbs, cnt))
            continue
        if spec.kind == "count":
            results.append(vcount())
        elif spec.kind in ("sum", "avg"):
            contrib = (
                s_data if s_valid is None
                else jnp.where(s_valid, s_data, jnp.zeros_like(s_data))
            )
            ssum = seg.sum(contrib)
            # SQL: sum over empty/all-null group is NULL — caller uses cnt
            results.append((ssum, vcount()))
        elif spec.kind in ("min", "max"):
            cnt = vcount()
            if getattr(s_data, "ndim", 1) == 2:
                from trino_tpu.ops.decimal128 import sort_operands_wide

                hi, lo = s_data[:, 0], s_data[:, 1]
                ident = _max_ident(hi.dtype) if spec.kind == "min" else _min_ident(hi.dtype)
                hk, lk = sort_operands_wide(hi, lo)
                if s_valid is not None:
                    hk = jnp.where(s_valid, hk, ident)
                    lk = jnp.where(s_valid, lk, ident)
                bh, blk = seg.extreme2(hk, lk, spec.kind)
                from trino_tpu.ops.decimal128 import _SIGNBIT

                results.append((jnp.stack([bh, blk ^ _SIGNBIT], axis=1), cnt))
            else:
                ident = (
                    _max_ident(s_data.dtype)
                    if spec.kind == "min"
                    else _min_ident(s_data.dtype)
                )
                masked = (
                    s_data if s_valid is None
                    else jnp.where(s_valid, s_data, ident)
                )
                results.append((seg.extreme(masked, spec.kind), cnt))
        else:
            raise NotImplementedError(spec.kind)
    return results


# Most slots the domain path takes. Its work grows with slots x rows x lanes
# and the sort path's does not. On the chip at Q1's 27 lanes and 2,097,152
# rows the sort path takes 259 ms, the domain path 4.5 ms at 12 slots, 26 at
# 256, 98 at 1,024 and 384 at 4,096 (PERF.md section 6, PR 29): 256 is where
# it still wins tenfold, which leaves room for calls with few lanes, where
# the sort path's fixed part is the larger share.
DOMAIN_MAX_SLOTS = 256


def _key_slots(data, valid, domain: int) -> int:
    """Slots one key spans: its codes, -1 (a dictionary miss; a boolean has
    none) and, where the key carries a validity mask, null."""
    return domain + _code_base(data) + (valid is not None)


def _code_base(data) -> int:
    """What turns a key's lowest code into slot 0: 1 for dictionary codes,
    which start at -1, 0 for a boolean."""
    return 0 if data.dtype == jnp.bool_ else 1


def key_domains_from(keys, dictionaries) -> list[int | None]:
    """``key_domains`` as a caller that holds the key columns knows them: a
    dictionary-coded key takes its dictionary's codes, a boolean two, any
    other key an unknown number. Only where the dictionaries are final."""
    return [
        len(d) if d is not None else 2 if data.dtype == jnp.bool_ else None
        for (data, _), d in zip(keys, dictionaries)
    ]


def domain_slots(keys, agg_inputs, agg_specs, max_groups, key_domains):
    """Slot count D when this ``group_aggregate`` call takes the domain
    path, None when it takes the sort path. Static: reads dtypes, shapes and
    ``key_domains`` only, so a caller can ask before (or without) the call."""
    if key_domains is None or not keys or len(key_domains) != len(keys):
        return None
    slots = 1
    for (data, valid), domain in zip(keys, key_domains):
        if domain is None or getattr(data, "ndim", 1) != 1:
            return None
        dt = np.dtype(data.dtype)
        if dt != np.bool_ and not np.issubdtype(dt, np.integer):
            return None
        slots *= _key_slots(data, valid, domain)
        if slots > min(DOMAIN_MAX_SLOTS, max_groups):
            return None
    for spec, pair in zip(agg_specs, agg_inputs):
        # a float sum in another order rounds differently: the sort path's
        # segmented scan keeps those answers what they were
        if spec.kind in ("sum", "avg") and np.issubdtype(
            np.dtype(pair[0].dtype), np.floating
        ):
            return None
    return slots


@functools.partial(
    jax.jit, static_argnames=("agg_specs", "key_domains", "max_groups")
)
def _domain_aggregate(keys, sel, agg_inputs, agg_specs, key_domains, max_groups):
    """``group_aggregate``'s domain path (contract and outputs as there).

    One program: inside a trace it inlines into the caller's, called eagerly
    it is one launch."""
    sizes = [_key_slots(d, v, dom) for (d, v), dom in zip(keys, key_domains)]
    D = math.prod(sizes)
    slot = jnp.zeros(sel.shape, jnp.int32)
    for (data, valid), size in zip(keys, sizes):
        s = data.astype(jnp.int32) + _code_base(data)
        if valid is not None:
            s = jnp.where(valid, s, size - 1)  # null: the key's last slot
        slot = slot * size + s
    # slot order IS the sort path's group order: key 0 most significant,
    # codes ascending from -1, null last (KeyPlan's fields)
    seg = _SlotSegments(jnp.where(sel, slot, D), D)
    per_slot = _reduce_segments(seg, agg_specs, agg_inputs, lambda pair: pair)

    # compact the non-empty slots to the front, in slot order: src[g] is the
    # g-th non-empty slot, by a (max_groups, D) comparison, no sort
    nonempty = seg.sizes > 0
    rank = jnp.cumsum(nonempty.astype(jnp.int32)) - 1
    num_groups = jnp.sum(nonempty.astype(jnp.int32))
    live = jnp.arange(max_groups, dtype=jnp.int32) < num_groups
    pick = nonempty[None, :] & (
        rank[None, :] == jnp.arange(max_groups, dtype=jnp.int32)[:, None]
    )
    src = jnp.sum(
        jnp.where(pick, jnp.arange(D, dtype=jnp.int32)[None, :], 0), axis=1
    )

    def compact(v):
        g = v[src]
        return jnp.where(live if g.ndim == 1 else live[:, None], g, jnp.zeros_like(g))

    out_key_data, out_key_valid = [], []
    stride = D
    for (data, valid), size in zip(keys, sizes):
        stride //= size
        s = (src // stride) % size
        kv = live if valid is None else live & (s != size - 1)
        code = s - _code_base(data)
        out_key_data.append(jnp.where(kv, code, 0).astype(data.dtype))
        out_key_valid.append(kv)
    results = jax.tree_util.tree_map(compact, per_slot)
    return (
        (out_key_data, out_key_valid), results, num_groups,
        jnp.zeros((), jnp.bool_),
    )


class _SlotSegments:
    """``_SortedSegments``' reductions over rows binned by slot, in any row
    order: each is one masked reduction over ``slot == d`` for the D slots
    (a row with slot D is in none). ``_hit`` and every ``(D, n)`` expression
    built on it is a broadcast that XLA fuses into the reduction consuming
    it; no such array exists in memory."""

    def __init__(self, slot, D: int):
        self._hit = slot[None, :] == jnp.arange(D, dtype=jnp.int32)[:, None]
        self.sizes = jnp.sum(self._hit, axis=1, dtype=jnp.int32)

    def sum(self, x):
        return jnp.sum(jnp.where(self._hit, x[None, :], 0), axis=1, dtype=x.dtype)

    def _extreme(self, mask, x, kind: str):
        ident = _max_ident(x.dtype) if kind == "min" else _min_ident(x.dtype)
        red = jnp.min if kind == "min" else jnp.max
        return red(jnp.where(mask, x[None, :], ident), axis=1)

    def extreme(self, masked, kind: str):
        return self._extreme(self._hit, masked, kind)

    def extreme2(self, k1, k2, kind: str):
        """Lexicographic two-lane min/max: the extreme of ``k1``, then of
        ``k2`` among the slot's rows tied on it."""
        b1 = self._extreme(self._hit, k1, kind)
        tied = self._hit & (k1[None, :] == b1[:, None])
        return b1, self._extreme(tied, k2, kind)


# Blocks whose totals ``_blocked_scan`` still scans in one window: what a
# slab step of 2,097,152 rows has, the widest scan inside a loop before PR 34,
# so no program of that many rows or fewer changes
_SCAN_DIRECT_BLOCKS = 1 << 12


def _blocked_scan(x, scan, combine, ident):
    """Inclusive prefix scan via a blocked two-level scan.

    ``jnp.cumsum`` (and ``lax.cummax``) lower to one big reduce-window:
    its scoped-vmem allocation blows up inside TPU while-loops (the
    streaming chunk loop), and XLA:TPU takes ~1min to COMPILE an int64
    reduce-window at odd (non-power-of-two) sizes. Odd sizes are padded
    with ``ident`` to a block multiple so every window stays small and
    power-of-two shaped.

    The second level (one total a block) is one window as long as there
    are blocks. Up to ``_SCAN_DIRECT_BLOCKS`` of them it is scanned
    directly; past that it goes through this function again: inside the
    slab loop the v5e compiler refuses the direct one at the 16,384 blocks
    of an 8,388,608-row step (scoped vmem 64.23M over 16.00M) and at the
    32,768 of a 16,777,216-row one (19.09M) (``PERF.md`` section 6, PR 34)."""
    n = x.shape[0]
    blk = 512
    if n <= blk:
        return scan(x)
    pad = (-n) % blk
    xp = jnp.concatenate([x, jnp.full((pad,), ident, x.dtype)]) if pad else x
    xb = jnp.reshape(xp, ((n + pad) // blk, blk))
    within = scan(xb, axis=1)
    totals = within[:, -1]
    if totals.shape[0] > _SCAN_DIRECT_BLOCKS:
        offsets = _blocked_scan(totals, scan, combine, ident)
    else:
        offsets = scan(totals)
    offsets = jnp.concatenate([jnp.full((1,), ident, x.dtype), offsets[:-1]])
    out = jnp.reshape(combine(within, offsets[:, None]), (n + pad,))
    return out[:n] if pad else out


def _prefix_sum(x):
    """Inclusive prefix sum (blocked, see ``_blocked_scan``)."""
    return _blocked_scan(x, jnp.cumsum, jnp.add, 0)


def _prefix_max(x):
    """Inclusive running maximum of an integer array (blocked)."""
    return _blocked_scan(
        x, jax.lax.cummax, jnp.maximum, jnp.iinfo(x.dtype).min
    )


def _segmented_scan(flags, x, kind: str):
    """Running within-segment reduction (sum/min/max) via one
    ``associative_scan`` over (segment-start flag, value) pairs — the
    standard segmented-scan operator, O(log n) passes, no sort and no
    scatter. ``run[last_row_of_segment]`` is the segment reduction."""
    if kind == "sum":
        op = jnp.add
    elif kind == "min":
        op = jnp.minimum
    else:
        op = jnp.maximum

    def comb(a, b):
        af, av = a
        bf, bv = b
        return af | bf, jnp.where(bf, bv, op(av, bv))

    _, run = jax.lax.associative_scan(comb, (flags, x))
    return run


class _SortedSegments:
    """Scatter-free reductions over rows sorted by a monotonic group id.

    ``starts[g]`` is the first sorted-row index of group ``g``; every
    reduction is then a cumsum difference, a boundary gather, or a
    segmented associative scan. Boundary positions come from one cheap
    single-lane sort of ``(is-not-boundary, row-index)`` packed into one
    integer (a ``searchsorted`` over the 1M-row group-id array costs ~5x
    more here: its binary-search rounds serialize, while one more narrow
    bitonic sort rides the same fast path the main sort uses)."""

    def __init__(self, changed, s_sel, group_id_sorted, num_groups,
                 max_groups: int, n: int):
        from trino_tpu.ops import keypack as KP

        g = min(max_groups + 1, n)
        pos = KP.compact_front_positions(changed, n)
        pos = pos[:g]
        if g < max_groups + 1:  # tiny batch: fewer rows than groups
            pos = jnp.concatenate(
                [pos, jnp.zeros(max_groups + 1 - g, dtype=jnp.int32)]
            )
        n_sel = jnp.sum(s_sel.astype(jnp.int32))
        live = jnp.arange(max_groups + 1, dtype=jnp.int32) < num_groups
        self.starts = jnp.where(live, pos, n_sel)
        self.sizes = self.starts[1:] - self.starts[:-1]
        self.nonempty = self.sizes > 0
        self._changed = changed
        self._gid = group_id_sorted
        self._max_groups = max_groups
        hi = max(n - 1, 0)
        self._first_idx = jnp.clip(self.starts[:-1], 0, hi)
        self._last_idx = jnp.clip(self.starts[1:] - 1, 0, hi)

    def first(self, x):
        """x gathered at each segment's first row (junk for empty segs)."""
        return x[self._first_idx]

    def sum(self, x):
        """Per-segment sum via exclusive-cumsum boundary differences.

        Exact for integers (modular wraparound cancels); floats use a
        segmented scan (the running within-segment sum read at each
        segment's last row) — a global float cumsum would accumulate
        cross-segment rounding, and a scatter ``segment_sum`` serializes
        on TPU."""
        import numpy as np

        if not np.issubdtype(np.dtype(x.dtype), np.integer):
            run = _segmented_scan(self._changed, x, "sum")
            return jnp.where(self.nonempty, run[self._last_idx], 0)
        cs = _prefix_sum(x)
        csz = jnp.concatenate([jnp.zeros((1,), x.dtype), cs])
        # one gather at the G + 1 boundaries, not one at each end of the G
        # segments: at a million groups the gathers are the step's time
        at = csz[self.starts]
        return at[1:] - at[:-1]

    def extreme(self, masked, kind: str):
        """Per-segment min/max of pre-masked values via one segmented
        associative scan (sort-free, scatter-free): the running extreme
        read at each segment's last row."""
        run = _segmented_scan(self._changed, masked, kind)
        return run[self._last_idx]

    def extreme2(self, k1, k2, kind: str):
        """Lexicographic two-lane min/max (wide DECIMAL) via one
        segmented scan over (hi, lo) pairs."""
        flags = self._changed

        def comb(a, b):
            af, ah, al = a
            bf, bh, bl = b
            a_less = (ah < bh) | ((ah == bh) & (al < bl))
            take_a = a_less if kind == "min" else ~a_less
            take_a = take_a & ~bf  # segment restart: keep b
            return (
                af | bf,
                jnp.where(take_a, ah, bh),
                jnp.where(take_a, al, bl),
            )

        _, rh, rl = jax.lax.associative_scan(comb, (flags, k1, k2))
        i = self._last_idx
        return rh[i], rl[i]


def distinct_first_mask(
    keys: Sequence[tuple[jnp.ndarray, jnp.ndarray]],
    value: tuple[jnp.ndarray, jnp.ndarray],
    sel: jnp.ndarray,
) -> jnp.ndarray:
    """Mask of first occurrences of each (group keys..., value) combination
    among selected rows — the dedup pass behind DISTINCT aggregates
    (reference: ``MarkDistinctOperator.java`` / distinct accumulators).

    Sort-based: one narrow bit-packed sort of (sel, keys..., value), mark
    rows where any packed lane differs from the previous row, and restore
    original row order with a scatter-free inverse-permutation sort.
    """
    from trino_tpu.ops import keypack as KP

    n = sel.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    s_lanes, perm, s_sel = KP.grouping_sort(list(keys) + [value], sel, n)
    changed = idx == 0
    for k in s_lanes:
        prev = jnp.concatenate([k[:1], k[:-1]])
        changed = changed | (k != prev)
    first_sorted = changed & s_sel
    return KP.inverse_permute_mask(perm, first_sorted)


def global_aggregate(
    sel: jnp.ndarray,
    agg_inputs: Sequence[tuple[jnp.ndarray, jnp.ndarray] | None],
    agg_specs: Sequence[AggSpec],
):
    """Aggregation without GROUP BY: single group, plain reductions."""
    results = []
    for spec, pair in zip(agg_specs, agg_inputs):
        if spec.kind == "count_star":
            results.append(jnp.sum(sel.astype(jnp.int64)))
            continue
        data, valid = pair
        use = sel if valid is None else (valid & sel)
        cnt = jnp.sum(use.astype(jnp.int64))
        if spec.kind in ("sum128", "sum128w"):
            from trino_tpu.ops import decimal128 as D

            total = lambda x: jnp.reshape(jnp.sum(x), (1,))  # noqa: E731
            if spec.kind == "sum128":
                limbs = D.narrow_limb_sums(data, use, total)
            else:
                limbs = D.wide_limb_sums(data[:, 0], data[:, 1], use, total)
            results.append((limbs, cnt))
            continue
        if spec.kind == "count":
            results.append(cnt)
        elif spec.kind in ("sum", "avg"):
            s = jnp.sum(jnp.where(use, data, jnp.zeros_like(data)))
            results.append((s, cnt))
        elif spec.kind in ("min", "max") and getattr(data, "ndim", 1) == 2:
            from trino_tpu.ops.decimal128 import global_minmax_wide

            bh, bl = global_minmax_wide(data[:, 0], data[:, 1], use, spec.kind)
            results.append((jnp.stack([bh, bl], axis=1), cnt))
        elif spec.kind == "min":
            results.append((jnp.min(jnp.where(use, data, _max_ident(data.dtype))), cnt))
        elif spec.kind == "max":
            results.append((jnp.max(jnp.where(use, data, _min_ident(data.dtype))), cnt))
        else:
            raise NotImplementedError(spec.kind)
    return results


def _max_ident(dtype):
    import numpy as np

    if np.issubdtype(dtype, np.integer):
        return jnp.asarray(np.iinfo(dtype).max, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(True)
    return jnp.asarray(np.inf, dtype=dtype)


def _min_ident(dtype):
    import numpy as np

    if np.issubdtype(dtype, np.integer):
        return jnp.asarray(np.iinfo(dtype).min, dtype=dtype)
    if dtype == jnp.bool_:
        return jnp.asarray(False)
    return jnp.asarray(-np.inf, dtype=dtype)
