"""Fragment-fused execution: one compiled SPMD program per plan fragment.

Reference: Trino executes each PlanFragment as a pipeline of operators with
per-operator scheduling (``operator/Driver.java:355-392``); its "native
tier" compiles the hot expression chains (``sql/gen/ExpressionCompiler.java``).
The TPU translation goes further (SURVEY §7 "Stage = pjit program"): the
ENTIRE fragment — scan filters, projections, joins, partial/final
aggregation, and the exchange collectives that feed the next fragment —
traces into a single ``jax.jit`` program over the device mesh. No per-node
materialization, no host syncs between operators; XLA fuses the chain and
schedules the collectives (``lax.all_to_all`` / ``all_gather``) inline.

Execution model:
- :func:`fragment_plan` (planner/fragmenter.py) splits the optimized plan
  at remote exchanges.
- :class:`FragmentedExecutor` runs the fragment tree bottom-up. Every
  fragment whose nodes are in the fusable set runs as ONE jitted program;
  queries containing non-fusable shapes (windows, set ops, grouping sets,
  semi/anti joins, DISTINCT aggregates, VALUES) fall back to the
  materialized interpreter (``DistributedExecutor``), which remains the
  semantics reference.
- Capacities (group budgets, join output sizes, exchange buckets) are
  static per compile; kernels report overflow flags and the host retries
  with capacities regrown to the next power-of-two bucket. Compiled
  programs live in an engine-owned store keyed by canonical-plan
  fingerprint (planner/canonicalize.py) and, per program, by the
  capacity signature it was traced at — repeated or literal-variant
  queries skip Python retracing entirely (hoisted literals ride in as
  the ``__params__`` jit input), and the overflow ladder re-hits any
  signature it has seen before. Identical programs additionally skip
  XLA compilation via the persistent on-disk compile cache enabled in
  trino_tpu.__init__.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column, Dictionary, bucket_capacity
from trino_tpu.exec.local import ExecutionError, Result, rank_codes, sum_spec_for
from trino_tpu.obs.metrics import get_registry
from trino_tpu.obs.trace import get_tracer
from trino_tpu.ops import join as J
from trino_tpu.ops.aggregation import AggSpec, global_aggregate, group_aggregate
from trino_tpu.ops.sort import sort_indices
from trino_tpu.parallel import exchange as X
from trino_tpu.parallel.distributed import DistributedExecutor, _sharded_probe
from trino_tpu.parallel.mesh import AXIS, shard_batch, smap
from trino_tpu.planner import plan as P
from trino_tpu.planner.fragmenter import (
    FusedFragment,
    PlanFragment,
    SubPlan,
    filtered_broadcast_fids,
    fragment_plan,
    fuse_groups,
    partitioned_join_pairs,
)


class FusedUnsupported(Exception):
    """Raised during tracing when a shape turns out not to be fusable."""


class BatchUnsupported(Exception):
    """Raised when a plan or input shape cannot ride the cross-query
    batched (K-unrolled) dispatch path — streaming-sized scans, spill
    inputs, multi-host meshes, non-fusable plans. Callers fall back to
    sequential per-member execution bit-identically."""


class CapacityRetryExceeded(ExecutionError):
    """Capacity-overflow retry budget exhausted.

    Carries the failing fragment, the final (grown) capacities, and the
    attempt count so operators see *where* growth diverged instead of a
    bare message. ``retryable=False``: capacity growth is a pure function
    of the data, so re-running on another worker (TASK retry) or from
    scratch (QUERY retry) replays the same growth path — the new retry
    policies treat this as fatal.
    """

    retryable = False

    def __init__(
        self,
        site: str,
        fragment_id=None,
        capacities: Optional[dict] = None,
        attempts: int = 0,
    ):
        self.site = site
        self.fragment_id = fragment_id
        self.capacities = dict(capacities or {})
        self.attempts = attempts
        caps_text = (
            ", ".join(f"{k}={v}" for k, v in sorted(self.capacities.items()))
            or "none recorded"
        )
        super().__init__(
            f"{site} capacity retry limit exceeded"
            f" (fragment={fragment_id if fragment_id is not None else '?'},"
            f" attempts={attempts}, final capacities: {caps_text})"
        )


# --- fusability -------------------------------------------------------------

_FUSABLE_NODES = (
    P.TableScan,
    P.RemoteSource,
    P.Filter,
    P.Project,
    P.Aggregate,
    P.Join,
    P.TopN,
    P.Limit,
    P.Sort,
    P.Output,
    P.Values,
)


def _is_wide_type(t) -> bool:
    return isinstance(t, T.DecimalType) and t.wide


def _expr_blocks_fusion(e) -> bool:
    """Modulus/cast touching wide DECIMAL narrows at runtime with a
    data-dependent check — not traceable; those queries interpret.
    (Wide DIVISION traces: ops/decimal128.div128_round.)"""
    from trino_tpu.ir import Call, SpecialForm

    if isinstance(e, Call):
        if e.name == "modulus" and (
            _is_wide_type(e.type) or any(_is_wide_type(a.type) for a in e.args)
        ):
            return True
        if e.name == "cast" and any(_is_wide_type(a.type) for a in e.args):
            st, rt = e.args[0].type, e.type
            traced = (
                isinstance(rt, (T.DoubleType, T.RealType))
                or (
                    isinstance(rt, T.DecimalType)
                    and isinstance(st, T.DecimalType)
                    and (rt.wide and rt.scale >= st.scale
                         or st.scale - rt.scale <= 18)
                )
            )
            if not traced:
                return True
        return any(_expr_blocks_fusion(a) for a in e.args)
    if isinstance(e, SpecialForm):
        return any(_expr_blocks_fusion(a) for a in e.args)
    return False


# XLA failure signatures that a SMALLER program can fix: scoped-vmem
# allocation failures at compile time and HBM exhaustion at run time
# (VERDICT.md weak #3: SF1 Q5's 33MB fragment program died in scoped
# allocation before any overflow flag could fire)
_RESOURCE_ERROR_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "Resource exhausted",
    "resource exhausted",
    "Scoped allocation",
    "scoped allocation",
    "vmem limit",
    "VMEM limit",
    "out of memory",
    "Out of memory",
)


def _is_resource_exhausted(e: BaseException) -> bool:
    """True when an XLA compile/allocation failure should enter the
    capacity-HALVING ladder instead of failing the query."""
    msg = f"{type(e).__name__}: {e}"
    return any(m in msg for m in _RESOURCE_ERROR_MARKERS)


def grow_or_raise(name: str, caps: "_Caps", need: int = 0) -> None:
    """Dispatch one fired traced flag: capacity names grow for a retry;
    ``err!<message>`` names are data-dependent runtime ERRORS discovered
    inside a compiled program (e.g. a scalar subquery returning multiple
    rows) and fail the query. ``need`` is the flag's own value: a group
    budget's flag carries the groups its program counted (``need_flag``),
    so the budget grows to hold them at once where that is more than the
    next rung; every other flag is 1."""
    if name.startswith("err!"):
        raise ExecutionError(name[4:])
    # spill/hot tiers are deliberately small (the cold bucket absorbs the
    # common case), so when they do overflow, converge in few retries
    caps.grow(name, 4 if name.startswith(("agg", "spill", "hot")) else 2, need)
    if name.startswith("agg"):
        span = get_tracer().current()
        if span is not None:
            span.add("groupBudgetGrowths")


def need_flag(overflow, num_groups):
    """A group budget's overflow flag (traced): 0 while the groups fit, else
    how many the program counted, which ``grow_or_raise`` grows to. A count
    made after an earlier budget dropped groups is a lower bound, and the
    ladder goes on from there."""
    return jnp.where(overflow, num_groups, 0).astype(jnp.int32)


def query_fusable(sub: SubPlan) -> bool:
    return all(fragment_fusable(frag) for frag in sub.all_fragments())


def fragment_fusable(frag: PlanFragment) -> bool:
    """True when every node in this one fragment traces into the fused
    program (worker tasks check per-fragment: a window fragment interprets
    while its scan fragments still run fused on device)."""
    for n in P.walk_plan(frag.root):
        if not isinstance(n, _FUSABLE_NODES):
            return False
        if isinstance(n, P.Join):
            if n.join_type in ("SEMI", "ANTI"):
                # membership marks trace (hash lookup + scatter); residual
                # correlated filters still interpret
                if n.filter is not None or any(
                    _is_wide_type(a.type) or _is_wide_type(b.type)
                    for a, b in n.criteria
                ):
                    return False
                continue
            if n.join_type == "CROSS" and n.single_row:
                # uncorrelated scalar subquery: the one-row build
                # broadcasts into every probe row (traced)
                continue
            if (
                n.join_type not in ("INNER", "LEFT")
                or not n.criteria
                or (n.single_row and n.join_type != "LEFT")
                or (n.join_type == "LEFT" and n.filter is not None)
                or any(
                    _is_wide_type(a.type) or _is_wide_type(b.type)
                    for a, b in n.criteria
                )
            ):
                return False
            if n.filter is not None and _expr_blocks_fusion(n.filter):
                return False
        if isinstance(n, P.Aggregate):
            if any(fn.distinct for _, fn in n.aggregates) and n.step != "single":
                return False  # distinct dedup must see all rows at once
            if any(_is_wide_type(k.type) for k in n.group_keys):
                return False  # wide group keys: interpreter path
            for _, fn in n.aggregates:
                if fn.kind not in (
                    "sum", "count", "count_star", "min", "max", "avg"
                ):
                    return False
                # wide sums/min/max/avg all fuse (limb accumulators,
                # two-lane extrema, div128_round for the avg divide)
        if isinstance(n, P.Filter) and _expr_blocks_fusion(n.predicate):
            return False
        if isinstance(n, P.Project) and any(
            _expr_blocks_fusion(e) for _, e in n.assignments
        ):
            return False
    return True


def remote_layout(node: P.RemoteSource, layout: dict) -> dict:
    """The producer's ``layout`` under ``node``'s symbols (the same order)."""
    producer_order = sorted(layout, key=layout.get)
    if len(producer_order) != len(node.symbols):
        raise FusedUnsupported("remote source arity mismatch")
    return {s.name: layout[p] for s, p in zip(node.symbols, producer_order)}


def sort_joins_only(session) -> bool:
    """Every equi-join of ``session`` runs by sort-merge: ``join_strategy``
    ``auto`` or ``sort``, or ``dense_join`` off (``_join_strategy``)."""
    pref = str(session.get("join_strategy") or "auto").lower()
    return pref in ("auto", "sort") or not bool(session.get("dense_join"))


@jax.jit
def _unique_flags(sides):
    """``ops/join.py::unique_key_columns`` of each build side of ``sides``,
    ``(key columns [(data, valid or None)], selection or None, rows)``,
    stacked: one program and one pull for them all."""
    flags = []
    for cols, sel, rows in sides:
        live = jnp.arange(cols[0][0].shape[0]) < rows
        if sel is not None:
            live = live & sel
        flags.append(J.unique_key_columns(
            [(d, jnp.ones(d.shape[0], jnp.bool_) if v is None else v)
             for d, v in cols],
            live,
        ))
    return jnp.stack(flags)


class _Caps:
    """Capacity knobs, grown on overflow (shape-bucketed).

    ``provenance`` records where each value came from (``default`` /
    ``seeded`` from planner stats / ``history`` from the observed-stats
    store / ``+grown`` suffix after an overflow retry / ``+halved`` after
    a RESOURCE_EXHAUSTED shrink) — surfaced in the per-query exchange
    counters so capacity decisions are auditable.

    ``sites`` maps the tracer's runtime capacity names (which embed
    ``id(node)`` and change across processes and dynamic-filter rewrites)
    to restart-stable names like ``agg@3#0`` (kind @ fragment id # plan
    ordinal) — the keying the history store persists under."""

    def __init__(self):
        self.vals: dict[str, int] = {}
        self.provenance: dict[str, str] = {}
        self._seed_floor: dict[str, tuple[int, str]] = {}
        self.sites: dict[str, str] = {}
        # join engine v2: per-site chosen strategy (surfaced as
        # exchangeStats.joinStrategy), grow counts, and the demotion set.
        # A ``densejoin`` site that keeps overflowing after capacity
        # growth has a duplicate-key chain longer than the static probe
        # window — doubling can never place it (same key ⇒ same slot
        # sequence), so the site demotes to the sort strategy and the
        # retrace drops its table entirely (graceful, still compiled).
        # Per site also what it was last traced at: the ``joins`` attribute
        # of the span over the site's program (``joins``).
        self.join_sites: dict[str, dict] = {}
        self.grow_counts: dict[str, int] = {}
        self.demoted: set[str] = set()

    def get(self, name: str, default: int) -> int:
        if name not in self.vals:
            floor = self._seed_floor.pop(name, None)
            if floor is not None and floor[0] > default:
                self.vals[name] = floor[0]
                self.provenance[name] = floor[1]
            else:
                self.vals[name] = default
                self.provenance.setdefault(name, "default")
        return self.vals[name]

    def seed(
        self,
        name: str,
        value: int,
        floor_only: bool = False,
        provenance: str = "seeded",
    ) -> None:
        """Install a stats- or history-derived starting value.
        ``floor_only`` seeds take effect only when above the site's
        built-in default (used for join caps, where shrinking below the
        data-derived default trades a recompile-retry for padding).
        Floors are first-wins: history seeding runs before stats seeding
        and observed truth must not be clobbered by a static estimate."""
        if name in self.vals:
            return
        if floor_only:
            if name not in self._seed_floor:
                self._seed_floor[name] = (value, provenance)
        else:
            self.vals[name] = value
            self.provenance[name] = provenance

    def seeded(self, name: str):
        """(value, provenance) of a site's installed value or pending
        seed floor, or None — lets cost gates consult history before the
        site's first ``get()`` (the floor only installs at get time)."""
        if name in self.vals:
            return self.vals[name], self.provenance.get(name, "default")
        fl = self._seed_floor.get(name)
        return (fl[0], fl[1]) if fl is not None else None

    def joins(self, wanted=None, traced=None) -> list[dict]:
        """The join sites traced under these capacities (those whose
        runtime name ``wanted`` accepts, where given; those one program
        traced, where ``traced`` is its record), by restart-stable
        site: the kernel each runs (``strategy``), the static shapes it was
        chosen at (``buildCap``, ``probeCap``), its key columns (``keys``),
        the output capacity it was given (``outCap``) and whether that is
        its probe's because the build key is unique (``unique``), and whether
        it ran as a lookup, no probe column gathered (``lookup``)."""
        by_site = {}
        for nm, info in (self.join_sites if traced is None else traced).items():
            if wanted is None or wanted(nm):
                site = self.sites.get(nm, nm)
                by_site[site] = {"site": site, **info}
        return [by_site[site] for site in sorted(by_site)]

    def grow(self, name: str, factor: int = 2, need: int = 0) -> None:
        # quantize growth to power-of-two buckets: stats-seeded odd-sized
        # caps would otherwise walk a per-query ladder of unique shapes,
        # and every distinct capacity signature is a separate traced
        # program in the cross-query store
        self.vals[name] = bucket_capacity(
            max(self.vals[name] * factor, need), minimum=1
        )
        prev = self.provenance.get(name, "default")
        if not prev.endswith("+grown"):
            self.provenance[name] = prev + "+grown"
        # count under the restart-stable alias: every retrace mints a
        # fresh ``densejoin{id(node)}`` runtime name, so an id-keyed
        # counter would reset each attempt and the ladder would grow
        # until CapacityRetryExceeded instead of ever demoting
        stable = self.sites.get(name, name)
        self.grow_counts[stable] = self.grow_counts.get(stable, 0) + 1
        # second fruitless table growth ⇒ duplicate-chain pathology, not
        # sizing: demote the site to the sort strategy (class docstring)
        if name.startswith("densejoin") and self.grow_counts[stable] >= 2:
            self.demoted.add(stable)

    def shrink_all(self, factor: int = 2, floor: int = 64) -> bool:
        """Inverse ladder for RESOURCE_EXHAUSTED compile/alloc failures:
        the program's static shapes exceed scoped vmem (or HBM) before any
        overflow flag can run, so halve every capacity still above
        ``floor`` and retrace smaller. Returns False when nothing can
        shrink (caller re-raises). Row overflow after a halve re-grows
        through the normal ladder — both walks land on the same
        power-of-two buckets."""
        changed = False
        for nm, v in list(self.vals.items()):
            nv = max(floor, bucket_capacity(max(1, v // factor), minimum=1))
            if nv < v:
                self.vals[nm] = nv
                prev = self.provenance.get(nm, "default")
                if not prev.endswith("+halved"):
                    self.provenance[nm] = prev + "+halved"
                changed = True
        return changed

    def signature(self) -> tuple:
        """Hashable view of the current capacity values — the part of a
        traced program's shape that the plan fingerprint cannot see.
        Demotions ride along: a demoted join site traces a different
        kernel at the same capacities, so it must key a new program."""
        return tuple(sorted(self.vals.items())) + tuple(sorted(self.demoted))


@dataclasses.dataclass
class _Meta:
    """Static metadata captured while tracing a fragment program."""

    layout: Optional[dict[str, int]] = None
    column_meta: Optional[list[tuple[T.SqlType, Optional[Dictionary]]]] = None
    overflow_names: Optional[list[str]] = None
    output_names: Optional[list[str]] = None
    # exchange observability: names of traced counters riding the output,
    # plus statically-known per-execution stats (wire slots, bytes)
    counter_names: Optional[list[str]] = None
    exchange_static: Optional[dict] = None
    # device profiling (obs/profiler.py): XLA cost/memory analysis of the
    # compiled program — rides the program-cache entry so warm hits reuse
    # it without recompiling — and the AOT executable itself (warm hits
    # execute through it; None when profiling was off at trace time, the
    # AOT path failed, or a later call saw different input shapes)
    device_stats: Optional[dict] = None
    aot: Any = None
    # cross-query batching: K > 0 marks a batched program whose outputs
    # are per-member tuples — _retry_traced demuxes them into K Results
    # instead of assembling one (rides the cached (jf, meta) entry, so
    # warm hits demux without retracing)
    batch_size: Optional[int] = None
    # the join sites this program traced, as ``_Caps.join_sites`` has them
    joins: Optional[dict] = None

    def capture(self, res: Result, tracer) -> None:
        self.layout = dict(res.layout)
        self.column_meta = [
            (c.type, c.dictionary) for c in res.batch.columns
        ]
        self.overflow_names = [nm for nm, _ in tracer.overflows]
        self.counter_names = [nm for nm, _ in tracer.counters]
        self.exchange_static = dict(tracer.exchange_static)
        self.joins = dict(tracer.joins)
        self._tracer = tracer

    def outputs(self, res: Result):
        flags = tuple(f for _, f in self._tracer.overflows)
        counters = tuple(c for _, c in self._tracer.counters)
        aux = tuple(self._tracer.aux_out)
        data = tuple((c.data, c.valid) for c in res.batch.columns)
        return data, res.batch.selection_mask(), flags, counters, aux


class _TracerSummary:
    """Merged view over the per-member tracers of a fused program, duck-
    typed to what ``_Meta.capture``/``_Meta.outputs`` read from a single
    :class:`_FragmentTracer`. Overflow flags and counters concatenate
    (site names are unique per node/fragment id); static exchange stats
    sum; ``aux_out`` carries only the ROOT member's exported hot set —
    interior probes' hot sets are consumed in-trace by their in-unit
    build peer and never leave the program."""

    def __init__(self):
        self.overflows: list = []
        self.counters: list = []
        self.exchange_static: dict = {}
        self.aux_out: tuple = ()
        self.joins: dict = {}

    def absorb(self, tracer) -> None:
        self.joins.update(tracer.joins)
        self.overflows.extend(tracer.overflows)
        self.counters.extend(tracer.counters)
        for k, v in tracer.exchange_static.items():
            self.exchange_static[k] = self.exchange_static.get(k, 0) + v


class _BatchSummary:
    """Combined view over the K per-member tracers of a cross-query
    batched program, duck-typed like :class:`_TracerSummary`. The K
    members are copies of ONE program over different parameter slices,
    so their overflow/counter site lists are identical — flags merge
    positionally by element-wise max (a site overflows when ANY member
    overflows; the grown rerun re-executes all members) and counters
    sum, keeping the host-side deferred-flag protocol at one scalar per
    site whatever K. Static exchange stats sum; ``aux_out`` stays empty
    (skew handling is disabled under batching)."""

    def __init__(self):
        self.overflows: list = []
        self.counters: list = []
        self.exchange_static: dict = {}
        self.aux_out: tuple = ()
        self.joins: dict = {}
        self._first = True

    def absorb(self, tracer) -> None:
        self.joins.update(tracer.joins)
        if self._first:
            self.overflows = [
                (nm, f.astype(jnp.int32)) for nm, f in tracer.overflows
            ]
            self.counters = list(tracer.counters)
            self._first = False
        else:
            self.overflows = [
                (nm, jnp.maximum(f, g.astype(jnp.int32)))
                for (nm, f), (_, g) in zip(self.overflows, tracer.overflows)
            ]
            self.counters = [
                (nm, c + d)
                for (nm, c), (_, d) in zip(self.counters, tracer.counters)
            ]
        for k, v in tracer.exchange_static.items():
            self.exchange_static[k] = self.exchange_static.get(k, 0) + v


def program_label(program_key) -> str:
    """Stable display label for a program-cache key: fragment identity
    without the per-run root-object id (metrics labels and deviceStats
    keys must not churn across executions of the same cached plan)."""
    if isinstance(program_key, tuple) and len(program_key) >= 2:
        if program_key[0] == "frag":
            return f"frag:{program_key[1]}"
        if program_key[0] == "post":
            return f"post:{program_key[1]}"
        if program_key[0] == "fused":
            return "fused:" + "+".join(str(i) for i in program_key[1])
        if program_key[0] == "bfrag":
            return f"bfrag:{program_key[1]}x{program_key[2]}"
        if program_key[0] == "bfused":
            return (
                "bfused:"
                + "+".join(str(i) for i in program_key[1])
                + f"x{program_key[2]}"
            )
    return repr(program_key)


def agg_site(frag: PlanFragment, agg: P.Aggregate) -> str:
    """An aggregate by content, ``agg@<fragment id>#<ordinal>`` (its place
    among the fragment's aggregates in walk order): the same for equal
    plans at any address, which ``id(node)`` is not."""
    k = 0
    for node in P.walk_plan(frag.root):
        if node is agg:
            break
        if isinstance(node, P.Aggregate):
            k += 1
    return f"agg@{frag.id}#{k}"


class FragmentedExecutor(DistributedExecutor):
    """Distributed executor that compiles each fragment into one program.

    ``programs`` (optional) is an engine-owned store that outlives this
    per-query executor: jitted fragment programs and their capture
    metadata are reused across executions of the same cached plan, so a
    warm query skips Python retracing entirely (the reference's operators
    are reused per-driver; ours are compiled programs reused per-plan).
    """

    # overflow flags queued during _execute_fragments (None outside it,
    # e.g. when worker tasks call run_fragment_program directly)
    deferred_flags: Optional[list] = None
    # exchange counters queued alongside: (names, stacked int64, static)
    deferred_counters: Optional[list] = None

    def __init__(
        self,
        *args,
        programs: Optional[dict] = None,
        params: Optional[Sequence] = None,
        history: Optional[dict] = None,
        **kwargs,
    ):
        super().__init__(*args, **kwargs)
        self.programs: dict = {} if programs is None else programs
        # this fingerprint's aggregate entry from the query-history store
        # (obs/history.py), or None when history is off / the query is
        # cold: observed final capacities floor the static stats seeds
        self.history = history
        # ordered (value, type) literals hoisted out of a canonicalized
        # plan (planner/canonicalize.py): interpreter paths read the host
        # values via self._params; traced programs receive device scalars
        # through the __params__ jit input
        self._param_list = list(params) if params else []
        self._params = (
            tuple(v for v, _ in self._param_list) or None
        )
        # per-query compile-time telemetry (CacheStatsMBean analog);
        # engine copies this onto StatementResult after execution
        self.compile_stats: dict = {
            "trace_count": 0,
            "compile_ms": 0.0,
            "program_cache_hits": 0,
            "program_cache_misses": 0,
        }
        # per-query operator telemetry accumulated off the op! counter
        # channel: {stable_site: {kind, rows_in, rows_out}}
        self.operator_stats: dict[str, dict] = {}
        # per-query: replicated hot-key tables exported by probe-side
        # exchanges, keyed by producer fragment id (device arrays)
        self._hot_sets: dict[int, tuple] = {}
        # chaos hook (trino_tpu/ft): per-fragment crash injection. None
        # unless the session configures fault probabilities.
        from trino_tpu.ft.injection import FaultInjector

        self.fault_injector = FaultInjector.from_session(self.session)

    def _execute_plan(self, node: P.PlanNode) -> tuple[Batch, list[str]]:
        # inside LocalExecutor.execute's ``execute_plan`` span, the fall-
        # backs to the interpreter included.
        # reuse the fragmented plan across executions of a cached plan:
        # program-cache keys and traced closures reference fragment node
        # identities, so the fragmentation must be stable too
        sub = self.programs.get("__subplan__")
        if sub is None:
            with get_tracer().span("fragment"):
                sub = fragment_plan(node)
            self.programs["__subplan__"] = sub
        if query_fusable(sub):
            try:
                return self._execute_fragments(sub)
            except (FusedUnsupported, jax.errors.TracerArrayConversionError):
                # not traceable, or an operator needed host values
                # mid-trace (e.g. datetime formatting over unique
                # values) — interpret instead
                pass
        span = get_tracer().current()
        if span is not None:
            span.set("fallback", "interpreter")
        return super()._execute_plan(node)

    def count_program(
        self, hit: bool, compile_ms: float = 0.0, stored: bool = True
    ) -> None:
        """Count one program of ``exec/streaming.py`` into this query's
        ``compile_stats`` and the store's ``__stats__``, as
        ``_retry_traced`` counts its own: a hit, or a trace (a miss too
        where the program went into the store)."""
        store = self.programs.setdefault(
            "__stats__",
            {"hits": 0, "misses": 0, "trace_count": 0, "compile_ms": 0.0},
        )
        if hit:
            self.compile_stats["program_cache_hits"] += 1
            store["hits"] += 1
            return
        if stored:
            self.compile_stats["program_cache_misses"] += 1
            store["misses"] += 1
        self.compile_stats["trace_count"] += 1
        self.compile_stats["compile_ms"] += compile_ms
        store["trace_count"] += 1
        store["compile_ms"] = round(store["compile_ms"] + compile_ms, 3)

    def _param_arrays(self) -> Optional[tuple]:
        """Hoisted literals as typed device scalars — the ``__params__``
        jit input. Dtypes come from the hoisted Constant's SQL type so a
        parameter-vector value is bit-identical to what ``jnp.full`` would
        have baked."""
        if not self._param_list:
            return None
        return tuple(
            jnp.asarray(v, dtype=t.storage_dtype) for v, t in self._param_list
        )

    def _store_program(self, program_key, sig, jf, meta) -> None:
        """Insert a traced program under (program_key, capacity signature).

        ``("frag", id, apply_exchange, id(root), unique)`` keys (and their
        ``("fused", ids, apply_exchange, root_ids, unique)`` counterparts) embed
        root-node identities because dynamic filtering rebuilds probe
        roots per execution; on a shared cross-query store those per-run
        keys would accumulate (each cached closure pins its root alive,
        keeping ids unique), so storing a new root's program evicts every
        entry for the same fragment(s) traced against a different — now
        unreachable — root.
        """
        if (
            isinstance(program_key, tuple)
            and len(program_key) == 5
            and program_key[0] in ("frag", "fused")
        ):
            prefix, rid = program_key[:3], program_key[3]
            stale = [
                k
                for k in self.programs
                if isinstance(k, tuple)
                and len(k) == 2
                and isinstance(k[0], tuple)
                and len(k[0]) == 5
                and k[0][:3] == prefix
                and k[0][3] != rid
            ]
            for k in stale:
                self.programs.pop(k, None)
        self.programs[(program_key, sig)] = (jf, meta)

    def _all_capacities(self) -> dict:
        """Flattened view of every grown capacity in the program store,
        for CapacityRetryExceeded diagnostics."""
        out: dict[str, int] = {}
        for key, val in self.programs.items():
            if (
                isinstance(key, tuple)
                and key
                and key[0] == "caps"
                and isinstance(val, _Caps)
            ):
                scope = ".".join(str(k) for k in key[1:])
                for nm, v in val.vals.items():
                    out[f"{scope}:{nm}"] = v
        return out

    # === skew / stats-seeding / observability ===========================

    def _skew_roles(self) -> dict[int, dict]:
        """Map producer-fragment id -> skew role for every partitioned
        (hash/hash) equi-join. The fragmenter cuts ``Join.left`` before
        ``Join.right``, so the probe producer always executes first — its
        exchange detects heavy hitters over the probe-side key hashes
        (build sides are typically near-unique, so probe frequencies are
        where Zipf skew is visible) and the build producer salts with the
        resulting hot set. SEMI/ANTI and single-row joins are left on the
        plain two-tier path."""
        roles = self.programs.get("__skewroles__")
        if roles is None:
            roles = {}
            sub = self.programs.get("__subplan__")
            if sub is not None and bool(self.session.get("skew_handling")):
                for frag in sub.all_fragments():
                    for node in P.walk_plan(frag.root):
                        if (
                            isinstance(node, P.Join)
                            and node.join_type in ("INNER", "LEFT")
                            and node.criteria
                            and not node.single_row
                            and isinstance(node.left, P.RemoteSource)
                            and node.left.exchange_type == "hash"
                            and isinstance(node.right, P.RemoteSource)
                            and node.right.exchange_type == "hash"
                        ):
                            roles[node.left.fragment_id] = {"role": "probe"}
                            roles[node.right.fragment_id] = {
                                "role": "build",
                                "peer": node.left.fragment_id,
                            }
            self.programs["__skewroles__"] = roles
        return roles

    def _history_sites(
        self, frag: PlanFragment, streamed: Optional[P.Aggregate] = None
    ) -> dict[str, str]:
        """Runtime capacity-site names → restart-stable names. The tracer
        mints sites as ``agg{id(node)}`` / ``join{id(node)}`` /
        ``semi{id(node)}`` — node ids churn across processes AND across
        dynamic-filter rewrites — so history keys them by kind, fragment
        id, and walk ordinal instead (``agg@3#0``), which is stable for a
        given fingerprint. ``semi`` sites are minted on Join nodes (the
        semi/mark-join exec path), so each Join registers both. Scan and
        filter sites carry no capacities — they exist so the operator
        row counters (the ``op!`` channel) key those nodes by the same
        restart-stable scheme."""
        sites = {
            f"exch{frag.id}": f"exch@{frag.id}",
            f"spill{frag.id}": f"spill@{frag.id}",
            f"hot{frag.id}": f"hot@{frag.id}",
        }
        agg_k = join_k = scan_k = filter_k = 0
        for node in P.walk_plan(frag.root):
            if isinstance(node, P.Aggregate):
                sites[f"agg{id(node)}"] = f"agg@{frag.id}#{agg_k}"
                agg_k += 1
            elif isinstance(node, P.Join):
                sites[f"join{id(node)}"] = f"join@{frag.id}#{join_k}"
                sites[f"ujoin{id(node)}"] = f"ujoin@{frag.id}#{join_k}"
                sites[f"semi{id(node)}"] = f"semi@{frag.id}#{join_k}"
                sites[f"densejoin{id(node)}"] = f"densejoin@{frag.id}#{join_k}"
                join_k += 1
            elif isinstance(node, P.TableScan):
                sites[f"opscan{id(node)}"] = f"scan@{frag.id}#{scan_k}"
                scan_k += 1
            elif isinstance(node, P.Filter):
                sites[f"opfilter{id(node)}"] = f"filter@{frag.id}#{filter_k}"
                filter_k += 1
        if streamed is not None:
            # exec/streaming.py names its aggregate's budget by content
            # at run time too
            stable = sites.pop(f"agg{id(streamed)}")
            sites[stable] = stable
        return sites

    def _seed_history(
        self,
        frag: PlanFragment,
        caps: "_Caps",
        streamed: Optional[P.Aggregate] = None,
    ) -> None:
        """History-seeded capacities: final observed shapes from earlier
        runs of this fingerprint floor the static estimates. Runs BEFORE
        ``_seed_caps`` — floors are first-wins, so observed truth beats a
        static guess. Grown sites seed floor-only (same contract as stats
        seeding: never shrink an engineered default); halved sites seed
        exactly — the larger shape failed to compile or allocate, and
        re-deriving that by retries is what history exists to avoid.
        Always registers the runtime→stable site map so the snapshot can
        persist capacities under restart-stable keys."""
        try:
            sites = self._history_sites(frag, streamed)
            caps.sites.update(sites)
            hcaps = (self.history or {}).get("capacities") or {}
            if not hcaps:
                return
            seeded = 0
            for runtime, stable in sites.items():
                ent = hcaps.get(stable)
                if not isinstance(ent, dict):
                    continue
                if runtime in caps.vals or runtime in caps._seed_floor:
                    continue
                prov = str(ent.get("provenance", ""))
                if runtime.startswith("ujoin") and not prov.endswith(
                    ("+grown", "+halved")
                ):
                    # a unique build's join is its probe's width by rule,
                    # which a bucketed floor would widen past a lookup
                    continue
                val = bucket_capacity(int(ent.get("value", 0)), minimum=1)
                if val <= 0:
                    continue
                caps.seed(
                    runtime,
                    val,
                    floor_only="+halved" not in prov,
                    provenance="history",
                )
                seeded += 1
            if seeded:
                from trino_tpu.obs.metrics import get_registry

                get_registry().counter(
                    "trino_tpu_history_seeds_total"
                ).inc(seeded)
        except Exception:  # noqa: BLE001 — seeding is best-effort
            pass

    def _seed_caps(self, frag: PlanFragment, caps: "_Caps") -> None:
        """Stats-seeded capacity defaults: planner NDV/row-count estimates
        pick realistic starting buckets per agg/join/exchange site, so
        cold runs skip the overflow-retry-recompile ladder. Site names use
        the (possibly dynamic-filter-rewritten) node ids of THIS trace, so
        stats are computed over the rewritten root; upstream fragment
        cardinalities come from the once-per-plan subplan stats."""
        if not bool(self.session.get("stats_capacity_seeding")):
            return
        try:
            from trino_tpu.planner import stats as PStats

            sub = self.programs.get("__subplan__")
            root_stats = self.programs.get("__fragstats__")
            if root_stats is None and sub is not None:
                root_stats = PStats.fragment_output_stats(sub, self.catalogs)
                self.programs["__fragstats__"] = root_stats
            calc = PStats.FragmentStatsCalculator(
                self.catalogs, root_stats or {}
            )
            n = max(int(self.mesh.devices.size), 1)
            for node in P.walk_plan(frag.root):
                if isinstance(node, P.Aggregate) and node.group_keys:
                    est = calc.stats(node).row_count
                    if est and est > 0:
                        groups = est / n if node.step == "final" else est
                        caps.seed(
                            f"agg{id(node)}",
                            min(
                                1 << 16,
                                bucket_capacity(
                                    max(256, int(4 * groups)), minimum=256
                                ),
                            ),
                            floor_only=True,
                        )
                elif (
                    isinstance(node, P.Join)
                    and node.criteria
                    and node.join_type in ("INNER", "LEFT")
                    and not node.single_row
                ):
                    est = calc.stats(node).row_count
                    if est and est > 0:
                        caps.seed(
                            f"join{id(node)}",
                            min(
                                1 << 20,
                                bucket_capacity(
                                    max(1024, int(4 * est) // n),
                                    minimum=1024,
                                ),
                            ),
                            floor_only=True,
                        )
            if frag.output_exchange == "hash":
                est = calc.stats(frag.root).row_count
                if est and est > 0:
                    # floor_only everywhere: stats may pre-grow a site the
                    # retry ladder would otherwise have to discover, but
                    # never shrink an engineered default — estimates miss
                    # per-shard amplification (partial-agg outputs exceed
                    # the fragment's global row count) and a low seed
                    # re-creates the overflow-retry-recompile ladder.
                    # Salted exchanges route the heavy mass off the cold
                    # path, so their cold seed is half the plain one
                    # (mirrors the salted default in apply_output_exchange)
                    mult = 1 if frag.id in self._skew_roles() else 2
                    caps.seed(
                        f"exch{frag.id}",
                        bucket_capacity(
                            max(64, int(mult * est) // (n * n)), minimum=64
                        ),
                        floor_only=True,
                    )
        except Exception:  # noqa: BLE001 — seeding is best-effort
            pass

    def _accumulate_exchange(self, names, vals, static) -> None:
        st = self.exchange_stats
        for k, v in (static or {}).items():
            st[k] = st.get(k, 0) + v
        for nm, v in zip(names or (), vals):
            if nm.startswith("sent"):
                st["shuffle_rows"] += int(v)
            elif nm.startswith("salted"):
                st["salted_rows"] += int(v)
            elif nm.startswith("hotkeys"):
                st["hot_keys"] += int(v)
            elif nm.startswith("op!"):
                # operator row counters: op!{kind}!{in|out}!{stable_site},
                # minted with the restart-stable site resolved at trace
                # time (deferred entries don't carry the _Caps site map)
                _, kind, io, site = nm.split("!", 3)
                ent = self.operator_stats.get(site)
                if ent is None:
                    ent = self.operator_stats[site] = {
                        "kind": kind,
                        "rows_in": 0,
                        "rows_out": 0,
                    }
                ent["rows_in" if io == "in" else "rows_out"] += int(v)

    def exchange_stats_snapshot(self) -> dict:
        """Finalized per-query exchange counters (engine attaches this to
        the statement result; /v1/query serves it as ``exchangeStats``)."""
        st = dict(self.exchange_stats)
        st["padding_ratio"] = round(
            st.get("padded_shuffle_rows", 0) / max(1, st.get("shuffle_rows", 0)),
            4,
        )
        caps: dict[str, dict] = {}
        join_strategy: dict[str, str] = {}
        history_seeds = 0
        for key, val in self.programs.items():
            if (
                isinstance(key, tuple)
                and key
                and key[0] == "caps"
                and isinstance(val, _Caps)
            ):
                scope = ".".join(str(k) for k in key[1:])
                for nm, v in val.vals.items():
                    prov = val.provenance.get(nm, "default")
                    caps[f"{scope}:{nm}"] = {
                        "value": v,
                        "provenance": prov,
                        # restart-stable name — what the history store
                        # keys this site by across processes
                        "site": val.sites.get(nm, nm),
                    }
                    if prov.startswith("history"):
                        history_seeds += 1
                for nm, traced in val.join_sites.items():
                    join_strategy[val.sites.get(nm, nm)] = traced["strategy"]
        st["capacities"] = caps
        # capacity sites whose value came from the observed-history store
        # (surfaced as queryStats.historySeeds on /v1/query)
        st["history_seeds"] = history_seeds
        # join engine v2: chosen kernel per join site (sort / dense /
        # matmul, including demotions observed during the retry ladder)
        st["joinStrategy"] = join_strategy
        if self.operator_stats:
            # per-operator row flow keyed by restart-stable site; batched
            # dispatches sum across stacked members (one program, K
            # queries), which the rollups document as combined flow
            st["operators"] = {
                site: dict(ent) for site, ent in self.operator_stats.items()
            }
        return st

    def ingest_stats_snapshot(self):
        """Per-query ingest counters plus the engine-wide device table
        cache state (entries/bytes/evictions), so /v1/query shows both
        what this query paid and what is HBM-resident for the next one."""
        snap = super().ingest_stats_snapshot()
        if snap is not None and self.table_cache is not None:
            snap["tableCache"] = self.table_cache.snapshot()
        return snap

    # === fragment scheduling ============================================

    def _execute_fragments(self, sub: SubPlan) -> tuple[Batch, list[str]]:
        import time as _time

        results: dict[int, Result] = {}
        names_holder: dict[int, list[str]] = {}
        units = self._fusion_units(sub)

        def run_units():
            for unit in units:
                fused = isinstance(unit, FusedFragment)
                if self.fault_injector is not None:
                    # fragment-level injection sites: deterministic per
                    # (seed, fragment id). A fused unit keeps one site per
                    # MEMBER so chaos schedules are identical with fusion
                    # on or off; in a worker's fused path the crash
                    # surfaces as a task failure (fused_strict) or a
                    # visible interpreter fallback
                    for fid in (
                        unit.fragment_ids if fused else (unit.id,)
                    ):
                        self.fault_injector.maybe_crash_task(f"frag:{fid}")
                if fused:
                    results[unit.id] = self._run_fused_unit(
                        unit, results, names_holder
                    )
                else:
                    results[unit.id] = self._run_fragment(
                        unit, results, names_holder
                    )

        # Optimistic overflow protocol: fragments enqueue their overflow
        # flags (device scalars) in `deferred_flags` instead of pulling
        # each one — a device->host pull costs a full runtime round trip,
        # so the whole query checks ALL flags in ONE transfer, and only
        # the (rare) overflow grows capacities and reruns.
        attempts = 0
        while True:
            attempts += 1
            if attempts > 12:
                raise CapacityRetryExceeded(
                    "fragmented-query",
                    fragment_id=sub.fragment.id,
                    capacities=self._all_capacities(),
                    attempts=attempts - 1,
                )
            self.deferred_flags = []
            self.deferred_counters = []
            results.clear()
            names_holder.clear()
            self._hot_sets.clear()
            run_units()
            root = results[sub.fragment.id]
            if jax.process_count() > 1:
                # multi-host: replicate the (small) root result so every
                # process holds it fully before host materialization
                from trino_tpu.parallel.mesh import replicated

                rep = jax.jit(
                    lambda b: b, out_shardings=replicated(self.mesh)
                )(root.batch)
                root = Result(rep, root.layout)
            deferred = self.deferred_flags
            dcounters = self.deferred_counters
            self.deferred_flags = None
            self.deferred_counters = None
            # the overflow flags (and exchange counters) ride the SAME
            # packed pull as the root batch (optimistic: the output of an
            # overflowed run is discarded and the query reruns with grown
            # budgets; counters only accumulate on the surviving attempt)
            extras = [
                jnp.ravel(f.astype(jnp.int32)) for _, _, f, _ in deferred
            ] + [jnp.ravel(c) for _, c, _ in dcounters if c is not None]
            with get_tracer().span(
                "device_pull",
                attrs={"extras": len(extras), "attempt": attempts},
            ):
                t_pull = _time.perf_counter()
                host_root, extra_vals = root.batch.to_host(extras=extras)
                pull_ms = (_time.perf_counter() - t_pull) * 1000.0
            get_registry().histogram("trino_tpu_device_pull_ms").observe(
                pull_ms
            )
            flag_vals = extra_vals[: len(deferred)]
            counter_vals = list(extra_vals[len(deferred):])
            overflowed = False
            span = get_tracer().current()
            if span is not None:
                span.set("meshDevices", int(self.mesh.devices.size))
            if span is not None and any(
                nm.startswith("agg") for _, names, _, _ in deferred for nm in names
            ):
                # one more run of every grouped aggregate of the plan
                span.add("aggAttempts")
            for (key, names, _, caps), seg in zip(deferred, flag_vals):
                seg = np.atleast_1d(np.asarray(seg))
                for nm, fl in zip(names, seg):
                    if fl:
                        overflowed = True
                        grow_or_raise(nm, caps, int(fl))
                # the overflowed program stays in the store: its key
                # carries the capacity signature it was traced at, so the
                # grown rerun traces fresh while a later same-sized query
                # (or regrow ladder revisit) still reuses it
            if not overflowed:
                for names, stacked, static in dcounters:
                    vals = (
                        np.atleast_1d(np.asarray(counter_vals.pop(0)))
                        if stacked is not None
                        else ()
                    )
                    self._accumulate_exchange(names, vals, static)
                root = Result(host_root, root.layout)
                break
            self.exchange_stats["overflow_retries"] += 1
        out = root.batch.compact()
        names = names_holder.get(sub.fragment.id) or [
            s.name for s in sub.fragment.root.output_symbols
        ]
        return out, names

    def _df_build_lookup(self, results: dict[int, Result]):
        """Dynamic-filter domain accessor over completed fragment results
        (None for fragments that haven't materialized — e.g. fused-unit
        interiors — or for cross-host sharded intermediates)."""

        def build_lookup(fid):
            res = results.get(fid)
            if res is None:
                return None
            if jax.process_count() > 1:
                # intermediate fragment results are sharded across hosts;
                # host-side domains would need a collective — skip
                return None
            sel = np.asarray(res.batch.selection_mask())

            def get_column(name):
                idx = res.layout.get(name)
                if idx is None:
                    return None
                c = res.batch.columns[idx]
                return c.data, np.asarray(c.valid_mask()) & sel

            return get_column, int(sel.sum())

        return build_lookup

    # === whole-pipeline fusion ==========================================

    def _fusion_units(self, sub: SubPlan) -> list:
        """Bottom-up execution units: :class:`FusedFragment` groups where
        pipeline fusion applies, plain fragments elsewhere. Cached per
        plan entry — the grouping references fragment identities, so like
        the subplan itself it must be stable across executions."""
        units = self.programs.get("__fusedunits__")
        if units is None:
            if bool(self.session.get("pipeline_fusion")):
                blocked = set(self._fusion_blocked(sub))
                if bool(self.session.get("enable_dynamic_filtering")):
                    # a selective broadcast build must stay a fragment
                    # boundary: worker-side dynamic filtering prunes the
                    # probe from the MATERIALIZED build, which a fused
                    # interior member never produces
                    blocked |= filtered_broadcast_fids(sub)
                units = fuse_groups(
                    sub,
                    fusable=fragment_fusable,
                    max_fragments=max(
                        1, int(self.session.get("fusion_max_fragments"))
                    ),
                    blocked=frozenset(blocked),
                    skew_pairs=(
                        partitioned_join_pairs(sub)
                        if bool(self.session.get("skew_handling"))
                        else ()
                    ),
                    # star joins: absorb broadcast dim builds so a fact
                    # chain probes every dim in ONE program (the traced
                    # broadcast link replicates in-trace)
                    broadcast_links=bool(self.session.get("dense_join")),
                )
            else:
                units = []

                def visit(sp: SubPlan):
                    for child in sp.children:
                        visit(child)
                    units.append(sp.fragment)

                visit(sub)
            self.programs["__fusedunits__"] = units
        return units

    def _graceful_overflow(self) -> bool:
        """True when the dense join tier's graceful overflow is active:
        a spill-sized join input can stay on the compiled path because a
        build-table overflow re-hashes at doubled capacity inside the
        retry ladder (``densejoin@…`` sites) instead of needing the
        interpreter's partitioned spill — so the spill threshold stops
        barring fragments from fusion and from the compiled path."""
        return bool(self.session.get("dense_join")) and str(
            self.session.get("join_strategy") or "auto"
        ).lower() != "sort"

    def _fusion_blocked(self, sub: SubPlan) -> set:
        """Fragment ids that must stay on the per-fragment path: scans
        big enough for the streaming chunk loop (bounded memory beats one
        materialized program) or for the interpreter's spill fallback.
        Estimate-based, mirroring the per-fragment gates; tables without
        estimates are discovered at materialization time and fall back
        via FusedUnsupported instead."""
        from trino_tpu.exec.streaming import streamable_chain

        blocked: set[int] = set()
        stream_threshold = int(
            self.session.get("stream_scan_threshold_rows")
        )
        spill_threshold = (
            int(self.session.get("spill_threshold_rows"))
            if self.session.get("spill_enabled")
            and not self._graceful_overflow()
            else None
        )
        for frag in sub.all_fragments():
            chain = streamable_chain(frag.root)
            stream_scan = chain[1] if chain is not None else None
            for n in P.walk_plan(frag.root):
                if not isinstance(n, P.TableScan):
                    continue
                try:
                    est = self.catalogs.get(n.catalog).estimate_rows(
                        n.schema, n.table
                    )
                except Exception:  # noqa: BLE001 — treat as unknown
                    est = None
                if est is None:
                    continue
                if n is stream_scan and est > stream_threshold:
                    blocked.add(frag.id)
                if spill_threshold is not None and est > spill_threshold:
                    blocked.add(frag.id)
        return blocked

    def _run_fused_unit(
        self,
        unit: FusedFragment,
        results: dict[int, Result],
        names_holder: dict[int, list[str]],
    ) -> Result:
        span = get_tracer().start_span(
            "fused_execute",
            attrs={"stage": unit.id, "fragments": len(unit.fragments)},
        )
        try:
            with span:
                return self._run_fused_spanned(
                    unit, results, names_holder, span
                )
        except (FusedUnsupported, CapacityRetryExceeded):
            # bit-identical fallback: run the members as the ordinary
            # per-fragment dispatches the grouping pass replaced (a member
            # that is itself ineligible — e.g. a spill-sized input found
            # only at materialization — then escalates to the interpreter
            # exactly as before)
            for frag in unit.fragments:
                results[frag.id] = self._run_fragment(
                    frag, results, names_holder
                )
            return results[unit.id]

    def _run_fused_spanned(
        self,
        unit: FusedFragment,
        results: dict[int, Result],
        names_holder: dict[int, list[str]],
        span,
    ) -> Result:
        import time as _time

        t0 = _time.perf_counter()
        from trino_tpu.dynfilter import fragment_dynamic_filters

        member_ids = set(unit.fragment_ids)
        # dynamic filtering sees only OUTSIDE-unit build results (interior
        # producers haven't run — they exist solely inside the trace);
        # lookups for them return None, which the rewrite treats as
        # "domain unavailable", a pure pruning loss, never a wrong result
        lookup = self._df_build_lookup(results)
        members = []
        for frag in unit.fragments:
            root = fragment_dynamic_filters(
                frag.root, lookup, self.session, self.dynamic_filters,
                memo=self.programs, memo_key=("dfroot", frag.id),
            )
            members.append(dataclasses.replace(frag, root=root))

        inputs: dict[str, Any] = {}
        input_layouts: dict[str, dict[str, int]] = {}
        spill_threshold = (
            int(self.session.get("spill_threshold_rows"))
            if self.session.get("spill_enabled")
            and not self._graceful_overflow()
            else None
        )
        for frag in members:
            for n in P.walk_plan(frag.root):
                if isinstance(n, P.TableScan):
                    res = self._exec_tablescan(n)
                    if (
                        spill_threshold is not None
                        and res.batch.capacity > spill_threshold
                    ):
                        raise FusedUnsupported("spill-sized input")
                    inputs[f"scan{id(n)}"] = res.batch
                    input_layouts[f"scan{id(n)}"] = res.layout
                elif (
                    isinstance(n, P.RemoteSource)
                    and n.fragment_id not in member_ids
                ):
                    r = results[n.fragment_id]
                    inputs[f"remote{n.fragment_id}"] = r.batch
                    input_layouts[f"remote{n.fragment_id}"] = r.layout
                elif isinstance(n, P.Output):
                    names_holder[frag.id] = list(n.column_names)
        # the unit ROOT's own output exchange may pair with an
        # outside-unit peer (the grouping pass keeps in-unit pairs whole,
        # so only the root can face an external probe/build mate)
        skew = None
        role = self._skew_roles().get(unit.id)
        if role is not None:
            if role["role"] == "probe":
                skew = {
                    "detect": (
                        max(1, int(self.session.get("skew_hot_k"))),
                        float(self.session.get("skew_hot_threshold_frac")),
                    )
                }
            else:
                hs = self._hot_sets.get(role["peer"])
                if hs is not None:
                    skew = {"salt": True}
                    inputs["__hotset__"] = (hs[0], hs[1])
        sink = {} if self.stats_collector is not None else None
        out = self.run_fused_program(
            members, inputs, input_layouts, stats_sink=sink, defer=True,
            skew=skew,
        )
        aux = getattr(self, "_last_aux", ())
        if aux:
            self._hot_sets[unit.id] = aux
        span.set("mode", "fused-pipeline")
        self._note_exchange(span)
        self._note_joins(span, "fused", tuple(unit.fragment_ids))
        if sink:
            span.set("attempts", sink.get("attempts", 1))
        get_registry().counter("trino_tpu_fused_programs_total").inc()
        if self.stats_collector is not None:
            self.stats_collector.record_fragment(
                unit.id,
                {
                    "mode": "fused-pipeline",
                    "fragments": list(unit.fragment_ids),
                    "wall_s": _time.perf_counter() - t0,
                    "attempts": (sink or {}).get("attempts", 1),
                    "input_rows": (sink or {}).get("input_rows", 0),
                    "output_rows": int(
                        np.asarray(out.batch.selection_mask()).sum()
                    ),
                },
            )
        return out

    def _run_fragment(
        self,
        frag: PlanFragment,
        results: dict[int, Result],
        names_holder: dict[int, list[str]],
    ) -> Result:
        # span per fragment execution; program_compile / exchange spans
        # emitted inside parent to it via the ambient stack
        span = get_tracer().start_span(
            "fragment_execute", attrs={"stage": frag.id}
        )
        with span:
            return self._run_fragment_spanned(
                frag, results, names_holder, span
            )

    def _run_fragment_spanned(
        self,
        frag: PlanFragment,
        results: dict[int, Result],
        names_holder: dict[int, list[str]],
        span,
    ) -> Result:
        import time as _time

        t0 = _time.perf_counter()
        streamed = self._try_streaming(frag, names_holder, results)
        if streamed is not None:
            span.set("mode", "streamed")
            if self.stats_collector is not None:
                self.stats_collector.record_fragment(
                    frag.id,
                    {
                        "mode": "streamed",
                        "wall_s": _time.perf_counter() - t0,
                        "output_rows": int(
                            np.asarray(streamed.batch.selection_mask()).sum()
                        ),
                    },
                )
            return streamed
        # dynamic filtering: completed build fragments prune this
        # fragment's probe scans before any input materializes
        from trino_tpu.dynfilter import fragment_dynamic_filters

        root = fragment_dynamic_filters(
            frag.root,
            self._df_build_lookup(results),
            self.session,
            self.dynamic_filters,
            memo=self.programs,
            memo_key=("dfroot", frag.id),
        )
        frag = dataclasses.replace(frag, root=root)

        inputs: dict[str, Batch] = {}
        input_layouts: dict[str, dict[str, int]] = {}
        spill_threshold = (
            int(self.session.get("spill_threshold_rows"))
            if self.session.get("spill_enabled")
            and not self._graceful_overflow()
            else None
        )
        for n in P.walk_plan(frag.root):
            if isinstance(n, P.TableScan):
                res = self._exec_tablescan(n)  # sharded host->device read
                if spill_threshold is not None and res.batch.capacity > spill_threshold:
                    # working set beyond the spill threshold: defer to the
                    # interpreter, which has the partitioned-spill path
                    raise FusedUnsupported("spill-sized input")
                inputs[f"scan{id(n)}"] = res.batch
                input_layouts[f"scan{id(n)}"] = res.layout
            elif isinstance(n, P.RemoteSource):
                res = results[n.fragment_id]
                inputs[f"remote{n.fragment_id}"] = res.batch
                input_layouts[f"remote{n.fragment_id}"] = res.layout
            elif isinstance(n, P.Output):
                names_holder[frag.id] = list(n.column_names)
        # skew handling: the probe-side producer of a partitioned join
        # detects heavy hitters inside its exchange program and exports
        # the hot-key tables; the build-side producer (which runs after
        # it) receives them as a traced input and salts its exchange
        skew = None
        role = self._skew_roles().get(frag.id)
        if role is not None:
            if role["role"] == "probe":
                skew = {
                    "detect": (
                        max(1, int(self.session.get("skew_hot_k"))),
                        float(self.session.get("skew_hot_threshold_frac")),
                    )
                }
            else:
                hs = self._hot_sets.get(role["peer"])
                if hs is not None:
                    skew = {"salt": True}
                    inputs["__hotset__"] = (hs[0], hs[1])
        sink = {} if self.stats_collector is not None else None
        out = self.run_fragment_program(
            frag, inputs, input_layouts, stats_sink=sink, defer=True,
            skew=skew,
        )
        aux = getattr(self, "_last_aux", ())
        if aux:
            self._hot_sets[frag.id] = aux
        span.set("mode", "fused")
        self._note_exchange(span)
        self._note_joins(span, frag.id)
        if sink:
            span.set("attempts", sink.get("attempts", 1))
        if self.stats_collector is not None:
            self.stats_collector.record_fragment(
                frag.id,
                {
                    "mode": "fused",
                    "wall_s": _time.perf_counter() - t0,
                    "attempts": sink.get("attempts", 1),
                    "input_rows": sink.get("input_rows", 0),
                    "output_rows": int(
                        np.asarray(out.batch.selection_mask()).sum()
                    ),
                },
            )
        return out

    def _try_streaming(
        self,
        frag: PlanFragment,
        names_holder: dict[int, list[str]],
        results: Optional[dict] = None,
    ) -> Optional[Result]:
        """Scan→agg(→join) fragments over large tables run as a bounded
        chunk loop (exec/streaming.py) instead of materializing the
        probe table; join build sides materialize once up front."""
        from trino_tpu.exec.streaming import (
            StreamingAggregator,
            StreamOverflow,
            streamable_chain,
        )

        chain = streamable_chain(frag.root)
        if chain is None:
            return None
        agg, scan, build_roots = chain
        connector = self.catalogs.get(scan.catalog)
        est = connector.estimate_rows(scan.schema, scan.table)
        if est is None or est <= int(
            self.session.get("stream_scan_threshold_rows")
        ):
            return None
        # build-side inputs: scans materialize now (bounded by the spill
        # threshold — bigger builds go to the interpreter's spill path),
        # remote sources come from completed upstream fragments
        build_inputs: dict[str, Batch] = {}
        build_layouts: dict[str, dict[str, int]] = {}
        build_bound = int(self.session.get("spill_threshold_rows"))
        for root in build_roots:
            for n in P.walk_plan(root):
                if isinstance(n, P.TableScan):
                    bconn = self.catalogs.get(n.catalog)
                    best = bconn.estimate_rows(n.schema, n.table)
                    if best is not None and best > build_bound:
                        return None
                    bres = self._exec_tablescan(n)
                    build_inputs[f"scan{id(n)}"] = bres.batch
                    build_layouts[f"scan{id(n)}"] = bres.layout
                elif isinstance(n, P.RemoteSource):
                    upstream = (results or {}).get(n.fragment_id)
                    if upstream is None:
                        return None
                    build_inputs[f"remote{n.fragment_id}"] = upstream.batch
                    build_layouts[f"remote{n.fragment_id}"] = upstream.layout
        caps = self.programs.setdefault(("caps", "stream", frag.id), _Caps())
        self._seed_history(frag, caps, streamed=agg)
        attempts = 0
        while True:
            attempts += 1
            if attempts > 12:
                raise CapacityRetryExceeded(
                    "streaming",
                    fragment_id=frag.id,
                    capacities=caps.vals,
                    attempts=attempts - 1,
                )
            try:
                res = StreamingAggregator(
                    self, frag, agg, scan, caps,
                    build_roots=build_roots,
                    build_inputs=build_inputs,
                    build_layouts=build_layouts,
                ).run()
                break
            except StreamOverflow as e:
                for nm, need in e.needs.items():
                    grow_or_raise(nm, caps, need)
        if isinstance(frag.root, P.Output):
            names_holder[frag.id] = list(frag.root.column_names)
            cols = [res.column(s) for s in frag.root.symbols]
            res = Result(
                Batch(cols, res.batch.capacity, res.batch.sel),
                {s.name: i for i, s in enumerate(frag.root.symbols)},
            )
        if frag.output_exchange in (None, "single") or self.mesh.devices.size == 1:
            # (one device: the exchange is the identity, see
            # ``apply_output_exchange``)
            return res
        # apply the fragment's output exchange as its own small program

        def build_post(meta: _Meta):
            def post(batch):
                tracer = _FragmentTracer(self, {}, {}, caps)
                out = tracer.apply_output_exchange(
                    frag, Result(batch, res.layout)
                )
                tracer.exchange_static["dispatchRoundTrips"] = 1
                meta.capture(out, tracer)
                return meta.outputs(out)

            return post

        with get_tracer().span("exchange") as span:
            out = self._retry_traced(
                caps, build_post, (res.batch,), program_key=("post", frag.id),
                defer=True,
            )
            self._note_exchange(span, frag.output_exchange)
        return out

    def _note_exchange(self, span, kind: Optional[str] = None) -> None:
        """The exchanges of the program ``_retry_traced`` ran last, on the
        span that dispatched it: ``exchange`` where the exchange is a program
        of its own, else the fused program's. ``rows`` and ``bytes`` are what
        the mesh's devices put on the wire, padding included (static; the
        live rows come with the deferred pull: ``exchangeStats.shuffle_rows``)."""
        static = getattr(self, "_last_exchange_static", None) or {}
        if kind is None and not static.get("exchanges"):
            return
        span.set("kind", kind or "hash")
        span.set("exchanges", static.get("exchanges", 0))
        span.set("rows", static.get("padded_shuffle_rows", 0))
        span.set("bytes", static.get("shuffle_bytes", 0))
        span.set("devices", int(self.mesh.devices.size))

    def _note_joins(self, span, *caps_key) -> None:
        """``joins`` on the span that covers a program with join sites:
        per site the kernel ``_join_strategy`` chose and the static
        capacities it chose at (``_Caps.joins``), as the program
        ``_retry_traced`` ran last traced them, a stored program too."""
        caps = self.programs.get(("caps",) + caps_key)
        traced = getattr(self, "_last_joins", None) or {}
        joins = caps.joins(traced=traced) if caps is not None else []
        if joins:
            span.set("joins", joins)

    def _retry_traced(
        self,
        caps: "_Caps",
        build_fn,
        args: tuple,
        stats_sink: Optional[dict] = None,
        input_rows: int = 0,
        program_key=None,
        defer: bool = False,
    ) -> Result:
        """Run a traced program under the capacity-overflow retry protocol
        and materialize its Result. ``build_fn(meta)`` returns the function
        to jit; it must call ``meta.capture`` and return ``meta.outputs``.

        ``program_key`` (optional) reuses the jitted program + meta from
        ``self.programs`` across queries on the same cached plan. Entries
        are stored under ``(program_key, caps.signature())`` — the
        capacity signature the program was traced at — so the overflow
        ladder never serves a stale-capacity program AND any signature
        seen before (by this query's regrow ladder or an earlier query on
        the shared store) is reused instead of retraced.

        With ``defer=True`` (fragments inside ``_execute_fragments``) the
        overflow flags are NOT pulled here: they are queued as device
        scalars on ``self.deferred_flags`` and the whole query checks them
        in one transfer; the outer loop grows ``caps`` and reruns.
        """
        import time as _time

        self._last_aux = ()
        attempts = 0
        while True:
            attempts += 1
            if attempts > 12:
                raise CapacityRetryExceeded(
                    "traced-program",
                    fragment_id=(
                        # keys are ("frag", frag.id, ...) / ("post", frag.id)
                        program_key[1]
                        if isinstance(program_key, tuple)
                        and len(program_key) >= 2
                        else None
                    ),
                    capacities=caps.vals,
                    attempts=attempts - 1,
                )
            cached = (
                self.programs.get((program_key, caps.signature()))
                if program_key is not None
                else None
            )
            traced_now = cached is None
            store_stats = (
                self.programs.setdefault(
                    "__stats__",
                    {"hits": 0, "misses": 0, "trace_count": 0,
                     "compile_ms": 0.0},
                )
                if program_key is not None
                else None
            )
            if cached is not None:
                jf, meta = cached
                self.compile_stats["program_cache_hits"] += 1
                store_stats["hits"] += 1
            else:
                meta = _Meta()
                jf = jax.jit(build_fn(meta))
                if program_key is not None:
                    self.compile_stats["program_cache_misses"] += 1
                    store_stats["misses"] += 1
            t0 = _time.perf_counter()
            outs = None
            # a program's first call traces, lowers and compiles (or loads
            # from the disk cache) before it dispatches: the span holds it
            compile_span = (
                get_tracer().span(
                    "program_compile",
                    attrs={
                        "key": repr(program_key) if program_key else None,
                        "attempt": attempts,
                    },
                )
                if traced_now
                else contextlib.nullcontext()
            )
            with compile_span:
                if self._device_profiling:
                    # AOT-compile the SAME jitted function and execute through
                    # the resulting executable: identical program (bit-identical
                    # results, no double compile), but the Compiled object
                    # additionally exposes XLA's cost/memory analysis
                    if traced_now:
                        try:
                            compiled = jf.lower(*args).compile()
                            meta.aot = compiled
                            from trino_tpu.obs.profiler import (
                                capture_device_stats,
                            )

                            meta.device_stats = capture_device_stats(compiled)
                        except Exception:  # noqa: BLE001 — degrade to plain jit
                            meta.aot = None
                    if meta.aot is not None:
                        try:
                            outs = meta.aot(*args)
                        except Exception:  # noqa: BLE001 — e.g. new input
                            # shapes on a warm hit: jf(*args) below retraces
                            # transparently, exactly as the unprofiled path does
                            meta.aot = None
                            outs = None
                if outs is None:
                    try:
                        outs = jf(*args)
                    except Exception as e:  # noqa: BLE001 — inspect and rethrow
                        if not _is_resource_exhausted(e) or not caps.shrink_all():
                            raise
                        # the program failed to COMPILE (scoped-vmem / HBM
                        # exhaustion) before any overflow flag could run:
                        # enter the same retry ladder as row overflow,
                        # inverted — halve every capacity and retrace smaller
                        self.exchange_stats["compile_halvings"] = (
                            self.exchange_stats.get("compile_halvings", 0) + 1
                        )
                        get_registry().counter(
                            "trino_tpu_compile_halvings_total"
                        ).inc()
                        get_tracer().record(
                            "compile_halving", 0.0,
                            attrs={
                                "key": repr(program_key) if program_key else None,
                                "attempt": attempts,
                            },
                        )
                        continue
            data, sel, flags, counters, aux = outs
            compile_ms = 0.0
            if traced_now:
                # trace + lower + (XLA or disk-cache) compile happen
                # synchronously inside the first call; execution itself
                # dispatches async, so this wall time ≈ compile cost
                compile_ms = (_time.perf_counter() - t0) * 1000.0
                self.compile_stats["trace_count"] += 1
                self.compile_stats["compile_ms"] += compile_ms
                if store_stats is not None:
                    store_stats["trace_count"] += 1
                    store_stats["compile_ms"] = round(
                        store_stats["compile_ms"] + compile_ms, 3
                    )
                get_registry().histogram(
                    "trino_tpu_program_compile_ms"
                ).observe(compile_ms)
            if self._device_profiling and program_key is not None:
                self._record_device_stats(
                    program_label(program_key), meta.device_stats, compile_ms
                )
            self._last_aux = aux
            self._last_exchange_static = meta.exchange_static or {}
            self._last_joins = meta.joins or {}
            if defer and getattr(self, "deferred_flags", None) is not None:
                if flags:
                    stacked = jnp.stack([jnp.reshape(f, ()) for f in flags])
                    self.deferred_flags.append(
                        (program_key, list(meta.overflow_names), stacked, caps)
                    )
                if (counters or meta.exchange_static) and getattr(
                    self, "deferred_counters", None
                ) is not None:
                    cstack = (
                        jnp.stack([jnp.reshape(c, ()) for c in counters])
                        if counters
                        else None
                    )
                    self.deferred_counters.append(
                        (
                            list(meta.counter_names),
                            cstack,
                            dict(meta.exchange_static),
                        )
                    )
                if program_key is not None and traced_now:
                    # keyed by the POST-trace signature: tracing filled in
                    # any capacities this program consults via caps.get
                    self._store_program(program_key, caps.signature(), jf, meta)
                if stats_sink is not None:
                    stats_sink.setdefault("attempts", 0)
                    stats_sink["attempts"] += 1
                    stats_sink["last_wall_s"] = _time.perf_counter() - t0
                    stats_sink["input_rows"] = input_rows
                break
            # ONE device->host pull for all overflow flags: each separate
            # scalar transfer pays the full runtime round-trip latency
            if flags:
                stacked = jnp.stack([jnp.reshape(f, ()) for f in flags])
                flags_np = [int(x) for x in np.asarray(stacked)]
            else:
                flags_np = []
            if stats_sink is not None:
                jax.block_until_ready(sel)
                stats_sink.setdefault("attempts", 0)
                stats_sink["attempts"] += 1
                stats_sink["last_wall_s"] = _time.perf_counter() - t0
                stats_sink["input_rows"] = input_rows
            if not any(flags_np):
                if program_key is not None and traced_now:
                    self._store_program(program_key, caps.signature(), jf, meta)
                if counters or meta.exchange_static:
                    vals = (
                        np.atleast_1d(
                            np.asarray(
                                jnp.stack(
                                    [jnp.reshape(c, ()) for c in counters]
                                )
                            )
                        )
                        if counters
                        else ()
                    )
                    self._accumulate_exchange(
                        meta.counter_names, vals, meta.exchange_static
                    )
                break
            self.exchange_stats["overflow_retries"] += 1
            for nm, f in zip(meta.overflow_names, flags_np):
                if f:
                    grow_or_raise(nm, caps, f)
        if meta.batch_size:
            # batched program: data/sel are tuples over the K members —
            # demux into one Result per member (all members share the
            # column meta and layout captured at trace time, since they
            # are copies of one program)
            out = []
            for mdata, msel in zip(data, sel):
                cols = [
                    Column(t, d, v, dictionary)
                    for (d, v), (t, dictionary) in zip(
                        mdata, meta.column_meta
                    )
                ]
                cap = cols[0].data.shape[0] if cols else int(msel.shape[0])
                out.append(Result(Batch(cols, cap, msel), meta.layout))
            return out
        cols = [
            Column(t, d, v, dictionary)
            for (d, v), (t, dictionary) in zip(data, meta.column_meta)
        ]
        # zero-column fragments (count(*) over pruned scans) still carry
        # row liveness in sel
        cap = cols[0].data.shape[0] if cols else int(sel.shape[0])
        return Result(Batch(cols, cap, sel), meta.layout)

    def _unique_builds(self, frags, inputs, input_layouts) -> dict[int, bool]:
        """The sort-merge INNER or LEFT joins of ``frags`` whose build side is
        an argument of the program, made before it (a ``RemoteSource``'s
        batch, or a scan with no operator above it): by ``id`` of that build
        side, whether the join's key is unique among the batch's live rows.
        Computed on the device and pulled in one transfer before the program
        is looked up, which keys it: ``_exec_join`` then gives such a join
        its probe's width and runs it as a lookup, the rule of the slab
        step's build sides (``exec/streaming.py``)."""
        if not sort_joins_only(self.session):
            return {}
        builds, sides = [], []
        for frag in frags:
            for node in P.walk_plan(frag.root):
                if not (
                    isinstance(node, P.Join)
                    and node.join_type in ("INNER", "LEFT")
                    and node.criteria
                    and not node.single_row
                ):
                    continue
                build = node.right
                if isinstance(build, P.RemoteSource):
                    name = f"remote{build.fragment_id}"
                elif isinstance(build, P.TableScan):
                    name = f"scan{id(build)}"
                else:
                    continue
                batch = inputs.get(name)
                if not isinstance(batch, Batch):
                    continue  # made inside this very program
                layout = input_layouts[name]
                if isinstance(build, P.RemoteSource):
                    layout = remote_layout(build, layout)
                cols = [
                    batch.columns[layout[rs.name]] for _, rs in node.criteria
                ]
                builds.append(id(build))
                sides.append((
                    tuple((c.data, c.valid) for c in cols),
                    batch.sel,
                    batch.num_rows,
                ))
        if not sides:
            return {}
        flags = np.asarray(_unique_flags(tuple(sides)))
        return dict(zip(builds, (bool(f) for f in flags)))

    def run_fragment_program(
        self,
        frag: PlanFragment,
        inputs: dict[str, Batch],
        input_layouts: dict[str, dict[str, int]],
        apply_exchange: bool = True,
        stats_sink: Optional[dict] = None,
        defer: bool = False,
        skew: Optional[dict] = None,
    ) -> Result:
        """Compile + run one fragment as a single jitted SPMD program.

        ``inputs`` maps ``scan{id(node)}`` / ``remote{fragment_id}`` keys to
        device batches. With ``apply_exchange=False`` the fragment's output
        exchange is skipped — callers that ship pages across processes
        (worker tasks) partition on the host instead. ``stats_sink``
        receives per-fragment compile/run timings when provided. ``skew``
        configures the output exchange's skew handling (see
        ``_FragmentTracer.apply_output_exchange``); the hot-key tables
        themselves travel as the ``__hotset__`` input so cached programs
        never bake a stale hot set in as constants.
        """
        caps = self.programs.setdefault(("caps", frag.id), _Caps())
        self._seed_history(frag, caps)
        self._seed_caps(frag, caps)
        unique = self._unique_builds([frag], inputs, input_layouts)
        pvec = self._param_arrays()
        if pvec is not None:
            # hoisted literals ride as device-scalar jit inputs: literal
            # variants of the same canonical plan reuse the traced program
            inputs = dict(inputs)
            inputs["__params__"] = pvec

        def build(meta: _Meta):
            def fn(inp: dict[str, Batch]):
                tracer = _FragmentTracer(
                    self, inp, input_layouts, caps, skew=skew
                )
                tracer.unique_builds.update(unique)
                res = tracer._exec(frag.root)
                if apply_exchange:
                    res = tracer.apply_output_exchange(frag, res)
                # every execution of this program is one dispatch
                # round-trip; the static rides the counter protocol so
                # only the surviving (non-overflowed) attempt counts
                tracer.exchange_static["dispatchRoundTrips"] = 1
                meta.capture(res, tracer)
                return meta.outputs(res)

            return fn

        return self._retry_traced(
            caps,
            build,
            (inputs,),
            stats_sink=stats_sink,
            input_rows=sum(
                b.capacity for b in inputs.values() if isinstance(b, Batch)
            ),
            # the rewritten root's identity is part of the key: dynamic
            # filtering rebuilds fragment nodes per attempt, and a program
            # traced against old node ids must not serve new inputs (the
            # cached closure pins the old root alive, so its id is unique)
            program_key=(
                "frag", frag.id, apply_exchange, id(frag.root),
                tuple(unique.values()),
            ),
            defer=defer,
        )

    def run_fused_program(
        self,
        frags: Sequence[PlanFragment],
        inputs: dict[str, Any],
        input_layouts: dict[str, dict[str, int]],
        apply_exchange: bool = True,
        stats_sink: Optional[dict] = None,
        defer: bool = False,
        skew: Optional[dict] = None,
    ) -> Result:
        """Compile + run a CHAIN of exchange-connected fragments as ONE
        jitted SPMD program — the whole-pipeline fusion path.

        ``frags`` is in bottom-up execution order (producers first, the
        consumer root LAST). ``inputs`` holds only EXTERNAL feeds: table
        scans of every member plus ``remote{fid}`` batches from producers
        outside the unit; interior exchange links never leave the device —
        each producer's output exchange lowers to in-program collectives
        (``skewed_repartition``'s all_to_all/all_gather) and feeds the
        consumer's RemoteSource as a traced value. ``skew`` configures the
        ROOT member's output exchange; in-unit partitioned-join pairs
        detect and salt entirely in-trace, hot-set tables passing from the
        probe member's exchange to the build member's without ever
        becoming a jit input. One program = one dispatch round-trip,
        whatever the member count.
        """
        frags = list(frags)
        fids = tuple(f.id for f in frags)
        member_ids = set(fids)
        caps = self.programs.setdefault(("caps", "fused", fids), _Caps())
        for f in frags:
            self._seed_history(f, caps)
            self._seed_caps(f, caps)
        unique = self._unique_builds(frags, inputs, input_layouts)
        pvec = self._param_arrays()
        if pvec is not None:
            inputs = dict(inputs)
            inputs["__params__"] = pvec
        # in-unit skew roles (host-side, static): the grouping pass
        # absorbs partitioned-join pairs atomically, so an interior
        # member's peer is always a member too; only the root can face an
        # external mate (handled by the caller through ``skew``)
        roles = self._skew_roles()
        member_skew: dict[int, dict] = {}
        for fid in fids[:-1]:
            role = roles.get(fid)
            if role is None:
                continue
            if role["role"] == "probe":
                member_skew[fid] = {
                    "detect": (
                        max(1, int(self.session.get("skew_hot_k"))),
                        float(self.session.get("skew_hot_threshold_frac")),
                    )
                }
            elif role["peer"] in member_ids:
                member_skew[fid] = {"salt": True, "peer": role["peer"]}

        def build(meta: _Meta):
            def fn(inp: dict[str, Any]):
                avail = dict(inp)
                layouts = dict(input_layouts)
                combined = _TracerSummary()
                hot_sets: dict[int, tuple] = {}
                res = None
                tracer = None
                for frag in frags:
                    last = frag is frags[-1]
                    mskew = member_skew.get(frag.id)
                    if mskew is not None and mskew.get("salt"):
                        hs = hot_sets.get(mskew["peer"])
                        if hs is None:
                            mskew = None
                        else:
                            # in-trace hot-set handoff: the probe member
                            # ran earlier in this same trace (fragmenter
                            # cuts Join.left first, so bottom-up order
                            # puts the probe before its build mate). The
                            # handoff key is peer-scoped: the plain
                            # "__hotset__" slot belongs to the CALLER
                            # (the root may salt against an external
                            # probe), and a unit can hold several pairs
                            key = f"__hotset__{mskew['peer']}"
                            avail = dict(avail)
                            avail[key] = (hs[0], hs[1])
                            mskew = {"salt": True, "hotset_key": key}
                    if last:
                        mskew = skew
                    tracer = _FragmentTracer(
                        self, avail, layouts, caps, skew=mskew
                    )
                    tracer.unique_builds.update(unique)
                    res = tracer._exec(frag.root)
                    if not last or apply_exchange:
                        res = tracer.apply_output_exchange(frag, res)
                    combined.absorb(tracer)
                    if tracer.aux_out:
                        hot_sets[frag.id] = tracer.aux_out
                    if not last:
                        avail = dict(avail)
                        layouts = dict(layouts)
                        avail[f"remote{frag.id}"] = res.batch
                        layouts[f"remote{frag.id}"] = res.layout
                # one program = one dispatch, whatever the member count;
                # fusedFragments rides the same surviving-attempt protocol
                combined.exchange_static["dispatchRoundTrips"] = 1
                combined.exchange_static["fusedFragments"] = len(frags)
                # only the ROOT's hot set leaves the program (interior
                # probes' tables were consumed in-trace above)
                combined.aux_out = tracer.aux_out
                meta.capture(res, combined)
                return meta.outputs(res)

            return fn

        return self._retry_traced(
            caps,
            build,
            (inputs,),
            stats_sink=stats_sink,
            input_rows=sum(
                b.capacity for b in inputs.values() if isinstance(b, Batch)
            ),
            # root identities of every member key the entry, for the same
            # dynamic-filter staleness reason as the per-fragment path
            program_key=(
                "fused",
                fids,
                apply_exchange,
                tuple(id(f.root) for f in frags),
                tuple(unique.values()),
            ),
            defer=defer,
        )

    # === cross-query batched dispatch ===================================

    def execute_batched(
        self, node: P.PlanNode, param_sets: Sequence[Sequence]
    ) -> tuple[list[Batch], list[str]]:
        """Execute K literal-variant queries as ONE stacked dispatch.

        ``param_sets`` holds one hoisted-literal vector per query, all
        canonicalizing to the plan this executor was built for. The K
        member executions unroll inside a single ``jax.jit`` trace —
        identical ops over different ``__params__`` slices — so every
        member's result is bit-identical to its sequential run while the
        whole batch pays one dispatch round-trip, one program-cache
        lookup, and one device->host pull. Returns (batches, names):
        one compacted host Batch per member, in submission order.

        Dynamic filtering and skew salting are disabled on this path
        (both rebuild per-execution state that would couple members or
        churn program keys); the losses are pruning/padding only, never
        results. Raises :class:`BatchUnsupported` for shapes the path
        cannot carry — non-fusable plans, streaming/spill-sized scans,
        multi-host meshes — and callers fall back to sequential
        per-member execution.
        """
        if jax.process_count() > 1:
            raise BatchUnsupported("multi-host mesh")
        if self.stats_collector is not None:
            raise BatchUnsupported("stats collector attached")
        if not self._param_list:
            raise BatchUnsupported("no hoisted parameters")
        sub = self.programs.get("__subplan__")
        if sub is None:
            with get_tracer().span("fragment"):
                sub = fragment_plan(node)
            self.programs["__subplan__"] = sub
        if not query_fusable(sub):
            raise BatchUnsupported("plan not fusable")
        if self._fusion_blocked(sub):
            raise BatchUnsupported("streaming/spill-sized scan")
        try:
            return self._execute_fragments_batched(sub, list(param_sets))
        except FusedUnsupported as e:
            raise BatchUnsupported(str(e)) from e
        except jax.errors.TracerArrayConversionError as e:
            raise BatchUnsupported("host values needed mid-trace") from e

    def _execute_fragments_batched(
        self, sub: SubPlan, param_sets: list
    ) -> tuple[list[Batch], list[str]]:
        import time as _time

        kreq = len(param_sets)
        # bucket K to a power of two, padding with copies of member 0
        # (only the first kreq results are returned): every distinct K is
        # a separately traced program, so quantizing batch sizes keeps
        # the program store small exactly like the capacity buckets do
        K = 1
        while K < kreq:
            K *= 2
        padded = list(param_sets) + [param_sets[0]] * (K - kreq)
        pstack = tuple(
            jnp.asarray([ps[i] for ps in padded], dtype=t.storage_dtype)
            for i, (_, t) in enumerate(self._param_list)
        )
        results: dict[int, list[Result]] = {}
        names_holder: dict[int, list[str]] = {}
        units = self._fusion_units(sub)

        def run_units():
            for unit in units:
                if isinstance(unit, FusedFragment):
                    results[unit.id] = self._run_fused_unit_batched(
                        unit, K, pstack, results, names_holder
                    )
                else:
                    results[unit.id] = self._run_fragment_batched(
                        unit, K, pstack, results, names_holder
                    )

        # same optimistic deferred-flag protocol as _execute_fragments:
        # flags are already max-merged across members in-trace, so the
        # host still checks one scalar per site in one transfer
        attempts = 0
        while True:
            attempts += 1
            if attempts > 12:
                raise CapacityRetryExceeded(
                    "batched-query",
                    fragment_id=sub.fragment.id,
                    capacities=self._all_capacities(),
                    attempts=attempts - 1,
                )
            self.deferred_flags = []
            self.deferred_counters = []
            results.clear()
            names_holder.clear()
            run_units()
            roots = results[sub.fragment.id]
            deferred = self.deferred_flags
            dcounters = self.deferred_counters
            self.deferred_flags = None
            self.deferred_counters = None
            extras = [
                jnp.ravel(f.astype(jnp.int32)) for _, _, f, _ in deferred
            ] + [jnp.ravel(c) for _, c, _ in dcounters if c is not None]
            with get_tracer().span(
                "device_pull",
                attrs={
                    "extras": len(extras),
                    "attempt": attempts,
                    "batch": K,
                },
            ):
                t_pull = _time.perf_counter()
                host_batches, extra_vals = self._demux_batch_to_host(
                    roots, extras
                )
                pull_ms = (_time.perf_counter() - t_pull) * 1000.0
            get_registry().histogram("trino_tpu_device_pull_ms").observe(
                pull_ms
            )
            flag_vals = extra_vals[: len(deferred)]
            counter_vals = list(extra_vals[len(deferred):])
            overflowed = False
            for (key, names, _, caps), seg in zip(deferred, flag_vals):
                seg = np.atleast_1d(np.asarray(seg))
                for nm, fl in zip(names, seg):
                    if fl:
                        overflowed = True
                        grow_or_raise(nm, caps, int(fl))
            if not overflowed:
                for names, stacked, static in dcounters:
                    vals = (
                        np.atleast_1d(np.asarray(counter_vals.pop(0)))
                        if stacked is not None
                        else ()
                    )
                    self._accumulate_exchange(names, vals, static)
                break
            self.exchange_stats["overflow_retries"] += 1
        self.exchange_stats["batchedQueries"] = kreq
        outs = [b.compact() for b in host_batches[:kreq]]
        names = names_holder.get(sub.fragment.id) or [
            s.name for s in sub.fragment.root.output_symbols
        ]
        return outs, names

    def _demux_batch_to_host(self, roots: list, extras: list):
        """ONE device->host pull for the whole batched dispatch: members
        1..K-1's column arrays, validity lanes, and selection masks (plus
        the deferred overflow/counter extras) ride member 0's packed
        ``Batch.to_host`` transfer; host batches are reassembled per
        member afterward. Returns (host_batches, extra_values)."""
        packed: list = list(extras)
        plan: list[list[bool]] = []  # per tail member: has-valid per column
        for r in roots[1:]:
            spec = []
            for c in r.batch.columns:
                packed.append(c.data)
                if c.valid is not None:
                    packed.append(c.valid)
                spec.append(c.valid is not None)
            packed.append(
                r.batch.sel
                if r.batch.sel is not None
                else r.batch.selection_mask()
            )
            plan.append(spec)
        host_head, vals = roots[0].batch.to_host(extras=packed)
        extra_vals = vals[: len(extras)]
        it = iter(vals[len(extras):])
        out = [host_head]
        for r, spec in zip(roots[1:], plan):
            cols = []
            for c, has_valid in zip(r.batch.columns, spec):
                data = next(it)
                valid = next(it) if has_valid else None
                cols.append(Column(c.type, data, valid, c.dictionary))
            sel = next(it)
            out.append(Batch(cols, r.batch.num_rows, sel))
        return out, extra_vals

    def _run_fragment_batched(
        self,
        frag: PlanFragment,
        K: int,
        pstack: tuple,
        results: dict[int, list[Result]],
        names_holder: dict[int, list[str]],
    ) -> list[Result]:
        span = get_tracer().start_span(
            "fragment_execute", attrs={"stage": frag.id, "batch": K}
        )
        with span:
            inputs: dict[str, Any] = {}
            input_layouts: dict[str, dict[str, int]] = {}
            spill_threshold = (
                int(self.session.get("spill_threshold_rows"))
                if self.session.get("spill_enabled")
                and not self._graceful_overflow()
                else None
            )
            for n in P.walk_plan(frag.root):
                if isinstance(n, P.TableScan):
                    res = self._exec_tablescan(n)
                    if (
                        spill_threshold is not None
                        and res.batch.capacity > spill_threshold
                    ):
                        raise BatchUnsupported("spill-sized input")
                    inputs[f"scan{id(n)}"] = res.batch
                    input_layouts[f"scan{id(n)}"] = res.layout
                elif isinstance(n, P.RemoteSource):
                    rs = results[n.fragment_id]
                    inputs[f"remote{n.fragment_id}"] = tuple(
                        r.batch for r in rs
                    )
                    input_layouts[f"remote{n.fragment_id}"] = rs[0].layout
                elif isinstance(n, P.Output):
                    names_holder[frag.id] = list(n.column_names)
            out = self.run_fragment_program_batched(
                frag, K, pstack, inputs, input_layouts, defer=True
            )
            span.set("mode", "batched")
            self._note_joins(span, frag.id)
            return out

    def _run_fused_unit_batched(
        self,
        unit: FusedFragment,
        K: int,
        pstack: tuple,
        results: dict[int, list[Result]],
        names_holder: dict[int, list[str]],
    ) -> list[Result]:
        span = get_tracer().start_span(
            "fused_execute",
            attrs={
                "stage": unit.id,
                "fragments": len(unit.fragments),
                "batch": K,
            },
        )
        with span:
            member_ids = set(unit.fragment_ids)
            inputs: dict[str, Any] = {}
            input_layouts: dict[str, dict[str, int]] = {}
            spill_threshold = (
                int(self.session.get("spill_threshold_rows"))
                if self.session.get("spill_enabled")
                and not self._graceful_overflow()
                else None
            )
            for frag in unit.fragments:
                for n in P.walk_plan(frag.root):
                    if isinstance(n, P.TableScan):
                        res = self._exec_tablescan(n)
                        if (
                            spill_threshold is not None
                            and res.batch.capacity > spill_threshold
                        ):
                            raise BatchUnsupported("spill-sized input")
                        inputs[f"scan{id(n)}"] = res.batch
                        input_layouts[f"scan{id(n)}"] = res.layout
                    elif (
                        isinstance(n, P.RemoteSource)
                        and n.fragment_id not in member_ids
                    ):
                        rs = results[n.fragment_id]
                        inputs[f"remote{n.fragment_id}"] = tuple(
                            r.batch for r in rs
                        )
                        input_layouts[f"remote{n.fragment_id}"] = rs[0].layout
                    elif isinstance(n, P.Output):
                        names_holder[frag.id] = list(n.column_names)
            out = self.run_fused_program_batched(
                unit.fragments, K, pstack, inputs, input_layouts, defer=True
            )
            span.set("mode", "batched-fused")
            self._note_joins(span, "fused", tuple(unit.fragment_ids))
            get_registry().counter("trino_tpu_fused_programs_total").inc()
            return out

    def run_fragment_program_batched(
        self,
        frag: PlanFragment,
        K: int,
        pstack: tuple,
        inputs: dict[str, Any],
        input_layouts: dict[str, dict[str, int]],
        apply_exchange: bool = True,
        defer: bool = False,
    ) -> list[Result]:
        """K-unrolled variant of :meth:`run_fragment_program`: the build
        closure constructs K copies of the member program inside ONE
        ``jax.jit``, each over its own slice of the stacked parameter
        vector — the same ops as K sequential dispatches (bit-identical
        member results), one XLA program, one dispatch round-trip.
        Capacities are SHARED with the single-query path, so a batch
        benefits from (and feeds) the same overflow ladder."""
        caps = self.programs.setdefault(("caps", frag.id), _Caps())
        self._seed_history(frag, caps)
        self._seed_caps(frag, caps)
        inputs = dict(inputs)
        inputs["__params__"] = pstack

        def build(meta: _Meta):
            def fn(inp: dict[str, Any]):
                summary = _BatchSummary()
                data, sels = [], []
                res = None
                for k in range(K):
                    tracer = _FragmentTracer(
                        self, _member_inputs(inp, k), input_layouts, caps
                    )
                    res = tracer._exec(frag.root)
                    if apply_exchange:
                        res = tracer.apply_output_exchange(frag, res)
                    summary.absorb(tracer)
                    data.append(
                        tuple((c.data, c.valid) for c in res.batch.columns)
                    )
                    sels.append(res.batch.selection_mask())
                summary.exchange_static["dispatchRoundTrips"] = 1
                meta.capture(res, summary)
                meta.batch_size = K
                return (
                    tuple(data),
                    tuple(sels),
                    tuple(f for _, f in summary.overflows),
                    tuple(c for _, c in summary.counters),
                    (),
                )

            return fn

        return self._retry_traced(
            caps,
            build,
            (inputs,),
            input_rows=sum(
                b.capacity for b in inputs.values() if isinstance(b, Batch)
            ),
            # 5-tuple keys bypass _store_program's stale-root eviction:
            # batching disables dynamic filtering, so frag.root is the
            # stable original and its id never churns
            program_key=(
                "bfrag", frag.id, K, apply_exchange, id(frag.root)
            ),
            defer=defer,
        )

    def run_fused_program_batched(
        self,
        frags: Sequence[PlanFragment],
        K: int,
        pstack: tuple,
        inputs: dict[str, Any],
        input_layouts: dict[str, dict[str, int]],
        apply_exchange: bool = True,
        defer: bool = False,
    ) -> list[Result]:
        """K-unrolled :meth:`run_fused_program`: each member's whole
        fragment CHAIN (interior exchanges as in-jit collectives) unrolls
        K times inside one program. Skew detection/salting is off under
        batching — the hot-set handoff would couple members — so
        exchanges run the plain two-tier (cold+spill) routing."""
        frags = list(frags)
        fids = tuple(f.id for f in frags)
        caps = self.programs.setdefault(("caps", "fused", fids), _Caps())
        for f in frags:
            self._seed_history(f, caps)
            self._seed_caps(f, caps)
        inputs = dict(inputs)
        inputs["__params__"] = pstack

        def build(meta: _Meta):
            def fn(inp: dict[str, Any]):
                summary = _BatchSummary()
                data, sels = [], []
                res = None
                for k in range(K):
                    avail = _member_inputs(inp, k)
                    layouts = dict(input_layouts)
                    member = _TracerSummary()
                    for frag in frags:
                        last = frag is frags[-1]
                        tracer = _FragmentTracer(
                            self, avail, layouts, caps
                        )
                        res = tracer._exec(frag.root)
                        if not last or apply_exchange:
                            res = tracer.apply_output_exchange(frag, res)
                        member.absorb(tracer)
                        if not last:
                            avail = dict(avail)
                            layouts = dict(layouts)
                            avail[f"remote{frag.id}"] = res.batch
                            layouts[f"remote{frag.id}"] = res.layout
                    summary.absorb(member)
                    data.append(
                        tuple((c.data, c.valid) for c in res.batch.columns)
                    )
                    sels.append(res.batch.selection_mask())
                summary.exchange_static["dispatchRoundTrips"] = 1
                summary.exchange_static["fusedFragments"] = len(frags)
                meta.capture(res, summary)
                meta.batch_size = K
                return (
                    tuple(data),
                    tuple(sels),
                    tuple(f for _, f in summary.overflows),
                    tuple(c for _, c in summary.counters),
                    (),
                )

            return fn

        return self._retry_traced(
            caps,
            build,
            (inputs,),
            input_rows=sum(
                b.capacity for b in inputs.values() if isinstance(b, Batch)
            ),
            program_key=(
                "bfused",
                fids,
                K,
                apply_exchange,
                tuple(id(f.root) for f in frags),
            ),
            defer=defer,
        )


def _member_inputs(inp: dict, k: int) -> dict:
    """Member k's view of a batched program's inputs: shared scans pass
    through, per-member tuples (remote feeds, the stacked ``__params__``
    vector) slice at k — exactly the inputs dict a sequential run of
    member k would see, as traced values."""
    mi: dict = {}
    for key, v in inp.items():
        if key == "__params__":
            mi[key] = tuple(a[k] for a in v)
        elif isinstance(v, tuple):
            mi[key] = v[k]
        else:
            mi[key] = v
    return mi


def _dup_key_rows(keys, sel):
    """Boolean per-row flags: row's full key appears on MORE than one
    selected row. Sort-based (scatter-free): one narrow bit-packed sort
    (ops/keypack.py) puts equal keys adjacent; neighbors with equal keys
    are duplicates; a scatter-free inverse-permutation sort restores
    original row order."""
    from trino_tpu.ops import keypack as KP

    n = sel.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    eq_lanes, perm, s_sel = KP.grouping_sort(keys, sel, n)
    same_prev = idx > 0  # first sorted row has no predecessor
    for k in eq_lanes:
        prev = jnp.concatenate([k[:1], k[:-1]])
        same_prev = same_prev & (k == prev)
    same_prev = same_prev & s_sel
    same_next = jnp.concatenate([same_prev[1:], jnp.zeros(1, jnp.bool_)])
    dup_sorted = (same_prev | same_next) & s_sel
    return KP.inverse_permute_mask(perm, dup_sorted)


class _OptPack:
    """Unpacker for flat shard_map operand lists built by
    :func:`pack_opt_pairs` (optional validity lanes are simply absent)."""

    def __init__(self, has_kv, input_kinds):
        self.has_kv = has_kv
        self.input_kinds = input_kinds

    def unpack(self, ops):
        i = 0
        lkeys = []
        for hk in self.has_kv:
            kd = ops[i]
            i += 1
            kv = None
            if hk:
                kv = ops[i]
                i += 1
            lkeys.append((kd, kv))
        lsel = ops[i]
        i += 1
        linputs = []
        for kind in self.input_kinds:
            if kind == "none":
                linputs.append(None)
            elif kind == "data":
                linputs.append((ops[i], None))
                i += 1
            else:
                linputs.append((ops[i], ops[i + 1]))
                i += 2
        return lkeys, lsel, linputs, i


def pack_opt_pairs(keys, sel, agg_inputs):
    """Flatten (key pairs, selection, agg-input pairs) into shard_map
    operands, omitting None validity lanes (columns with no nulls cost
    zero extra sort passes downstream)."""
    flat = []
    has_kv = []
    for kd, kv in keys:
        flat.append(kd)
        has_kv.append(kv is not None)
        if kv is not None:
            flat.append(kv)
    flat.append(sel)
    kinds = []
    for p in agg_inputs:
        if p is None:
            kinds.append("none")
        elif p[1] is None:
            kinds.append("data")
            flat.append(p[0])
        else:
            kinds.append("data+valid")
            flat.extend([p[0], p[1]])
    return flat, _OptPack(has_kv, kinds)


class _FragmentTracer(DistributedExecutor):
    """Pure-traceable execution of one fragment's node chain. Instances are
    created inside ``jax.jit``; every method avoids host synchronization —
    capacities come from the shared :class:`_Caps`, and data-dependent
    overflow is reported via traced flags instead of host retries."""

    def __init__(
        self,
        base: DistributedExecutor,
        inputs,
        input_layouts,
        caps,
        skew: Optional[dict] = None,
    ):
        super().__init__(base.catalogs, base.session, base.mesh, memory_ctx=None)
        self._inputs = inputs
        self._input_layouts = input_layouts
        # traced parameter vector (hoisted plan literals); the inherited
        # ExprCompiler call sites read it via getattr(self, "_params")
        self._params = (
            inputs.get("__params__") if isinstance(inputs, dict) else None
        )
        self.caps = caps
        self.skew = skew or {}
        self.overflows: list[tuple[str, jax.Array]] = []
        # exchange observability: traced int64 scalars pulled with the
        # overflow flags, plus statically-known wire-slot accounting
        self.counters: list[tuple[str, jax.Array]] = []
        self.exchange_static: dict[str, int] = {}
        # replicated hot-key tables exported for the peer build exchange
        self.aux_out: tuple = ()
        # the join sites this trace made, as ``_Caps.join_sites`` has them
        self.joins: dict[str, dict] = {}
        self._memo: dict[int, Result] = {}
        # build sides made before this trace (a streamed aggregate's), by
        # ``id`` of their plan root: True where the join's key is unique
        # among the build's live rows (``_exec_join``)
        self.unique_builds: dict[int, bool] = {}
        # operator telemetry: per-node traced row counts appended to the
        # shared counter channel (pulled with the overflow flags — zero
        # extra host round trips). Off -> no extra ops traced at all.
        self._op_enabled = bool(base.session.get("operator_stats"))
        self._op_rowcounts: dict[int, jax.Array] = {}

    @property
    def n(self) -> int:
        return self.mesh.devices.size

    def _exec(self, node: P.PlanNode) -> Result:
        key = id(node)
        if key not in self._memo:
            self._memo[key] = self._dispatch(node)
            self._op_count(node)
        return self._memo[key]

    def _dispatch(self, node: P.PlanNode) -> Result:
        method = getattr(self, f"_exec_{type(node).__name__.lower()}", None)
        if method is None:
            raise FusedUnsupported(type(node).__name__)
        return method(node)

    # --- operator telemetry (op! counter channel) -----------------------

    def _op_rows(self, node: P.PlanNode) -> jax.Array:
        """Traced selected-row count of a memoized node result, computed
        once per node regardless of how many parents (or the in/out pair)
        reference it."""
        key = id(node)
        r = self._op_rowcounts.get(key)
        if r is None:
            sel = self._memo[key].batch.selection_mask()
            r = jnp.sum(sel.astype(jnp.int64))
            self._op_rowcounts[key] = r
        return r

    def _op_count(self, node: P.PlanNode) -> None:
        """Mint per-operator input/output row counters for the just-memoized
        node. Counters ride the existing deferred pull: per-shard partial
        sums are pure reductions XLA folds into the program, so results
        stay bit-identical with telemetry on or off and no new D2H round
        trip is issued. Site names resolve at trace time via the _Caps
        site map (always registered by _seed_history), so deferred
        accumulation needs no capture context."""
        if not self._op_enabled:
            return
        if isinstance(node, P.Aggregate):
            kind = {
                "partial": "partial-agg",
                "final": "final-agg",
            }.get(node.step, "agg")
            site = self.caps.sites.get(f"agg{id(node)}")
        elif isinstance(node, P.Join):
            kind = "semijoin" if node.join_type in ("SEMI", "ANTI") else "join"
            site = self.caps.sites.get(f"join{id(node)}")
        elif isinstance(node, P.TableScan):
            kind, site = "scan", self.caps.sites.get(f"opscan{id(node)}")
        elif isinstance(node, P.Filter):
            kind, site = "filter", self.caps.sites.get(f"opfilter{id(node)}")
        else:
            return
        if site is None:
            return  # node not registered (e.g. synthetic rewrite artifact)
        sources = [] if isinstance(node, P.TableScan) else list(node.sources)
        if sources and all(id(s) in self._memo for s in sources):
            rows_in = self._op_rows(sources[0])
            for s in sources[1:]:
                rows_in = rows_in + self._op_rows(s)
        else:
            # leaves count their own batch as input (scan in == out)
            rows_in = self._op_rows(node)
        self.counters.append((f"op!{kind}!in!{site}", rows_in))
        self.counters.append((f"op!{kind}!out!{site}", self._op_rows(node)))

    # --- leaves ---------------------------------------------------------

    def _exec_tablescan(self, node: P.TableScan) -> Result:
        batch = self._inputs[f"scan{id(node)}"]
        return Result(batch, dict(self._input_layouts[f"scan{id(node)}"]))

    def _exec_remotesource(self, node: P.RemoteSource) -> Result:
        batch = self._inputs[f"remote{node.fragment_id}"]
        layout = self._input_layouts[f"remote{node.fragment_id}"]
        return Result(batch, remote_layout(node, layout))

    # --- output / row-preserving ---------------------------------------

    def _exec_output(self, node: P.Output) -> Result:
        res = self._exec(node.source)
        cols = [res.column(s) for s in node.symbols]
        layout = {s.name: i for i, s in enumerate(node.symbols)}
        return Result(Batch(cols, res.batch.num_rows, res.batch.sel), layout)

    # _exec_filter / _exec_project inherited (already traceable)

    def _exec_limit(self, node: P.Limit) -> Result:
        res = self._exec(node.source)
        sel = res.batch.selection_mask()
        from trino_tpu.ops.aggregation import _prefix_sum
        rank = _prefix_sum(sel.astype(jnp.int32))
        keep = sel
        if node.offset:
            keep = keep & (rank > node.offset)
        if node.count is not None:
            keep = keep & (rank <= node.offset + node.count)
        return Result(
            Batch(res.batch.columns, res.batch.num_rows, keep), res.layout
        )

    def _exec_sort(self, node: P.Sort) -> Result:
        return self._traced_sort(self._exec(node.source), node.order_by, None)

    def _exec_topn(self, node: P.TopN) -> Result:
        res = self._exec(node.source)
        if node.step == "partial":
            return self._partial_topn(res, node)
        return self._traced_sort(res, node.order_by, node.count)

    def _sort_operands(self, res: Result, order_by):
        key_pairs, keys, ranks = [], [], []
        for o in order_by:
            c = res.column(o.symbol)
            key_pairs.append((c.data, c.valid_mask()))
            keys.append(o.sort_key())
            ranks.append(c.dictionary.ranks() if c.dictionary is not None else None)
        return key_pairs, keys, ranks

    def _traced_sort(
        self, res: Result, order_by, keep: Optional[int]
    ) -> Result:
        b = res.batch
        key_pairs, keys, ranks = self._sort_operands(res, order_by)
        sel = b.selection_mask()
        perm = sort_indices(key_pairs, keys, sel, ranks)
        if keep is not None:
            perm = perm[: min(keep, b.capacity)]
        cols = []
        for c in b.columns:
            cols.append(
                Column(c.type, c.data[perm], c.valid_mask()[perm], c.dictionary)
            )
        out_sel = sel[perm]
        return Result(Batch(cols, perm.shape[0], out_sel), res.layout)

    def _partial_topn(self, res: Result, node: P.TopN) -> Result:
        """Per-shard topN: each shard keeps its own best `count` rows
        (reference: TopNNode PARTIAL)."""
        b = res.batch
        key_pairs, keys, ranks = self._sort_operands(res, node.order_by)
        sel = b.selection_mask()
        keep = min(node.count, max(1, b.capacity // self.n))
        flat = []
        for c in b.columns:
            flat.append(c.data)
            flat.append(c.valid_mask())
        for kd, kv in key_pairs:
            flat.append(kd)
            flat.append(kv)
        flat.append(sel)
        ncols = len(b.columns)
        nkeys = len(key_pairs)

        def shard_topn(*ops):
            cols_ = ops[: 2 * ncols]
            kp = [
                (ops[2 * ncols + 2 * i], ops[2 * ncols + 2 * i + 1])
                for i in range(nkeys)
            ]
            s = ops[-1]
            perm = sort_indices(kp, keys, s, ranks)[:keep]
            outs = [c[perm] for c in cols_]
            return tuple(outs), s[perm]

        mapped = smap(
            shard_topn,
            mesh=self.mesh,
            in_specs=(PS(AXIS),) * len(flat),
            out_specs=(tuple(PS(AXIS) for _ in range(2 * ncols)), PS(AXIS)),
        )
        outs, out_sel = mapped(*flat)
        cols = []
        for i, c in enumerate(b.columns):
            cols.append(Column(c.type, outs[2 * i], outs[2 * i + 1], c.dictionary))
        return Result(Batch(cols, self.n * keep, out_sel), res.layout)

    # --- aggregation -----------------------------------------------------

    def _exec_aggregate(self, node: P.Aggregate) -> Result:
        res = self._exec(node.source)
        if node.step == "partial":
            return self._agg_partial(node, res)
        if node.step == "final":
            return self._agg_final(node, res)
        return self._agg_single(node, res)

    def _agg_inputs(self, node: P.Aggregate, res: Result,
                    distinct_keys=None, distinct_sel=None):
        """Traceable version of the interpreter's aggregate input prep.
        ``distinct_keys``/``distinct_sel`` enable DISTINCT dedup (single
        step only — the fragmenter gathers distinct aggregations)."""
        agg_inputs, specs, string_dicts = [], [], []
        for _, fn in node.aggregates:
            if fn.distinct and distinct_keys is None:
                raise FusedUnsupported("distinct aggregate outside single step")
            if fn.kind == "count_star":
                if fn.filter is not None:
                    fc = res.column(P.Symbol(fn.filter.name, T.BOOLEAN))
                    ones = jnp.ones(res.batch.capacity, dtype=jnp.int64)
                    agg_inputs.append((ones, fc.data & fc.valid_mask()))
                    specs.append(AggSpec("count"))
                    string_dicts.append(None)
                    continue
                agg_inputs.append(None)
                specs.append(AggSpec("count_star"))
                string_dicts.append(None)
                continue
            sym = P.Symbol(fn.argument.name, fn.argument.type)
            c = res.column(sym)
            data, valid = c.data, c.valid  # None valid = no nulls (cheaper)
            if c.dictionary is not None and fn.kind in ("min", "max"):
                data = rank_codes(c.dictionary, data)
                string_dicts.append(c.dictionary)
            else:
                string_dicts.append(None)
            if fn.filter is not None:
                fc = res.column(P.Symbol(fn.filter.name, T.BOOLEAN))
                fmask = fc.data & fc.valid_mask()
                valid = fmask if valid is None else (valid & fmask)
            if fn.distinct:
                # DISTINCT: only the first occurrence of each
                # (group keys, value) pair contributes (reference:
                # MarkDistinctOperator / distinct accumulators)
                from trino_tpu.ops.aggregation import distinct_first_mask

                vmask = (
                    distinct_sel
                    if valid is None
                    else (valid & distinct_sel)
                )
                first = distinct_first_mask(
                    distinct_keys, (data, c.valid_mask()), vmask
                )
                valid = first if valid is None else (valid & first)
            agg_inputs.append((data, valid))
            specs.append(sum_spec_for(fn, data))
        return agg_inputs, specs, string_dicts

    def _agg_partial(self, node: P.Aggregate, res: Result) -> Result:
        """Per-shard partial aggregation -> accumulator rows (sharded)."""
        sel = res.batch.selection_mask()
        agg_inputs, specs, string_dicts = self._agg_inputs(node, res)
        key_cols = [res.column(k) for k in node.group_keys]
        keys = [(c.data, c.valid) for c in key_cols]
        nkeys = len(keys)
        if nkeys == 0:
            return self._agg_partial_global(node, res, sel, agg_inputs, specs, string_dicts)
        G = self.caps.get(f"agg{id(node)}", 1 << 12)

        flat, pack = pack_opt_pairs(keys, sel, agg_inputs)

        def shard_partial(*ops):
            lkeys, lsel, linputs, _ = pack.unpack(ops)
            (kd, kv), raw, ng, ovf = group_aggregate(lkeys, lsel, linputs, specs, G)
            vals, cnts = [], []
            for spec, r in zip(specs, raw):
                if spec.kind in ("count", "count_star"):
                    vals.append(r.astype(jnp.int64))
                    cnts.append(None)
                else:
                    vals.append(r[0])
                    cnts.append(r[1])
            live = jnp.arange(G) < ng
            outs = []
            for i2 in range(nkeys):
                outs.extend([kd[i2], kv[i2]])
            for v, c in zip(vals, cnts):
                outs.append(v)
                if c is not None:
                    outs.append(c)
            ovf_any = jax.lax.pmax(need_flag(ovf, ng), AXIS)
            return tuple(outs), live, ovf_any

        # outputs: keys*2 + per agg (1 for count kinds, else value+count)
        n_out = 2 * nkeys + sum(
            1 if s.kind in ("count", "count_star") else 2 for s in specs
        )
        mapped = smap(
            shard_partial,
            mesh=self.mesh,
            in_specs=(PS(AXIS),) * len(flat),
            out_specs=(tuple(PS(AXIS) for _ in range(n_out)), PS(AXIS), PS()),
        )
        outs, live, ovf = mapped(*flat)
        self.overflows.append((f"agg{id(node)}", ovf))

        # assemble accumulator Result
        cols: list[Column] = []
        layout: dict[str, int] = {}
        i = 0
        for ksym, kc in zip(node.group_keys, key_cols):
            data = outs[i].astype(ksym.type.storage_dtype)
            cols.append(Column(ksym.type, data, outs[i + 1], kc.dictionary))
            layout[ksym.name] = len(cols) - 1
            i += 2
        for (vsym, csym), spec, sdict in zip(node.acc_symbols, specs, string_dicts):
            if spec.kind in ("count", "count_star"):
                cols.append(Column(T.BIGINT, outs[i].astype(np.int64), None))
                layout[vsym.name] = len(cols) - 1
                i += 1
            else:
                val = outs[i]
                if getattr(val, "ndim", 1) == 2:
                    # 128-bit limb sums -> wide (hi, lo) acc column
                    from trino_tpu.ops import decimal128 as D128

                    hi, lo = D128.limb_sums_to_pair(val)
                    val = jnp.stack([hi, lo], axis=1)
                elif sdict is not None:
                    # string min/max: convert the winning rank back to a
                    # CODE — the accumulator wire representation is codes
                    # (ranks are dictionary-local, codes travel with it)
                    order = np.argsort(sdict.ranks(), kind="stable")
                    if len(order):
                        val = jnp.asarray(order)[
                            jnp.clip(val, 0, len(order) - 1)
                        ].astype(jnp.int32)
                    else:
                        val = jnp.full(val.shape, -1, dtype=jnp.int32)
                cols.append(Column(vsym.type, val, None, sdict))
                layout[vsym.name] = len(cols) - 1
                i += 1
                cols.append(Column(T.BIGINT, outs[i].astype(np.int64), None))
                layout[csym.name] = len(cols) - 1
                i += 1
        return Result(Batch(cols, cols[0].data.shape[0], live), layout)

    def _agg_partial_global(
        self, node, res, sel, agg_inputs, specs, string_dicts
    ) -> Result:
        """Global (ungrouped) partial: one accumulator row per shard."""

        flat, pack = pack_opt_pairs([], sel, agg_inputs)

        def shard_partial(*ops):
            _, lsel, linputs, _ = pack.unpack(ops)
            raw = global_aggregate(lsel, linputs, specs)
            outs = []
            for spec, r in zip(specs, raw):
                if spec.kind in ("count", "count_star"):
                    outs.append(r.astype(jnp.int64)[None])
                else:
                    v = r[0]
                    # limb-sum matrices (sum128*) are already (1, k)
                    outs.append(v if getattr(v, "ndim", 0) == 2 else v[None])
                    outs.append(r[1].astype(jnp.int64)[None])
            return tuple(outs)

        n_out = sum(1 if s.kind in ("count", "count_star") else 2 for s in specs)
        mapped = smap(
            shard_partial,
            mesh=self.mesh,
            in_specs=(PS(AXIS),) * len(flat),
            out_specs=tuple(PS(AXIS) for _ in range(n_out)),
        )
        outs = mapped(*flat)
        cols: list[Column] = []
        layout: dict[str, int] = {}
        i = 0
        for (vsym, csym), spec, sdict in zip(node.acc_symbols, specs, string_dicts):
            if spec.kind in ("count", "count_star"):
                cols.append(Column(T.BIGINT, outs[i].astype(np.int64), None))
                layout[vsym.name] = len(cols) - 1
                i += 1
            else:
                val = outs[i]
                if getattr(val, "ndim", 1) == 2:
                    from trino_tpu.ops import decimal128 as D128

                    hi, lo = D128.limb_sums_to_pair(val)
                    val = jnp.stack([hi, lo], axis=1)
                elif sdict is not None:
                    order = np.argsort(sdict.ranks(), kind="stable")
                    if len(order):
                        val = jnp.asarray(order)[
                            jnp.clip(val, 0, len(order) - 1)
                        ].astype(jnp.int32)
                    else:
                        val = jnp.full(val.shape, -1, dtype=jnp.int32)
                cols.append(Column(vsym.type, val, None, sdict))
                layout[vsym.name] = len(cols) - 1
                i += 1
                cols.append(Column(T.BIGINT, outs[i].astype(np.int64), None))
                layout[csym.name] = len(cols) - 1
                i += 1
        n_rows = self.n
        return Result(
            Batch(cols, n_rows, jnp.ones(n_rows, dtype=jnp.bool_)), layout
        )

    def _agg_final(self, node: P.Aggregate, res: Result) -> Result:
        """Combine accumulator rows (reference: AggregationNode FINAL +
        the aggregation combine function)."""
        sel = res.batch.selection_mask()
        combine_inputs: list = []
        combine_specs: list[AggSpec] = []
        acc_cols = []
        for (vsym, csym), (_, fn) in zip(node.acc_symbols, node.aggregates):
            vcol = res.column(vsym)
            acc_cols.append(vcol)
            if fn.kind in ("count", "count_star"):
                combine_inputs.append((vcol.data, jnp.ones_like(sel)))
                combine_specs.append(AggSpec("sum"))
            else:
                ccol = res.column(csym)
                nonempty = ccol.data > 0
                vdata = vcol.data
                if vcol.dictionary is not None and fn.kind in ("min", "max"):
                    # accumulator codes -> local ranks for order combining
                    vdata = rank_codes(vcol.dictionary, vdata)
                    nonempty = nonempty & (vcol.data >= 0)
                combine_inputs.append((vdata, nonempty))
                if fn.kind in ("sum", "avg"):
                    from trino_tpu.ops.decimal128 import is_wide_data

                    combine_specs.append(
                        AggSpec("sum128w" if is_wide_data(vdata) else "sum")
                    )
                else:
                    combine_specs.append(AggSpec(fn.kind))
                combine_inputs.append((ccol.data, jnp.ones_like(sel)))
                combine_specs.append(AggSpec("sum"))

        dicts = [c.dictionary for c in acc_cols]
        if not node.group_keys:
            raw = global_aggregate(sel, combine_inputs, combine_specs)
            results = self._fold_combined(node, raw)
            cols = self._finalize_traced(node, results, dicts, 1)
            return Result(
                Batch(cols, 1, jnp.ones(1, dtype=jnp.bool_)),
                {s.name: i for i, s in enumerate(node.output_symbols)},
            )

        key_cols = [res.column(k) for k in node.group_keys]
        keys = [(c.data, c.valid_mask()) for c in key_cols]
        nkeys = len(keys)
        G = self.caps.get(f"agg{id(node)}", 1 << 12)

        flat = []
        for kd, kv in keys:
            flat.extend([kd, kv])
        flat.append(sel)
        for d, v in combine_inputs:
            flat.extend([d, v])

        def shard_combine(*ops):
            i = 0
            lkeys = []
            for _ in range(nkeys):
                lkeys.append((ops[i], ops[i + 1]))
                i += 2
            lsel = ops[i]
            i += 1
            linputs = []
            for _ in combine_specs:
                linputs.append((ops[i], ops[i + 1]))
                i += 2
            (kd, kv), raw, ng, ovf = group_aggregate(
                lkeys, lsel, linputs, combine_specs, G
            )
            live = jnp.arange(G) < ng
            outs = []
            for i2 in range(nkeys):
                outs.extend([kd[i2], kv[i2]])
            for r in raw:
                outs.append(r[0])  # all combine kinds return (value, cnt)
            ovf_any = jax.lax.pmax(need_flag(ovf, ng), AXIS)
            return tuple(outs), live, ovf_any

        n_out = 2 * nkeys + len(combine_specs)
        mapped = smap(
            shard_combine,
            mesh=self.mesh,
            in_specs=(PS(AXIS),) * len(flat),
            out_specs=(tuple(PS(AXIS) for _ in range(n_out)), PS(AXIS), PS()),
        )
        outs, live, ovf = mapped(*flat)
        self.overflows.append((f"agg{id(node)}", ovf))

        i = 0
        cols: list[Column] = []
        for ksym, kc in zip(node.group_keys, key_cols):
            data = outs[i].astype(ksym.type.storage_dtype)
            cols.append(Column(ksym.type, data, outs[i + 1], kc.dictionary))
            i += 2
        combined = outs[i:]
        results = self._fold_combined(node, list(combined))
        total = cols[0].data.shape[0] if cols else combined[0].shape[0]
        cols.extend(self._finalize_traced(node, results, dicts, total))
        return Result(
            Batch(cols, total, live),
            {s.name: i2 for i2, s in enumerate(node.output_symbols)},
        )

    def _fold_combined(self, node: P.Aggregate, raw):
        """Fold the combine outputs back to per-aggregate (value, count).
        ``raw`` entries are either plain arrays (per-shard path) or
        ``(value, count)`` tuples from :func:`global_aggregate` — take the
        value part either way."""

        def val(x):
            return x[0] if isinstance(x, tuple) else x

        results = []
        j = 0
        for _, fn in node.aggregates:
            if fn.kind in ("count", "count_star"):
                results.append(val(raw[j]))
                j += 1
            else:
                results.append((val(raw[j]), val(raw[j + 1])))
                j += 2
        return results

    def _agg_single(self, node: P.Aggregate, res: Result) -> Result:
        sel = res.batch.selection_mask()
        dkeys = [res.pair(k) for k in node.group_keys]
        agg_inputs, specs, string_dicts = self._agg_inputs(
            node, res, distinct_keys=dkeys, distinct_sel=sel
        )
        if not node.group_keys:
            raw = global_aggregate(sel, agg_inputs, specs)
            cols = self._finalize_traced(node, raw, string_dicts, 1)
            return Result(
                Batch(cols, 1, jnp.ones(1, dtype=jnp.bool_)),
                {s.name: i for i, s in enumerate(node.output_symbols)},
            )
        keys = [res.opt_pair(k) for k in node.group_keys]
        key_cols = [res.column(k) for k in node.group_keys]
        G = self.caps.get(f"agg{id(node)}", 1 << 12)
        (kd, kv), raw, ng, ovf = group_aggregate(keys, sel, agg_inputs, specs, G)
        self.overflows.append((f"agg{id(node)}", need_flag(ovf, ng)))
        live = jnp.arange(G) < ng
        cols = []
        for i, (ksym, kc) in enumerate(zip(node.group_keys, key_cols)):
            cols.append(
                Column(
                    ksym.type,
                    kd[i].astype(ksym.type.storage_dtype),
                    kv[i],
                    kc.dictionary,
                )
            )
        cols.extend(self._finalize_traced(node, raw, string_dicts, G))
        return Result(
            Batch(cols, G, live),
            {s.name: i for i, s in enumerate(node.output_symbols)},
        )

    def _finalize_traced(self, node, results, dicts, n) -> list[Column]:
        """Traceable _finalize_aggs: avg division, NULL-on-empty, string
        min/max rank->code mapping."""
        cols = []
        for (sym, fn), raw, sdict in zip(node.aggregates, results, dicts):
            t = fn.result_type
            if fn.kind in ("count", "count_star"):
                data = jnp.reshape(raw, (-1,)).astype(jnp.int64)
                cols.append(Column(t, data, None))
                continue
            ssum, cnt = raw
            if getattr(ssum, "ndim", 1) == 2 and ssum.shape[1] in (3, 5):
                # limb sums -> wide (hi, lo) lanes, in-program
                from trino_tpu.ops import decimal128 as D128

                hi, lo = D128.limb_sums_to_pair(ssum)
                ssum = jnp.stack([hi, lo], axis=1)
            if getattr(ssum, "ndim", 1) == 2 and ssum.shape[1] == 2:
                cnt = jnp.reshape(cnt, (-1,))
                valid = cnt > 0
                if fn.kind == "avg":
                    from trino_tpu.ops.decimal128 import (
                        div128_round,
                        widen_i64,
                    )

                    chi, clo = widen_i64(jnp.maximum(cnt, 1))
                    qhi, qlo, _ok = div128_round(
                        ssum[:, 0], ssum[:, 1], chi, clo, 0
                    )
                    if isinstance(t, T.DecimalType) and t.wide:
                        cols.append(
                            Column(t, jnp.stack([qhi, qlo], axis=1), valid)
                        )
                    else:
                        cols.append(Column(t, qlo.astype(t.storage_dtype), valid))
                    continue
                if fn.kind not in ("sum", "min", "max"):
                    raise FusedUnsupported(f"wide decimal {fn.kind}")
                cols.append(Column(t, ssum, valid))
                continue
            ssum = jnp.reshape(ssum, (-1,))
            cnt = jnp.reshape(cnt, (-1,))
            valid = cnt > 0
            if fn.kind == "sum":
                cols.append(Column(t, ssum.astype(t.storage_dtype), valid))
            elif fn.kind == "avg":
                safe = jnp.maximum(cnt, 1)
                if isinstance(t, T.DecimalType):
                    data = jnp.where(
                        ssum >= 0,
                        (ssum + safe // 2) // safe,
                        -((-ssum + safe // 2) // safe),
                    ).astype(jnp.int64)
                else:
                    data = (ssum / safe).astype(t.storage_dtype)
                cols.append(Column(t, data, valid))
            else:  # min / max
                if sdict is not None:
                    order = np.argsort(sdict.ranks(), kind="stable")
                    data = jnp.asarray(order)[
                        jnp.clip(ssum, 0, len(order) - 1)
                    ].astype(jnp.int32)
                    cols.append(Column(t, data, valid, sdict))
                else:
                    cols.append(Column(t, ssum.astype(t.storage_dtype), valid))
        return cols

    # --- joins -----------------------------------------------------------

    def _join_strategy(self, node: P.Join, lkeys) -> str:
        """Pick the join kernel for one Join node: ``sort`` (ops/join.py:
        sort-merge), ``dense`` (ops/dense_join.py: the open-addressing
        table) or ``matmul`` (the same table under identity binning of a
        single integer key).

        ``auto`` answers ``sort``, from what one TPU v5 lite chip read
        (``scripts/join_crossover.py``, PR 36; ``PERF.md`` section 6): the
        whole per-shard join, one 64-bit key, 2,097,152 probe rows into
        4,194,304 output slots, took sort / dense / matmul 782 / 6,191 /
        5,207 ms against a build side of 2,097,152 rows (Q3's slab step),
        773 / 4,549 / 4,641 ms against 262,144 (the fragment below it) and
        656 / 2,919 / 2,917 ms against 1,024 (a dimension, the shape the
        ``matmul`` promotion was written for); Q3 at SF1 end to end 4.59 s
        against 36.1. The table tiers pay 16 rounds of random gathers,
        one of them 64-bit, over every probe row and again over every
        output slot whatever the build side holds, where sort-merge pays
        three sorts, so no static shape the trace can see (the sides'
        capacities, the output's, the key lanes, the mesh) buys them a
        join: the answer is a constant of the key every stored program
        already has. ``join_strategy=dense|matmul`` still pin them, with
        their ladder: sites it demoted (duplicate chains beyond the probe
        window) stay on sort, as does every join with ``dense_join`` off."""
        if sort_joins_only(self.session):
            return "sort"
        # demotions are recorded under the restart-stable alias (node
        # ids churn across retraces); the alias map is registered by
        # _seed_history before any node of this fragment traces
        site = f"densejoin{id(node)}"
        if self.caps.sites.get(site, site) in self.caps.demoted:
            return "sort"
        matmul_ok = len(lkeys) == 1 and jnp.issubdtype(
            lkeys[0][0].dtype, jnp.integer
        )
        pref = str(self.session.get("join_strategy")).lower()
        return "matmul" if pref == "matmul" and matmul_ok else "dense"

    def _exec_join(self, node: P.Join) -> Result:
        if node.join_type in ("SEMI", "ANTI"):
            return self._exec_semi_join_traced(node)
        if node.join_type == "CROSS" and node.single_row:
            return self._exec_scalar_cross_traced(node)
        if node.join_type not in ("INNER", "LEFT") or not node.criteria:
            raise FusedUnsupported(f"join {node.join_type}")
        right = self._exec(node.right)
        left = self._exec(node.left)
        lkeys, rkeys = self._join_keys(left, right, node.criteria)
        if node.single_row:
            # correlated scalar subquery (EnforceSingleRowNode analog):
            # any build-key group with >1 selected rows that a probe row
            # actually joins is a runtime error. The dup flag rides the
            # probe as a synthetic build column so unmatched dup groups
            # (which the reference tolerates) don't fire.
            dup = _dup_key_rows(rkeys, right.batch.selection_mask())
            self._single_row_dup = dup  # consumed below via build columns
        ph, _pv = J.hash_keys(lkeys)
        bh, _bv = J.hash_keys(rkeys)
        # per-shard probing needs key-co-partitioned sides, which only a
        # hash exchange guarantees; any other placement (broadcast, single,
        # same-fragment subtree) probes a replicated build — XLA inserts
        # the gather when the value isn't replicated already
        build_sharded = (
            isinstance(node.right, P.RemoteSource)
            and node.right.exchange_type == "hash"
        )
        probe_cols, probe_schema = [], []
        for s in node.left.output_symbols:
            c = left.column(s)
            probe_cols.extend([c.data, c.valid_mask()])
            probe_schema.append((s, c.dictionary))
        build_cols, build_schema = [], []
        for s in node.right.output_symbols:
            c = right.column(s)
            build_cols.extend([c.data, c.valid_mask()])
            build_schema.append((s, c.dictionary))
        if node.single_row:
            # synthetic build lane: gathered per output row, True only
            # when the matched build row's key group had duplicates
            build_cols.extend(
                [self._single_row_dup, jnp.ones_like(self._single_row_dup)]
            )
        probe_keys = []
        for kd, kv in lkeys:
            probe_keys.extend([kd, kv])
        build_keys = []
        for kd, kv in rkeys:
            build_keys.extend([kd, kv])

        # where the build key is unique among the build's live rows a probe
        # row matches one build row at the most, so the output fits in the
        # probe's own capacity; else twice that. Each rule has a capacity of
        # its own, so a build that stops being unique starts from the wide
        # one (the overflow flag and the ladder stay the guard either way)
        unique = self.unique_builds.get(id(node.right), False)
        cap_name = f"{'ujoin' if unique else 'join'}{id(node)}"
        probe_cap = left.batch.capacity
        default_cap = (
            max(1, probe_cap // max(self.n, 1)) if unique
            else bucket_capacity(max(1024, 2 * probe_cap // max(self.n, 1)))
        )
        cap = self.caps.get(cap_name, default_cap)
        strategy = self._join_strategy(node, lkeys)
        # at the probe's own width a unique build is a lookup: output row i
        # is probe row i, nothing expands and no probe column is gathered.
        # Its flag (a probe row that matched twice) grows ``cap`` past the
        # probe's width, and the retrace takes the expansion
        lookup = (
            strategy == "sort" and unique and not node.single_row
            and cap * max(self.n, 1) == probe_cap
        )
        self.joins[f"densejoin{id(node)}"] = self.caps.join_sites[
            f"densejoin{id(node)}"
        ] = {
            "strategy": strategy, "buildCap": right.batch.capacity,
            "probeCap": probe_cap, "keys": len(node.criteria),
            "outCap": cap * max(self.n, 1), "unique": unique, "lookup": lookup,
        }
        table_cap = None
        if strategy != "sort":
            # table slots per shard: 4x the per-shard build rows (load
            # factor <= 0.25 — linear-probe clusters coalesce past the
            # static window at 0.5); a replicated build holds ALL rows
            build_cap = right.batch.capacity
            per_shard_build = (
                build_cap // max(self.n, 1) if build_sharded else build_cap
            )
            table_cap = self.caps.get(
                f"densejoin{id(node)}",
                bucket_capacity(max(1024, 4 * per_shard_build)),
            )
        res = _sharded_probe(
            self.mesh,
            probe_cols,
            probe_keys,
            ph,
            left.batch.selection_mask(),
            build_cols,
            build_keys,
            bh,
            right.batch.selection_mask(),
            cap,
            node.join_type,
            len(lkeys),  # wide criteria expand into two lane pairs
            build_sharded=build_sharded,
            strategy=strategy,
            table_cap=table_cap,
            lookup=lookup,
        )
        if strategy == "sort":
            out_cols, out_sel, ovf = res
        else:
            out_cols, out_sel, ovf, table_ovf = res
            # graceful overflow: the ladder doubles the table site and
            # re-hashes — never the interpreter's partitioned spill
            self.overflows.append((f"densejoin{id(node)}", table_ovf))
        self.overflows.append((cap_name, ovf))
        cols: list[Column] = []
        layout: dict[str, int] = {}
        i = 0
        for s, d in probe_schema:
            cols.append(Column(s.type, out_cols[i], out_cols[i + 1], d))
            layout[s.name] = len(cols) - 1
            i += 2
        for s, d in build_schema:
            cols.append(Column(s.type, out_cols[i], out_cols[i + 1], d))
            layout[s.name] = len(cols) - 1
            i += 2
        if node.single_row:
            dup_hit = out_cols[i] & out_cols[i + 1] & out_sel
            self.overflows.append(
                (
                    "err!Scalar sub-query has returned multiple rows",
                    jnp.any(dup_hit),
                )
            )
            i += 2
        total = out_cols[0].shape[0]
        result = Result(Batch(cols, total, out_sel), layout)
        if node.filter is not None:
            from trino_tpu.compiler import ExprCompiler
            from trino_tpu.strings import lower_string_calls

            expr = self._bind(node.filter, result.layout)
            work = list(result.batch.columns)
            expr = lower_string_calls(expr, work)
            mask = ExprCompiler(
                work, params=getattr(self, "_params", None)
            ).predicate_mask(expr)
            result = Result(Batch(result.batch.columns, total, mask & out_sel), layout)
        return result

    def _exec_scalar_cross_traced(self, node: P.Join) -> Result:
        """Uncorrelated scalar subquery (single-row CROSS): broadcast the
        one selected build row into every probe row. Zero rows -> NULL;
        more than one -> runtime error via the err! flag channel
        (reference: ``EnforceSingleRowNode`` semantics)."""
        right = self._exec(node.right)
        left = self._exec(node.left)
        rsel = right.batch.selection_mask()
        cnt = jnp.sum(rsel.astype(jnp.int32))
        self.overflows.append(
            ("err!Scalar sub-query has returned multiple rows", cnt > 1)
        )
        pick = jnp.argmax(rsel)  # index of the selected row (0 if none)
        cap = left.batch.capacity
        cols: list[Column] = []
        layout: dict[str, int] = {}
        for s in node.left.output_symbols:
            c = left.column(s)
            cols.append(c)
            layout[s.name] = len(cols) - 1
        from jax.sharding import NamedSharding

        from trino_tpu.parallel.mesh import AXIS as _AXIS

        row_sh = NamedSharding(self.mesh, PS(_AXIS))
        has_row = cnt >= 1
        for s in node.right.output_symbols:
            c = right.column(s)
            val = c.data[pick]
            # materialized row-sharded arrays (not lazy broadcast views of
            # the replicated build): these columns feed shard_map operands
            # downstream, which need real global row-sharded arrays
            data = jax.lax.with_sharding_constraint(
                jnp.zeros((cap,) + val.shape, dtype=c.data.dtype) + val,
                row_sh,
            )
            valid = jax.lax.with_sharding_constraint(
                jnp.zeros((cap,), dtype=jnp.bool_)
                | (c.valid_mask()[pick] & has_row),
                row_sh,
            )
            cols.append(Column(s.type, data, valid, c.dictionary))
            layout[s.name] = len(cols) - 1
        return Result(
            Batch(cols, left.batch.num_rows, left.batch.sel), layout
        )

    def _exec_semi_join_traced(self, node: P.Join) -> Result:
        """SEMI/ANTI as a traced membership mark: probe key rows carry only
        their global row id through the hash-partitioned lookup, matches
        scatter back into a boolean mark column (reference:
        ``HashSemiJoinOperator.java`` — mark semantics incl. 3-valued IN).
        """
        left = self._exec(node.left)
        right = self._exec(node.right)
        cap = left.batch.capacity
        lsel = left.batch.selection_mask()
        bsel = right.batch.selection_mask()

        if not node.criteria:
            if node.filter is not None:
                raise FusedUnsupported("uncorrelated EXISTS with filter")
            nonempty = bsel.any()
            mark = jnp.broadcast_to(
                nonempty if node.join_type == "SEMI" else ~nonempty, (cap,)
            )
            cols = list(left.batch.columns) + [Column(T.BOOLEAN, mark, None)]
            layout = dict(left.layout)
            layout[node.mark_symbol.name] = len(cols) - 1
            return Result(Batch(cols, cap, left.batch.sel), layout)

        lkeys, rkeys = self._join_keys(left, right, node.criteria)
        ph, _ = J.hash_keys(lkeys)
        bh, bv_all = J.hash_keys(rkeys)
        build_sharded = (
            isinstance(node.right, P.RemoteSource)
            and node.right.exchange_type == "hash"
        )
        row_ids = jnp.arange(cap, dtype=jnp.int64)
        probe_cols = [row_ids, jnp.ones(cap, dtype=jnp.bool_)]
        probe_keys = []
        for kd, kv in lkeys:
            probe_keys.extend([kd, kv])
        build_keys = []
        for kd, kv in rkeys:
            build_keys.extend([kd, kv])
        out_cap = self.caps.get(
            f"semi{id(node)}",
            bucket_capacity(max(1024, 2 * cap // max(self.n, 1))),
        )
        out_cols, out_sel, ovf = _sharded_probe(
            self.mesh,
            probe_cols,
            probe_keys,
            ph,
            lsel,
            [],  # no build payload — membership only
            build_keys,
            bh,
            bsel,
            out_cap,
            "INNER",
            len(lkeys),
            build_sharded=build_sharded,
        )
        self.overflows.append((f"semi{id(node)}", ovf))
        match_ids = out_cols[0]
        matched = (
            jnp.zeros(cap, dtype=jnp.bool_)
            .at[jnp.where(out_sel, match_ids, cap)]
            .set(True, mode="drop")
        )
        # 3-valued IN: NULL probe key (or build NULLs without a match)
        # yields NULL; EXISTS semantics are strict TRUE/FALSE
        pv = jnp.ones(cap, dtype=jnp.bool_)
        for _, kv in lkeys:
            pv = pv & kv
        build_nonempty = bsel.any()
        any_null_build = ((~bv_all) & bsel).any()
        if node.null_aware:
            valid = jnp.where(
                build_nonempty,
                matched | (pv & ~any_null_build),
                jnp.ones(cap, dtype=jnp.bool_),
            )
        else:
            valid = jnp.ones(cap, dtype=jnp.bool_)
        value = matched if node.join_type == "SEMI" else ~matched
        cols = list(left.batch.columns) + [Column(T.BOOLEAN, value, valid)]
        layout = dict(left.layout)
        layout[node.mark_symbol.name] = len(cols) - 1
        return Result(Batch(cols, cap, left.batch.sel), layout)

    # --- output exchange --------------------------------------------------

    def apply_output_exchange(self, frag: PlanFragment, res: Result) -> Result:
        if frag.output_exchange in (None, "single"):
            return res  # SPMD consumers read global arrays directly
        if self.n == 1 and not self.skew:
            # one device: every row already is where its key's hash sends
            # it, and a repartition would sort and scatter every lane (and
            # climb a bucket and a spill ladder of its own) to say so
            return res
        b = res.batch
        sel = b.selection_mask()
        # flatten columns into 1-D lane arrays (wide DECIMAL columns ship
        # as separate hi/lo lanes through the collective kernels)
        arrays = []
        schema = []  # (type, dictionary, n_lanes)
        for c in b.columns:
            if getattr(c.data, "ndim", 1) == 2:
                arrays.extend([c.data[:, 0], c.data[:, 1], c.valid_mask()])
                schema.append((c.type, c.dictionary, 2))
            else:
                arrays.extend([c.data, c.valid_mask()])
                schema.append((c.type, c.dictionary, 1))

        def rebuild(out):
            cols = []
            i = 0
            for t, d, lanes in schema:
                if lanes == 2:
                    data = jnp.stack([out[i], out[i + 1]], axis=1)
                    cols.append(Column(t, data, out[i + 2], d))
                    i += 3
                else:
                    cols.append(Column(t, out[i], out[i + 1], d))
                    i += 2
            return cols

        if frag.output_exchange == "broadcast":
            out, out_sel = X.broadcast_all(self.mesh, arrays, sel)
            cols = rebuild(out)
            self._op_exchange(frag, sel, out_sel)
            return Result(
                Batch(cols, cols[0].data.shape[0], out_sel), res.layout
            )
        # hash: two-tier repartition by output key hash — a small cold
        # bucket per (src,dst) plus a shared spill tier, optionally with a
        # salted hot region for heavy-hitter keys (see exchange.py)
        key_pairs = [res.pair(s) for s in frag.output_keys]
        khash, _ = J.hash_keys(key_pairs)
        n = max(self.n, 1)
        detect = self.skew.get("detect")
        hot_set = (
            self._inputs.get(self.skew.get("hotset_key", "__hotset__"))
            if self.skew.get("salt")
            else None
        )
        salted = detect is not None or hot_set is not None
        # cold tier: ~2x the uniform per-(src,dst) share; when a hot set
        # routes the heavy mass away from the cold path, half that
        per_pair = b.capacity // max(n * n, 1)
        default_bucket = bucket_capacity(
            max(64, per_pair if salted else 2 * per_pair), minimum=64
        )
        bucket = self.caps.get(f"exch{frag.id}", default_bucket)
        spill = self.caps.get(f"spill{frag.id}", max(64, bucket // 2))
        if detect is not None:
            # probe side: detect heavy hitters in-program; hot rows stay
            # on their source shard (zero wire cost), so the hot region
            # is safely sized at the full per-shard row count
            hot_mode = "local"
            hot_cap = self.caps.get(
                f"hot{frag.id}",
                bucket_capacity(max(64, b.capacity // n), minimum=64),
            )
        elif hot_set is not None:
            # build side: replicate just the hot slice (partial
            # broadcast); near-unique build keys make this slice tiny
            hot_mode = "replicate"
            hot_cap = self.caps.get(
                f"hot{frag.id}",
                bucket_capacity(max(64, per_pair), minimum=64),
            )
        else:
            hot_mode, hot_cap = None, 0
        out, out_sel, (sp_ovf, hot_ovf), (sent, hot_rows, hot_keys), hotset = (
            X.skewed_repartition(
                self.mesh, arrays, khash, sel, bucket, spill,
                hot_mode=hot_mode, hot_cap=hot_cap, hot_set=hot_set,
                detect=detect,
            )
        )
        self.overflows.append((f"spill{frag.id}", sp_ovf))
        if hot_mode is not None:
            self.overflows.append((f"hot{frag.id}", hot_ovf))
            self.counters.append((f"salted{frag.id}", hot_rows))
        if detect is not None:
            self.aux_out = hotset
            self.counters.append((f"hotkeys{frag.id}", hot_keys))
        self.counters.append((f"sent{frag.id}", sent))
        # wire accounting is static: slots each source ships per attempt
        wire_slots = n * bucket + spill + (
            hot_cap if hot_mode == "replicate" else 0
        )
        row_bytes = sum(int(a.dtype.itemsize) for a in arrays)
        self.exchange_static["exchanges"] = (
            self.exchange_static.get("exchanges", 0) + 1
        )
        self.exchange_static["padded_shuffle_rows"] = (
            self.exchange_static.get("padded_shuffle_rows", 0) + n * wire_slots
        )
        self.exchange_static["shuffle_bytes"] = (
            self.exchange_static.get("shuffle_bytes", 0)
            + n * wire_slots * row_bytes
        )
        cols = rebuild(out)
        self._op_exchange(frag, sel, out_sel)
        return Result(Batch(cols, cols[0].data.shape[0], out_sel), res.layout)

    def _op_exchange(self, frag: PlanFragment, sel_in, sel_out) -> None:
        """Exchange leg of the op! channel: rows offered to the exchange
        vs rows landed after repartition/broadcast (broadcast lands n×
        copies — the fan-out is the signal). The in/out pair around a
        partial-agg producer is the per-exchange reduction-ratio seed the
        mid-query-adaptivity roadmap item reads from history."""
        if not self._op_enabled:
            return
        site = self.caps.sites.get(f"exch{frag.id}", f"exch@{frag.id}")
        self.counters.append(
            (f"op!exchange!in!{site}", jnp.sum(sel_in.astype(jnp.int64)))
        )
        self.counters.append(
            (f"op!exchange!out!{site}", jnp.sum(sel_out.astype(jnp.int64)))
        )
