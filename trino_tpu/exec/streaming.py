"""Streaming scan execution: bounded device-resident chunks through one
compiled step program, with H2D transfer overlapping compute.

Reference: Trino drives scans through the operator pipeline in bounded
pages (``operator/Driver.java:355-392``,
``ScanFilterAndProjectOperator.java:64``) so working memory stays bounded
regardless of table size. The TPU translation: a scan→filter→project→
aggregate fragment becomes ONE jitted *step* function with carried
accumulator state

    state' = step(state, chunk)

executed in a host loop over split chunks. Chunk shapes are fixed
(padded), so the step compiles once; JAX dispatch is asynchronous, so the
host reads and transfers chunk k+1 while the device reduces chunk k
(double buffering without explicit streams). Overflow flags are carried
IN the state and inspected once at the end — no host sync per step; on
overflow the caller grows capacities and restarts the stream.

Wide-DECIMAL sums stream too: chunk partials produce per-group limb sums
(ops/decimal128), and limb lanes are independent int64 accumulators, so
the cross-chunk merge just sums each lane (carry resolution happens once,
at finalize).

One rule for literals: every program built here takes the plan's hoisted
literals (``planner/canonicalize.py``) as its ``params`` ARGUMENT, the
``__params__`` of a fragment program. The engine executes one cached plan
object for every query of a fingerprint, so a program that closed over a
literal's value would answer each later variant with the first one's rows.
The same rule for the build sides of probe-spine joins: ``_prebuild``
materializes them anew every query and every program takes them as its
``builds`` ARGUMENT, so a stored program answers from this query's builds.
What goes into the executor's program store is keyed by content (the
aggregate's fragment id and ordinal, the capacities by their stable site
names, the build batches' capacities and schemas, the mesh's size), never by
``id(node)``; the dictionaries a program was traced against are held beside
it and compared by identity on a hit."""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as PS

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column, bucket_capacity
from trino_tpu.connectors.api import slab_shard_rows
from trino_tpu.exec.local import Result
from trino_tpu.obs.trace import get_tracer
from trino_tpu.ops.aggregation import (
    AggSpec,
    domain_slots,
    global_aggregate,
    group_aggregate,
    key_domains_from,
)
from trino_tpu.parallel.mesh import AXIS, shard_batch, smap
from trino_tpu.planner import plan as P


class StreamOverflow(Exception):
    """A capacity overflowed mid-stream; retry with grown caps. ``needs``:
    each fired flag's value by its capacity's name (``grow_or_raise``)."""

    def __init__(self, needs: dict):
        super().__init__(f"stream capacity overflow: {sorted(needs)}")
        self.needs = needs


# Rows of a slab step for each group of the budget, on the sort path. A
# step's merge sorts, gathers and scans the ``2 G`` rows of state and chunk
# groups whatever the chunk held, so a chunk has to outweigh them. On the
# chip at ``G`` = 1,048,576 and h2o q5's lanes one step of 2,097,152 rows
# takes 0.851 s, of 8,388,608 1.165, of 16,777,216 1.782
# (``scripts/groupby_crossover.py --step-rows``), and the query over 1e8
# rows 16.1 s at 8 rows a group (12 steps), 12.7 at 16 (6), 14.6 at 32 (3:
# the deeper sorts and the gathers from a wider source cost more than three
# merges save), 42.6 at the base width (48); peak memory 3.71, 3.77, 3.83
# and 3.74 GB (``PERF.md`` section 6, PR 34).
SLAB_ROWS_PER_GROUP = 16
# a compile failure at this width or under is not the width's: no halving
SLAB_MIN_ROWS = 1 << 18


def slab_step_rows(base: int, groups: int, sort_path: bool, held: int) -> int:
    """Rows a slab step takes: ``base`` (the session's
    ``stream_device_chunk_rows``) on the domain path, for a global aggregate
    (``sort_path`` false for both) and while ``SLAB_ROWS_PER_GROUP`` chunks
    of ``groups`` fit in it; else the smallest power of two that holds
    them, and no more than the ``held`` rows the slab has."""
    if not sort_path or SLAB_ROWS_PER_GROUP * groups <= base:
        return base
    return min(bucket_capacity(SLAB_ROWS_PER_GROUP * groups), max(base, held))


def streamable_chain(frag_root: P.PlanNode):
    """Detect a streamable fragment:
    Output?→Aggregate→(Filter|Project|Join)*→TableScan along the PROBE
    (left) spine. Joins on the spine have their build (right) sides
    materialized once before the stream (reference: build-once
    ``HashBuilderOperator.java:51``, probe-streamed
    ``LookupJoinOperator.java:71``); each probe chunk then flows through
    join→agg inside the compiled step with bounded output capacity.

    Returns (agg_node, probe_scan, build_roots) or None. ``build_roots``
    is the list of build-side subtree roots, outermost first."""
    node = frag_root
    if isinstance(node, P.Output):
        node = node.source
    if not isinstance(node, P.Aggregate):
        return None
    agg = node
    if agg.step == "final":
        return None
    if any(fn.distinct for _, fn in agg.aggregates):
        return None
    for _, fn in agg.aggregates:
        if fn.kind not in ("sum", "count", "count_star", "min", "max", "avg"):
            return None
    node = agg.source
    build_roots: list[P.PlanNode] = []
    while True:
        if isinstance(node, (P.Filter, P.Project)):
            node = node.source
            continue
        if isinstance(node, P.Join):
            if node.join_type not in ("INNER", "LEFT", "SEMI", "ANTI"):
                return None
            if not node.criteria:
                return None
            build_roots.append(node.right)
            node = node.left
            continue
        break
    if not isinstance(node, P.TableScan):
        return None
    return agg, node, build_roots


class StreamingAggregator:
    """Runs one streamable fragment as a chunk loop with carried state.

    Joins on the probe spine stream too: the build (right) sides are
    materialized ONCE up front (``_prebuild``), and every probe chunk
    flows through join→agg inside the compiled step — the reference's
    build-once/probe-streamed hash join (``HashBuilderOperator.java:51``,
    ``LookupJoinOperator.java:71``) with the probe loop compiled."""

    def __init__(self, executor, frag, agg_node, scan_node, caps,
                 build_roots=(), build_inputs=None, build_layouts=None):
        self.executor = executor
        self.mesh = executor.mesh
        self.n = self.mesh.devices.size
        self.frag = frag
        self.agg = agg_node
        self.scan = scan_node
        self.caps = caps
        self.build_roots = list(build_roots)
        self.build_inputs = build_inputs or {}
        self.build_layouts = build_layouts or {}
        # this query's materialized build sides, in ``build_roots``' order:
        # batches (an ARGUMENT of every program built here) and layouts
        self._builds: Optional[tuple] = None
        self._build_layouts: list[dict] = []
        # per build side, whether its join's key is unique among its live
        # rows (``_prebuild``): static to the step's trace, so in its key
        self._unique: tuple = ()
        self.nkeys = len(agg_node.group_keys)
        from trino_tpu.exec.fragments import agg_site

        # the aggregate by content (``agg@<fragment>#<ordinal>``): names the
        # group budget, its overflow flag and every stored program, so an
        # equal plan at another address finds them and a stranger does not
        self.site = agg_site(frag, agg_node)
        self.G = caps.get(
            self.site, int(executor.session.get("stream_group_budget"))
        )
        # this query's hoisted literals: an ARGUMENT of every program built
        # here (``__params__``), never a constant of one. None when nothing
        # was hoisted (program_cache / constant_hoisting off)
        self.params = executor._param_arrays()
        # running per-column dictionaries for the chunk stream; ids of
        # dictionaries whose growth would invalidate the traced step
        self._running_dicts: Optional[list] = None
        self._sensitive_dicts: set[int] = set()

    def _prebuild(self) -> None:
        """Materialize the build sides of probe-spine joins once a query
        (device resident for the whole stream), under the span
        ``stream.build``: one program, stored like the slab program under a
        key of content (``_run_build``), so a warm query compiles
        nothing (run op by op, a build side that joins broadcast tables
        compiled hundreds of small programs a query). Their overflow flags
        join the deferred check; build capacities grow through the same
        retry. Each batch is normalized to one pytree shape (``num_rows``
        its capacity, the live rows in ``sel``), so a table that gained a
        row is the same argument shape to a stored program."""
        if self._builds is not None:
            return
        if not self.build_roots:
            self._builds = ()
            return
        with get_tracer().span(
            "stream.build", attrs={"site": self.site}
        ) as span:
            (builds, flags, live), meta = self._run_build()
            self._builds = tuple(builds)
            self._build_layouts = [dict(layout) for layout in meta["layouts"]]
            span.set("builds", len(builds))
            span.set("capacities", [b.capacity for b in builds])
            joins = self.caps.joins(self._build_join_names().__contains__)
            if joins:
                span.set("joins", joins)
            # the one wait of the span: the build sides' live rows, which
            # the device has once the fragments below have run, and whether
            # each join's key is unique among them
            live = np.asarray(live)
            rows, self._unique = live[: len(builds)], tuple(
                bool(u) for u in live[len(builds):]
            )
            span.set("rows", int(rows.sum()))
            spine = self._spine_joins()
            sites = [f"join{id(spine.get(id(root)))}" for root in self.build_roots]
            span.set("rowsBySite", {
                self.caps.sites.get(nm, nm): int(n) for nm, n in zip(sites, rows)
            })
        names = meta["ovf_names"]
        if names:
            dfl = getattr(self.executor, "deferred_flags", None)
            if dfl is not None:
                dfl.append((None, names, flags, self.caps))
            else:
                fired = np.asarray(flags)
                if fired.any():
                    raise StreamOverflow(
                        {nm: int(f) for nm, f in zip(names, fired) if f}
                    )

    def _build_input_names(self) -> list:
        """The build sides' inputs (``build_inputs``' keys) in the order
        their plans reach them: the build program's argument order."""
        names = []
        for root in self.build_roots:
            for n in P.walk_plan(root):
                nm = (
                    f"scan{id(n)}" if isinstance(n, P.TableScan)
                    else f"remote{n.fragment_id}"
                    if isinstance(n, P.RemoteSource) else None
                )
                if nm in self.build_inputs and nm not in names:
                    names.append(nm)
        return names

    def _run_build(self):
        """The program that makes the build sides from their inputs, run:
        its outputs (the builds, the overflow flags, the live rows and
        uniqueness of each build) and its ``meta`` (the builds' layouts, the
        flags' names). The stored one where the key and the inputs'
        dictionaries match, else traced and stored. The key holds the
        capacities the trace consulted and the inputs' shapes, as the slab
        program's does."""
        from trino_tpu.exec.fragments import _FragmentTracer

        programs = getattr(self.executor, "programs", None)
        names = self._build_input_names()
        inputs = tuple(self.build_inputs[nm] for nm in names)
        dicts = tuple(c.dictionary for b in inputs for c in b.columns)

        below = {
            f"{kind}{id(n)}"
            for root in self.build_roots for n in P.walk_plan(root)
            for kind in ("agg", "join", "ujoin", "semi", "densejoin")
        }

        def key():
            return ("build", self.site, self._held_caps(below), tuple(
                (b.capacity, b.num_rows, b.sel is None, _columns_shape(b))
                for b in inputs
            ))

        hit = self._stored(programs, key(), dicts)
        if hit is not None:
            program, meta, _ = hit
            self.executor.count_program(hit=True)
            return program(inputs, self.params), meta
        meta: dict = {}
        roots, layouts, executor, caps = (
            self.build_roots, self.build_layouts, self.executor, self.caps
        )
        spine, unique_key = self._spine_joins(), self._unique_key

        def build(inputs, params):
            tracer = _FragmentTracer(
                executor,
                StreamingAggregator._with_params(dict(zip(names, inputs)), params),
                layouts,
                caps,
            )
            builds, meta["layouts"] = [], []
            for root in roots:
                res = tracer._exec(root)
                b = res.batch
                if b.sel is None or b.num_rows != b.capacity:
                    b = Batch(b.columns, b.capacity, b.selection_mask())
                builds.append(b)
                meta["layouts"].append(dict(res.layout))
            live = [jnp.sum(b.sel, dtype=jnp.int64) for b in builds] + [
                unique_key(spine.get(id(root)), b, layout)
                for root, b, layout in zip(roots, builds, meta["layouts"])
            ]
            meta["ovf_names"] = [nm for nm, _ in tracer.overflows]
            flags = jnp.stack(
                [f.astype(jnp.int32) for _, f in tracer.overflows]
                or [jnp.zeros((), jnp.int32)]
            )
            return tuple(builds), flags, jnp.stack(live)

        program = jax.jit(build)
        t0 = time.perf_counter()
        # trace and compile are synchronous in the first call
        out = program(inputs, self.params)
        self.executor.count_program(
            hit=False,
            compile_ms=(time.perf_counter() - t0) * 1000.0,
            stored=programs is not None,
        )
        if programs is not None:
            # (the key now holds the capacities the trace consulted)
            programs[key()] = (program, meta, dicts)
        return out, meta

    def _spine_joins(self) -> dict:
        """The joins of the probe spine, by ``id`` of their build side's root."""
        joins, node = {}, self.agg.source
        while isinstance(node, (P.Filter, P.Project, P.Join)):
            if isinstance(node, P.Join):
                joins[id(node.right)] = node
                node = node.left
            else:
                node = node.source
        return joins

    @staticmethod
    def _unique_key(join, build: Batch, layout: dict):
        """On the device: True where ``join``'s key is unique among
        ``build``'s live rows whose key has no NULL
        (``ops/join.py::unique_keys``); a SEMI or ANTI join, which keeps no
        build row, answers False."""
        from trino_tpu.ops import join as J

        if join is None or join.join_type not in ("INNER", "LEFT"):
            return jnp.int64(0)
        cols = [build.columns[layout[rs.name]] for _, rs in join.criteria]
        return J.unique_key_columns(
            [(c.data, c.valid_mask()) for c in cols], build.selection_mask()
        ).astype(jnp.int64)

    def _build_join_names(self) -> set:
        """The capacity names of the joins inside the build sides' plans:
        ``stream.build`` runs those, ``stream.slab`` the probe spine's."""
        return {
            f"densejoin{id(n)}"
            for root in self.build_roots
            for n in P.walk_plan(root)
            if isinstance(n, P.Join)
        }

    def _program_key(self, kind: str, *shape) -> tuple:
        """A stored program's key, by content: the aggregate's site, its
        budget and ``shape`` (the step's rows and what else the caller's
        program was traced at). With build sides also every capacity the
        trace consulted, under its restart-stable site name, and each build
        batch's capacity, column types and whether its join's key is unique
        among its live rows: a build that outgrew its capacity, or gained a
        duplicate key, is another key, never a program run on the wrong
        shape or at a width its rows can overflow."""
        key = (kind, self.site, self.G) + shape
        if not self.build_roots:
            return key
        builds = tuple(
            (b.capacity, _columns_shape(b), unique)
            for b, unique in zip(self._builds, self._unique)
        )
        return key + (self._held_caps(), builds)

    def _held_caps(self, wanted=None) -> tuple:
        """The capacities the caps hold (those whose runtime name ``wanted``
        holds, where given), under their restart-stable site names, and the
        demoted sites (the fragment's output exchange sizes a program of its
        own)."""
        caps = self.caps
        sited = [
            (caps.sites.get(nm, nm), v) for nm, v in caps.vals.items()
            if wanted is None or nm in wanted
        ]
        return tuple(sorted(
            sv for sv in sited
            if not sv[0].startswith(("exch@", "spill@", "hot@"))
        )) + tuple(sorted(caps.demoted))

    def _traced_dicts(self, *batches) -> tuple:
        """The dictionaries of the build sides and of ``batches``: static
        data of a traced program (``Column``'s pytree aux), held beside the
        stored program and compared by identity (``_stored``) before it
        is called again. An equal key over other dictionaries is a miss,
        and the program traced for it takes the entry."""
        return tuple(
            c.dictionary
            for b in (*self._builds, *batches) if b is not None
            for c in b.columns
        )

    @staticmethod
    def _stored(programs, key: tuple, dicts: tuple):
        """The store's ``(program, meta, dicts)`` under ``key`` if it was
        traced against these very dictionaries, else None."""
        hit = programs.get(key) if programs is not None else None
        if hit is None or len(hit[2]) != len(dicts):
            return None
        return hit if all(x is y for x, y in zip(hit[2], dicts)) else None

    # === chunk source ====================================================

    def _canonicalize_dicts(self, b: Batch) -> Batch:
        """Remap every string column of a split batch onto the stream's
        *running* dictionaries (one stable object per column, grown
        append-only via ``Dictionary.absorb``).

        Two reasons (both bite on any multi-split table):
        - correctness: per-split dictionaries assign unrelated codes to
          the same strings, so carried group keys / min-max state would
          compare garbage across chunks;
        - jit stability: ``Dictionary`` objects are static aux data of the
          chunk pytree, so a fresh dictionary per chunk would retrace and
          recompile the step every chunk.

        If a dictionary grows after the step was traced AND the trace
        embedded growth-sensitive constants from it (rank tables, missed
        equality encodes — see ``Dictionary.trace_log``), the compiled
        step is stale: raise and let the executor fall back."""
        from trino_tpu.exec.fragments import FusedUnsupported

        if not any(c.dictionary is not None for c in b.columns):
            return b
        if self._running_dicts is None:
            self._running_dicts = [None] * b.width
        cols = list(b.columns)
        for j, c in enumerate(cols):
            if c.dictionary is None:
                continue
            running = self._running_dicts[j]
            if running is None:
                self._running_dicts[j] = c.dictionary
                continue
            remap, grew = running.absorb(c.dictionary)
            if grew and id(running) in self._sensitive_dicts:
                raise FusedUnsupported(
                    "split dictionary grew under a rank-dependent trace"
                )
            if remap is not None:
                data = np.asarray(c.data)
                data = np.where(
                    data >= 0, remap[np.maximum(data, 0)], -1
                ).astype(np.int32)
                cols[j] = Column(c.type, data, c.valid, running)
            elif c.dictionary is not running:
                cols[j] = Column(c.type, c.data, c.valid, running)
        return Batch(cols, b.num_rows, b.sel)

    def _chunks(self, chunk_rows: int):
        """Yield lists of n host part-batches, each padded to a fixed
        per-shard capacity (decided from the first split)."""
        connector = self.executor.catalogs.get(self.scan.catalog)
        est = connector.estimate_rows(self.scan.schema, self.scan.table)
        target = max(self.n, (est + chunk_rows - 1) // chunk_rows)
        splits = connector.get_splits(
            self.scan.schema,
            self.scan.table,
            target_splits=target,
            constraint=self.scan.constraint,
        )
        if not splits:
            return
        cap: Optional[int] = None
        proto: Optional[Batch] = None
        pending: list[Batch] = []
        # double-buffered decode (trino_tpu/ingest.py): the next split
        # decodes on a background thread while the device steps over the
        # current chunk — the streaming loop is where overlap pays most
        for b in self.executor._read_splits(
            connector,
            self.scan.schema,
            self.scan.table,
            self.scan.column_names,
            splits,
        ):
            b = self._canonicalize_dicts(b)
            if cap is None:
                cap = bucket_capacity(max(1, min(b.num_rows, chunk_rows)))
                proto = b
            lo = 0
            while True:
                hi = min(lo + cap, b.num_rows)
                piece = _slice_rows(b, lo, hi) if b.num_rows else b
                pending.append(piece)
                if len(pending) == self.n:
                    yield pending, cap
                    pending = []
                lo = hi
                if lo >= b.num_rows:
                    break
        if pending:
            while len(pending) < self.n:
                pending.append(_empty_like(proto))
            yield pending, cap

    # === driver loop =====================================================

    def run(self) -> Result:
        chunk_rows = int(self.executor.session.get("stream_chunk_rows"))
        self._prebuild()
        try:
            res = self._run_device_slab(chunk_rows)
            if res is None:
                res = self._run_host_chunks(chunk_rows)
            return res
        finally:
            # a stored program's closure keeps this object: not this
            # query's build sides nor what they were made from
            self._builds = None
            self.build_inputs = {}

    def _run_host_chunks(self, chunk_rows: int) -> Result:
        """The table read split by split on the host, a padded chunk a
        step. The jitted step is stored like the slab program, under a key
        that holds the mesh's size and the chunk's shape, so a warm query
        traces nothing on any mesh."""
        it = self._chunks(chunk_rows)
        first = next(it, None)
        if first is None:
            from trino_tpu.exec.fragments import FusedUnsupported

            raise FusedUnsupported("streaming scan with zero splits")
        parts, cap = first
        chunk, counts = _pad_batch(self.mesh, parts, cap)
        programs = getattr(self.executor, "programs", None)

        def key():
            return self._program_key(
                "step", self.n, cap, counts is None, chunk.sel is None,
                _columns_shape(chunk),
            )

        dicts = self._traced_dicts(chunk)
        hit = self._stored(programs, key(), dicts)
        if hit is not None:
            step, meta, _ = hit
            self._sensitive_dicts = set(meta["sensitive_dicts"])
            self.executor.count_program(hit=True)
            state = step(
                self._init_state(meta), chunk, counts, self.params, self._builds
            )
        else:
            meta = self._collect_meta(chunk)
            step = jax.jit(self._make_step(meta), donate_argnums=(0,))
            # the real trace happens on this first call — log dictionary
            # accesses here too (eval_shape in _collect_meta covers the same
            # path, but belt-and-braces keeps the invalidation set complete)
            from trino_tpu.columnar import Dictionary

            prev_log = Dictionary.begin_trace_log()
            t0 = time.perf_counter()
            try:
                state = step(
                    self._init_state(meta), chunk, counts, self.params,
                    self._builds,
                )
            finally:
                log = Dictionary.end_trace_log(prev_log)
            self._sensitive_dicts |= set(log.get("growth_sensitive", ()))
            meta["sensitive_dicts"] = frozenset(self._sensitive_dicts)
            self.executor.count_program(
                hit=False,
                compile_ms=(time.perf_counter() - t0) * 1000.0,
                stored=programs is not None,
            )
            if programs is not None:
                programs[key()] = (step, meta, dicts)
        for parts, cap in it:
            chunk, counts = _pad_batch(self.mesh, parts, cap)
            state = step(state, chunk, counts, self.params, self._builds)
        self._check_overflow(state, None, meta)
        return self._finish(state, meta)

    def _check_overflow(self, state, prog_key, meta) -> None:
        """Overflow handling: inside a fragmented query, queue the flag
        vector on the executor's deferred list (ONE device->host pull per
        query, in ``_execute_fragments``); otherwise pull and raise here
        so the caller's retry loop grows the fired budgets."""
        names = meta["ovf_names"]
        dfl = getattr(self.executor, "deferred_flags", None)
        if dfl is not None:
            dfl.append((prog_key, names, state["overflow"], self.caps))
            return
        fired = np.asarray(state["overflow"])
        if fired.any():
            raise StreamOverflow(
                {nm: int(f) for nm, f in zip(names, fired) if f}
            )

    # === device-resident slab source =====================================

    def _run_device_slab(self, chunk_rows: int) -> Optional[Result]:
        """Stream a device-resident table: the connector stages the whole
        table into HBM once (``device_slab``), and each chunk is a
        ``dynamic_slice`` INSIDE the compiled step — zero per-chunk host
        work or host->device transfer, one dispatch per chunk.

        On a mesh of several devices the connector stages the table
        row-sharded (``slab_shard_rows``: each device an equal run of the
        rows, padded alike), and every device walks its own rows: a step is
        ``cap`` rows of each shard, sliced inside a ``shard_map``."""
        connector = self.executor.catalogs.get(self.scan.catalog)
        # device chunks can be much larger than host chunks (no transfer
        # to overlap, and fewer dispatches beat smaller sorts); on a mesh
        # the width is each device's
        cap = bucket_capacity(
            max(1, int(self.executor.session.get("stream_device_chunk_rows")))
        )
        slab = None
        chunk_cols = None
        stage = getattr(connector, "device_slab", None)
        if stage is not None:
            limit = int(self.executor.session.get("stream_device_cache_bytes"))
            # what this query stages counts as its ingest: the whole
            # table on its first stream, 0 while the slab stays resident
            stats = self.executor.ingest_stats
            stats.setdefault("h2d_bytes", 0)
            staged = stage(
                self.scan.schema, self.scan.table, self.scan.column_names,
                cap, limit, stats, self.mesh if self.n > 1 else None,
            )
            if staged is not None:
                slab, num_rows = staged
                cap = min(cap, slab.capacity // self.n)
        if slab is None:
            gen = getattr(connector, "device_generator", None)
            if gen is None or self.n > 1:
                return None
            spec = gen(self.scan.schema, self.scan.table, self.scan.column_names)
            if spec is None:
                return None
            chunk_cols, num_rows = spec
            if num_rows <= 0:
                return None
        programs = getattr(self.executor, "programs", None)
        # rows each shard holds (one shard: all of them), the loop's bound
        shard_rows = slab_shard_rows(num_rows, self.n)
        # the step widens with the group budget (``slab_step_rows``); which
        # way the step groups is asked only once the budget could widen it
        base, meta = cap, None
        if self.nkeys and SLAB_ROWS_PER_GROUP * self.G > base:
            meta = self._probe_meta(slab, chunk_cols, base)
            cap = slab_step_rows(
                base, self.G, not meta["slots"],
                slab.capacity // self.n
                if slab is not None else bucket_capacity(num_rows),
            )
        # wide pipelines (many payload lanes) can exceed scoped vmem at
        # large chunk sizes: on a compile failure, halve the chunk (a wide
        # step that no longer divides the slab's padded rows clamps its
        # last offset) and REMEMBER the working cap, for the budget the
        # compiler refused the wider one at, so warm queries never repeat
        # the failing compile
        cap_key = ("slabcap", self.site, self.G)
        if programs is not None:
            cap = min(cap, programs.get(cap_key, cap))
        attempt = 0
        while True:
            attempt += 1
            with get_tracer().span(
                "stream.slab",
                attrs={
                    "site": self.site,
                    "steps": (int(shard_rows[0]) + cap - 1) // cap,
                    "cap": cap,
                    "baseCap": base,
                    "shards": self.n,
                    "groups": self.G,
                    "params": len(self.params or ()),
                    "attempt": attempt,
                },
            ) as span:
                res = self._slab_attempt(
                    programs, slab, chunk_cols, shard_rows, cap, span, meta
                )
                below = self._build_join_names()
                joins = self.caps.joins(lambda nm: nm not in below)
                if joins:
                    span.set("joins", joins)
            if res is not None:
                if attempt > 1 and programs is not None:
                    programs[cap_key] = cap
                return res
            cap //= 2

    def _probe_meta(self, slab, chunk_cols, cap: int) -> dict:
        """``_collect_meta`` over an abstract chunk of ``cap`` rows of each
        shard of the slab (or of the connector's generator): nothing in it
        depends on ``cap``."""
        cap *= self.n
        if slab is not None:
            probe_cols = [
                Column(
                    c.type,
                    jax.ShapeDtypeStruct((cap,) + c.data.shape[1:], c.data.dtype),
                    None
                    if c.valid is None
                    else jax.ShapeDtypeStruct((cap,), jnp.bool_),
                    c.dictionary,
                )
                for c in slab.columns
            ]
        else:
            probe_cols = [
                Column(
                    c.type,
                    jax.ShapeDtypeStruct((cap,) + c.data.shape[1:], c.data.dtype),
                    None,
                    c.dictionary,
                )
                for c in jax.eval_shape(
                    lambda: chunk_cols(jnp.zeros((), jnp.int32), cap)
                )
            ]
        probe_chunk = Batch(
            probe_cols, cap, jax.ShapeDtypeStruct((cap,), jnp.bool_)
        )
        # a resident slab's dictionaries are final at staging, so their
        # lengths are the group keys' domains; a program stored with them
        # lives in a store that the table's data version names (engine.py)
        return self._collect_meta(probe_chunk, resident=slab is not None)

    def _slab_attempt(
        self, programs, slab, chunk_cols, shard_rows, cap: int, span,
        meta: Optional[dict] = None,
    ) -> Optional[Result]:
        """One run of the slab program at chunk size ``cap``: the stored
        program if there is one, else trace, compile and store it (``meta``
        where the caller has probed it already). None when the compiler
        refused the size and a halved ``cap`` may fit."""
        n_steps = np.int32((int(shard_rows[0]) + cap - 1) // cap)
        # one device: the table's row count; a mesh: each shard's
        rows = np.int64(shard_rows[0]) if self.n == 1 else shard_rows
        dicts = self._traced_dicts(slab)

        def key():
            return self._program_key("slab", cap, slab is None, self.n)

        hit = self._stored(programs, key(), dicts)
        span.set("cacheHit", hit is not None)
        if hit is not None:
            program, meta, _ = hit
            self._note_group_by(span, meta)
            state = program(
                self._init_state(meta), slab, n_steps, rows, self.params,
                self._builds,
            )
            self.executor.count_program(hit=True)
            self._check_overflow(state, key(), meta)
            return self._finish(state, meta)
        if meta is None:
            meta = self._probe_meta(slab, chunk_cols, cap)
        self._note_group_by(span, meta)
        program = jax.jit(
            self._make_slab_program(meta, cap, chunk_cols),
            donate_argnums=(0,),
        )
        t0 = time.perf_counter()
        try:
            state = program(
                self._init_state(meta), slab, n_steps, rows, self.params,
                self._builds,
            )
        except jax.errors.JaxRuntimeError as e:
            msg = str(e).lower()
            compile_failure = any(
                tok in msg
                for tok in ("compile", "vmem", "resource_exhausted")
            )
            if not compile_failure or cap <= SLAB_MIN_ROWS:
                raise
            return None
        # trace + lower + compile are synchronous in the first call and
        # the loop itself is dispatched, so this wall is theirs
        self.executor.count_program(
            hit=False,
            compile_ms=(time.perf_counter() - t0) * 1000.0,
            stored=programs is not None,
        )
        if programs is not None:
            # (the key now holds the capacities the trace consulted)
            programs[key()] = (program, meta, dicts)
        self._check_overflow(state, key(), meta)
        return self._finish(state, meta)

    def _note_group_by(self, span, meta: dict) -> None:
        """Which way the step's chunk partial groups its rows, on the span."""
        if self.nkeys:
            span.set("groupBy", "domain" if meta["slots"] else "sort")
            if meta["slots"]:
                span.set("slots", meta["slots"])

    def _make_slab_program(self, meta: dict, cap: int, chunk_cols=None):
        """The ENTIRE chunk loop as one compiled program: a
        ``lax.fori_loop`` whose body takes chunk i — dynamic-sliced from
        the resident slab, or computed by the connector's traced
        generator (``chunk_cols``) — and folds it into the carried
        accumulators. One dispatch per query regardless of table size,
        and the dynamic trip count means one compilation serves any row
        count. On a mesh chunk i is rows ``[i*cap, (i+1)*cap)`` of every
        shard (``_shard_chunk``); ``rows`` is then the shards' row counts."""
        inner = self._make_step(meta)
        n = self.n

        def body_for(slab, rows, params, builds):
            def body(i, state):
                # int64 offset: i*cap wraps int32 past 2^31 rows (the
                # generator path has no table-size bound)
                off = i.astype(jnp.int64) * cap
                # rows left: the table's, or on a mesh each shard's
                cnt = jnp.minimum(cap, (rows - off).astype(jnp.int32))
                skip = None
                if slab is not None:
                    held = slab.capacity // n  # rows a shard holds, padded
                    start = off
                    if held % cap:
                        # a wide step that does not divide the padded
                        # rows: the last one starts where it still fits
                        # and masks the rows the step before it read
                        start = jnp.minimum(off, held - cap)
                        skip = (off - start).astype(jnp.int32)
                    if n > 1:
                        cols = self._shard_chunk(slab, start, cap)
                    else:
                        cols = [
                            Column(
                                c.type,
                                jax.lax.dynamic_slice_in_dim(c.data, start, cap, axis=0),
                                None
                                if c.valid is None
                                else jax.lax.dynamic_slice_in_dim(c.valid, start, cap, axis=0),
                                c.dictionary,
                            )
                            for c in slab.columns
                        ]
                else:
                    cols = chunk_cols(off, cap)
                pos = jnp.arange(n * cap, dtype=jnp.int32)
                if n > 1:
                    # each row's place in its shard's step, and that shard's
                    cnt, pos = cnt[pos // cap], pos % cap
                live = pos < cnt
                if skip is not None:
                    live = (pos >= skip) & (pos - skip < cnt)
                return inner(
                    state, Batch(cols, n * cap, live), None, params, builds
                )

            return body

        def program(state, slab, n_steps, rows, params, builds):
            return jax.lax.fori_loop(
                0, n_steps, body_for(slab, rows, params, builds), state
            )

        return program

    def _shard_chunk(self, slab: Batch, start, cap: int) -> list[Column]:
        """Rows ``[start, start + cap)`` of every shard of a row-sharded
        slab, as the columns of one chunk of ``n * cap`` rows sharded alike:
        each device slices what it holds, nothing moves."""
        arrays = [
            a for c in slab.columns for a in (c.data, c.valid) if a is not None
        ]

        def take(at, *held):
            return tuple(
                jax.lax.dynamic_slice_in_dim(a, at, cap, axis=0) for a in held
            )

        taken = iter(smap(
            take,
            mesh=self.mesh,
            in_specs=(PS(),) + (PS(AXIS),) * len(arrays),
            out_specs=(PS(AXIS),) * len(arrays),
        )(start, *arrays))
        return [
            Column(
                c.type, next(taken),
                None if c.valid is None else next(taken), c.dictionary,
            )
            for c in slab.columns
        ]

    # === metadata (abstract pass over the first chunk) ===================

    @staticmethod
    def _with_params(inputs: dict, params) -> dict:
        """``inputs`` with the hoisted literals under ``__params__``, where
        ``_FragmentTracer`` (and with it every ``ExprCompiler``) reads them."""
        return inputs if params is None else {**inputs, "__params__": params}

    def _tracer_for(self, chunk: Batch, params, builds):
        """The chunk's tracer. ``params`` and ``builds`` are the caller's
        OWN arguments: the traced ones inside a compiled step, the abstract
        ones under ``_collect_meta``'s ``eval_shape``; never ``self.params``
        or ``self._builds`` from inside a stored program, which would bake
        the first query's literals and build sides in."""
        from trino_tpu.exec.fragments import _FragmentTracer

        tracer = _FragmentTracer(
            self.executor,
            self._with_params({f"scan{id(self.scan)}": chunk}, params),
            {
                f"scan{id(self.scan)}": {
                    s.name: i for i, s in enumerate(self.scan.symbols)
                }
            },
            self.caps,
        )
        # build sides of probe-spine joins: already materialized, so the
        # chunk trace reads them as its arguments instead of re-executing
        # the build per chunk
        for root, layout, batch, unique in zip(
            self.build_roots, self._build_layouts, builds, self._unique
        ):
            tracer._memo[id(root)] = Result(batch, dict(layout))
            tracer.unique_builds[id(root)] = unique
        return tracer

    def _chunk_prep(self, tracer):
        res = tracer._exec(self.agg.source)
        sel = res.batch.selection_mask()
        agg_inputs, specs, string_dicts = tracer._agg_inputs(self.agg, res)
        keys = [res.opt_pair(k) for k in self.agg.group_keys]
        key_dicts = [res.column(k).dictionary for k in self.agg.group_keys]
        return agg_inputs, specs, string_dicts, keys, key_dicts, sel

    def _collect_meta(self, chunk: Batch, resident: bool = False) -> dict:
        """Static metadata (specs/widths/dicts) via abstract evaluation —
        no device compute; the first chunk is only executed by the step.
        Dictionary accesses that embed growth-sensitive constants (rank
        tables, missed encodes) are recorded so later chunks know whether
        growing a dictionary invalidates the step.

        ``resident``: the chunk is a slice of a staged slab, whose
        dictionaries no later chunk grows, so the step may take their
        lengths as the group keys' domains (``key_domains``; ``slots`` is
        the chunk partial's slot count where that takes the domain path).
        The host-chunk stream grows dictionaries chunk by chunk
        (``_canonicalize_dicts``): a length baked into its step would go
        stale, so it passes none and keeps the sort path."""
        from trino_tpu.columnar import Dictionary

        box = {}

        def probe(ch, params, builds):
            tracer = self._tracer_for(ch, params, builds)
            agg_inputs, specs, string_dicts, keys, key_dicts, sel = (
                self._chunk_prep(tracer)
            )
            box["specs"] = specs
            box["string_dicts"] = string_dicts
            box["key_dicts"] = key_dicts
            box["key_dtypes"] = [kd.dtype for kd, _ in keys]
            box["key_domains"] = (
                key_domains_from(keys, key_dicts) if resident else None
            )
            box["slots"] = domain_slots(
                keys, agg_inputs, specs, self.G, box["key_domains"]
            )
            # per-chunk overflow sources (probe-spine join capacities);
            # execution order is deterministic, so the step trace will
            # produce flags in this same order
            box["ovf_names"] = [nm for nm, _ in tracer.overflows]
            return sel

        prev_log = Dictionary.begin_trace_log()
        try:
            jax.eval_shape(probe, chunk, self.params, self._builds)
        finally:
            log = Dictionary.end_trace_log(prev_log)
        self._sensitive_dicts = set(log.get("growth_sensitive", ()))
        specs = box["specs"]
        string_dicts = box["string_dicts"]
        key_dicts = box["key_dicts"]
        widths = []
        for spec in specs:
            if spec.kind == "sum128":
                widths.append(3)
            elif spec.kind == "sum128w":
                widths.append(5)
            else:
                widths.append(1)
        combine = []
        for spec in specs:
            if spec.kind in ("min", "max"):
                combine.append(spec.kind)
            else:
                combine.append("sum")  # counts and (limb) sums add
        return {
            "specs": specs,
            "combine": combine,
            "widths": widths,
            "string_dicts": string_dicts,
            "key_dicts": key_dicts,
            "key_dtypes": box["key_dtypes"],
            "key_domains": box["key_domains"],
            "slots": box["slots"],
            "ovf_names": [self.site] + box["ovf_names"],
        }

    def _init_state(self, meta: dict) -> dict:
        rows = self.n * self.G if self.nkeys else self.n
        sh = NamedSharding(self.mesh, PS(AXIS))

        def zeros(shape, dt):
            return jax.device_put(jnp.zeros(shape, dtype=dt), sh)

        state: dict = {
            "overflow": jnp.zeros(len(meta["ovf_names"]), dtype=jnp.int32)
        }
        if self.nkeys:
            state["key_data"] = [
                zeros((rows,), dt) for dt in meta["key_dtypes"]
            ]
            state["key_valid"] = [
                zeros((rows,), jnp.bool_) for _ in range(self.nkeys)
            ]
            state["live"] = zeros((rows,), jnp.bool_)
        state["values"] = [
            zeros((rows,) if w == 1 else (rows, w), jnp.int64)
            for w in meta["widths"]
        ]
        state["counts"] = [zeros((rows,), jnp.int64) for _ in meta["specs"]]
        return state

    # === the compiled step ==============================================

    def _make_step(self, meta: dict):
        specs = meta["specs"]
        combine = meta["combine"]
        widths = meta["widths"]
        nkeys, G, n = self.nkeys, self.G, self.n
        nspec = len(specs)
        sagg = self

        def step(state, chunk: Batch, counts, params, builds):
            if counts is not None:
                # per-shard valid-row counts (dynamic) instead of a host
                # mask: tail chunks keep the same pytree structure, so the
                # step compiles exactly once per stream
                cap = chunk.capacity // sagg.n
                pos = jnp.arange(chunk.capacity, dtype=jnp.int32)
                live = pos % cap < counts[pos // cap]
                chunk = Batch(chunk.columns, chunk.num_rows, live)
            tracer = sagg._tracer_for(chunk, params, builds)
            agg_inputs, _specs, _sd, keys, _kd, sel = sagg._chunk_prep(tracer)
            prev_ovf = state["overflow"]
            if nkeys == 0:
                out = sagg._step_global(
                    state, sel, agg_inputs, specs, combine, widths
                )
            else:
                out = sagg._step_grouped(
                    state, keys, sel, agg_inputs, specs, combine, widths,
                    meta["key_domains"],
                )
            # overflow lanes: [agg] + per-chunk join capacities, max'd
            # with the carried vector
            flags = [jnp.reshape(out["overflow"], ())] + [
                jnp.reshape(f.astype(jnp.int32), ())
                for _, f in tracer.overflows
            ]
            out["overflow"] = jnp.maximum(prev_ovf, jnp.stack(flags))
            return out

        return step

    def _step_grouped(self, state, keys, sel, agg_inputs, specs, combine,
                      widths, key_domains):
        nkeys, G, n = self.nkeys, self.G, self.n
        nspec = len(specs)
        Gc = G  # chunk groups bounded by the same budget

        from trino_tpu.exec.fragments import need_flag, pack_opt_pairs

        flat, pack = pack_opt_pairs(keys, sel, agg_inputs)
        flat.extend(state["key_data"])
        flat.extend(state["key_valid"])
        flat.append(state["live"])
        flat.extend(state["values"])
        flat.extend(state["counts"])

        def shard_step(*ops):
            lkeys, lsel, linputs, i = pack.unpack(ops)
            skd = list(ops[i : i + nkeys]); i += nkeys
            skv = list(ops[i : i + nkeys]); i += nkeys
            slive = ops[i]; i += 1
            svals = list(ops[i : i + nspec]); i += nspec
            scnts = list(ops[i : i + nspec]); i += nspec

            # 1) chunk partial: raw rows -> chunk groups
            (ckd, ckv), craw, cng, covf = group_aggregate(
                lkeys, lsel, linputs, specs, Gc, key_domains
            )
            clive = jnp.arange(Gc) < cng
            cvals, ccnts = [], []
            for spec, r in zip(specs, craw):
                if spec.kind in ("count", "count_star"):
                    v = r.astype(jnp.int64)
                    cvals.append(v)
                    ccnts.append(v)
                else:
                    cvals.append(r[0])
                    ccnts.append(r[1].astype(jnp.int64))

            # 2) merge state + chunk groups (lane-expanded for limb sums)
            mkeys = [
                (
                    jnp.concatenate([skd[k], ckd[k].astype(skd[k].dtype)]),
                    jnp.concatenate([skv[k], ckv[k]]),
                )
                for k in range(nkeys)
            ]
            msel = jnp.concatenate([slive, clive])
            ones = jnp.ones_like(msel)
            minputs, mspecs, mplan = [], [], []
            for j in range(nspec):
                sv, cv = svals[j], cvals[j]
                sc, cc = scnts[j], ccnts[j]
                if widths[j] == 1:
                    mv = jnp.concatenate([sv, cv.astype(jnp.int64)])
                    if combine[j] in ("min", "max"):
                        valid = jnp.concatenate([sc > 0, cc > 0])
                    else:
                        valid = ones
                    minputs.append((mv, valid))
                    mspecs.append(AggSpec(combine[j]))
                    mplan.append(("v", j, 0))
                else:
                    for lane in range(widths[j]):
                        mv = jnp.concatenate([sv[:, lane], cv[:, lane]])
                        minputs.append((mv, ones))
                        mspecs.append(AggSpec("sum"))
                        mplan.append(("v", j, lane))
                minputs.append((jnp.concatenate([sc, cc]), ones))
                mspecs.append(AggSpec("sum"))
                mplan.append(("c", j, 0))
            (nkd, nkv), nraw, nng, novf = group_aggregate(
                mkeys, msel, minputs, mspecs, G, key_domains
            )
            nlive = jnp.arange(G) < nng
            nvals = [None] * nspec
            ncnts = [None] * nspec
            lanes: dict[int, list] = {}
            for (kind, j, lane), r in zip(mplan, nraw):
                val = r[0]
                if kind == "c":
                    ncnts[j] = val.astype(jnp.int64)
                elif widths[j] == 1:
                    nvals[j] = val
                else:
                    lanes.setdefault(j, [None] * widths[j])[lane] = val
            for j, ln in lanes.items():
                nvals[j] = jnp.stack(ln, axis=1)
            # the budget holds both the chunk's groups and the merged ones
            ovf = jax.lax.pmax(
                need_flag(covf | novf, jnp.maximum(cng, nng)), AXIS
            )
            return (
                tuple(nkd), tuple(nkv), nlive,
                tuple(nvals), tuple(ncnts), ovf,
            )

        out_specs = (
            tuple(PS(AXIS) for _ in range(nkeys)),
            tuple(PS(AXIS) for _ in range(nkeys)),
            PS(AXIS),
            tuple(PS(AXIS) for _ in range(nspec)),
            tuple(PS(AXIS) for _ in range(nspec)),
            PS(),
        )
        mapped = smap(
            shard_step,
            mesh=self.mesh,
            in_specs=(PS(AXIS),) * len(flat),
            out_specs=out_specs,
        )
        nkd, nkv, nlive, nvals, ncnts, ovf = mapped(*flat)
        return {
            "key_data": list(nkd),
            "key_valid": list(nkv),
            "live": nlive,
            "values": list(nvals),
            "counts": list(ncnts),
            # chunk-local agg overflow; the caller folds it into the
            # carried per-source overflow vector
            "overflow": ovf.astype(jnp.int32),
        }

    def _step_global(self, state, sel, agg_inputs, specs, combine, widths):
        from trino_tpu.exec.fragments import pack_opt_pairs

        nspec = len(specs)
        flat, pack = pack_opt_pairs([], sel, agg_inputs)
        flat.extend(state["values"])
        flat.extend(state["counts"])

        def shard_step(*ops):
            _, lsel, linputs, i = pack.unpack(ops)
            svals = list(ops[i : i + nspec]); i += nspec
            scnts = list(ops[i : i + nspec]); i += nspec
            raw = global_aggregate(lsel, linputs, specs)
            outs_v, outs_c = [], []
            for j, (spec, r) in enumerate(zip(specs, raw)):
                if spec.kind in ("count", "count_star"):
                    cv = jnp.reshape(r.astype(jnp.int64), (1,))
                    cc = cv
                else:
                    cv = r[0]
                    cv = cv if getattr(cv, "ndim", 0) == 2 else jnp.reshape(cv, (1,))
                    cc = jnp.reshape(r[1].astype(jnp.int64), (1,))
                sv, sc = svals[j], scnts[j]
                if combine[j] == "min":
                    nv = jnp.where(
                        sc == 0, cv, jnp.where(cc == 0, sv, jnp.minimum(sv, cv))
                    )
                elif combine[j] == "max":
                    nv = jnp.where(
                        sc == 0, cv, jnp.where(cc == 0, sv, jnp.maximum(sv, cv))
                    )
                else:
                    nv = sv + jnp.reshape(cv, sv.shape)
                outs_v.append(jnp.reshape(nv, sv.shape))
                outs_c.append(sc + cc)
            return tuple(outs_v), tuple(outs_c)

        mapped = smap(
            shard_step,
            mesh=self.mesh,
            in_specs=(PS(AXIS),) * len(flat),
            out_specs=(
                tuple(PS(AXIS) for _ in range(nspec)),
                tuple(PS(AXIS) for _ in range(nspec)),
            ),
        )
        nvals, ncnts = mapped(*flat)
        return {
            "values": list(nvals),
            "counts": list(ncnts),
            "overflow": jnp.zeros((), dtype=jnp.int32),  # global agg: none
        }

    # === result assembly =================================================

    def _finish(self, state, meta) -> Result:
        if self.agg.step == "partial":
            return self._finish_partial(state, meta)
        return self._finish_single(state, meta)

    def _acc_value_column(self, vsym, spec, sdict, v, c):
        """Accumulator wire representation (mirrors _agg_partial)."""
        from trino_tpu.ops import decimal128 as D128

        val = v
        if getattr(val, "ndim", 1) == 2 and val.shape[1] in (3, 5):
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
        return Column(vsym.type, val, None, sdict)

    def _finish_partial(self, state, meta) -> Result:
        agg = self.agg
        cols: list[Column] = []
        layout: dict[str, int] = {}
        if self.nkeys:
            for i, ksym in enumerate(agg.group_keys):
                cols.append(
                    Column(
                        ksym.type,
                        state["key_data"][i].astype(ksym.type.storage_dtype),
                        state["key_valid"][i],
                        meta["key_dicts"][i],
                    )
                )
                layout[ksym.name] = len(cols) - 1
            live = state["live"]
            total = self.n * self.G
        else:
            live = jnp.ones(self.n, dtype=jnp.bool_)
            total = self.n
        for (vsym, csym), spec, sdict, v, c in zip(
            agg.acc_symbols,
            meta["specs"],
            meta["string_dicts"],
            state["values"],
            state["counts"],
        ):
            if spec.kind in ("count", "count_star"):
                cols.append(
                    Column(T.BIGINT, v.reshape(-1).astype(jnp.int64), None)
                )
                layout[vsym.name] = len(cols) - 1
                continue
            cols.append(self._acc_value_column(vsym, spec, sdict, v, c))
            layout[vsym.name] = len(cols) - 1
            cols.append(Column(T.BIGINT, c.astype(jnp.int64), None))
            layout[csym.name] = len(cols) - 1
        return Result(Batch(cols, total, live), layout)

    def _finish_single(self, state, meta) -> Result:
        from trino_tpu.exec.fragments import _FragmentTracer

        agg = self.agg
        tracer = _FragmentTracer(self.executor, {}, {}, self.caps)
        if self.nkeys:
            results = []
            for spec, v, c in zip(
                meta["specs"], state["values"], state["counts"]
            ):
                if spec.kind in ("count", "count_star"):
                    results.append(v.reshape(-1))
                else:
                    results.append((v, c))
            total = self.n * self.G
            cols = []
            for i, ksym in enumerate(agg.group_keys):
                cols.append(
                    Column(
                        ksym.type,
                        state["key_data"][i].astype(ksym.type.storage_dtype),
                        state["key_valid"][i],
                        meta["key_dicts"][i],
                    )
                )
            cols.extend(
                tracer._finalize_traced(
                    agg, results, meta["string_dicts"], total
                )
            )
            layout = {s.name: i for i, s in enumerate(agg.output_symbols)}
            return Result(Batch(cols, total, state["live"]), layout)
        # global: fold the n per-shard accumulators on host (n rows)
        results = []
        for spec, v, c in zip(meta["specs"], state["values"], state["counts"]):
            vn = np.asarray(v)
            cn = np.asarray(c)
            if spec.kind in ("count", "count_star"):
                results.append(jnp.asarray([int(vn.sum())]))
            elif spec.kind in ("min", "max"):
                valid = cn > 0
                if valid.any():
                    vv = vn[valid]
                    val = int(vv.min() if spec.kind == "min" else vv.max())
                else:
                    val = 0
                results.append(
                    (jnp.asarray([val]), jnp.asarray([int(cn.sum())]))
                )
            else:
                ssum = vn.sum(axis=0)
                ssum = ssum[None] if ssum.ndim else np.asarray([ssum])
                results.append(
                    (jnp.asarray(ssum), jnp.asarray([int(cn.sum())]))
                )
        cols = tracer._finalize_traced(agg, results, meta["string_dicts"], 1)
        layout = {s.name: i for i, s in enumerate(agg.output_symbols)}
        return Result(Batch(cols, 1, jnp.ones(1, dtype=jnp.bool_)), layout)


# === host-side batch helpers ================================================


def _columns_shape(b: Batch) -> tuple:
    """What a traced program holds of a batch's columns beside its rows:
    each column's type, storage and whether it carries a validity mask."""
    return tuple(
        (str(c.type), str(c.data.dtype), c.valid is None) for c in b.columns
    )


def _slice_rows(b: Batch, lo: int, hi: int) -> Batch:
    cols = []
    for c in b.columns:
        data, valid = c.to_numpy()
        v = valid[lo:hi]
        cols.append(
            Column(c.type, data[lo:hi], None if v.all() else v, c.dictionary)
        )
    out = Batch(cols, hi - lo)
    if b.sel is not None:
        sel = np.asarray(b.sel)[lo:hi]
        out = Batch(cols, hi - lo, sel)
    return out


def _empty_like(b: Batch) -> Batch:
    cols = [
        Column(
            c.type,
            np.zeros(
                (0,) + np.asarray(c.data).shape[1:],
                dtype=np.asarray(c.data).dtype,
            ),
            None,
            c.dictionary,
        )
        for c in b.columns
    ]
    return Batch(cols, 0)


def _pad_batch(mesh, parts: list[Batch], cap: int):
    """shard_batch with every part padded to exactly ``cap`` rows so each
    step shares one compiled shape.

    Returns (chunk, counts): when no part carries a selection mask, the
    padding is expressed as per-shard valid-row *counts* (an (n,) int32
    array the compiled step turns into a mask in-trace) — no mask bytes
    cross to the device, and full and tail chunks share one pytree
    structure (one compile per stream). Sources that do carry ``sel``
    fall back to explicit masks (counts=None)."""
    if all(p.sel is None for p in parts):
        counts = np.asarray([p.num_rows for p in parts], dtype=np.int32)
        padded = []
        for p in parts:
            if p.capacity == cap and p.num_rows == cap:
                padded.append(p)
                continue
            cols = []
            for c in p.columns:
                data = np.asarray(c.data)
                pad = cap - data.shape[0]
                if pad:
                    data = np.concatenate(
                        [data, np.zeros((pad,) + data.shape[1:], dtype=data.dtype)]
                    )
                valid = c.valid
                if valid is not None:
                    valid = np.concatenate(
                        [np.asarray(valid), np.zeros(pad, dtype=np.bool_)]
                    ) if pad else valid
                cols.append(Column(c.type, data, valid, c.dictionary))
            # num_rows=cap: padding liveness is carried by `counts`
            padded.append(Batch(cols, cap))
        return shard_batch(mesh, padded), counts
    padded = []
    for p in parts:
        if p.capacity == cap and p.sel is None and p.num_rows == cap:
            padded.append(p)
            continue
        cols = []
        for c in p.columns:
            data, valid = c.to_numpy()
            pad = cap - data.shape[0]
            if pad:
                data = np.concatenate(
                    [data, np.zeros((pad,) + data.shape[1:], dtype=data.dtype)]
                )
                valid = np.concatenate([valid, np.zeros(pad, dtype=np.bool_)])
            cols.append(Column(c.type, data, valid, c.dictionary))
        sel = np.zeros(cap, dtype=np.bool_)
        sel[: p.num_rows] = True
        if p.sel is not None:
            sel[: p.capacity] &= np.asarray(p.sel)
        padded.append(Batch(cols, cap, sel))
    return shard_batch(mesh, padded), None