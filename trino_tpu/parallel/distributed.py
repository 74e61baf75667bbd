"""Distributed (mesh-SPMD) executor.

Reference: Trino's distributed execution — stages over workers
(``SqlQueryScheduler.java:538``), partitioned/broadcast joins
(``DetermineJoinDistributionType.java``), partial/final aggregation split
(``AggregationNode`` steps + ``spi/function`` combine contract).

TPU translation:
- scans: splits assigned round-robin to mesh shards (SOURCE_DISTRIBUTION)
- filter/project: elementwise on row-sharded global arrays (sharding
  propagates; XLA fuses)
- aggregation: per-shard partial (shard_map sort+segment-reduce) ->
  small partial tables gathered -> final re-aggregation (combine)
- joins: broadcast (all_gather build side) or partitioned
  (lax.all_to_all hash repartition of both sides) chosen by size
- sort/topN/limit/output: final gather (SINGLE_DISTRIBUTION analog)
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as PS

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column, bucket_capacity
from trino_tpu.config import Session
from trino_tpu.connectors.api import CatalogManager
from trino_tpu.exec.local import ExecutionError, LocalExecutor, Result
from trino_tpu.obs.trace import get_tracer
from trino_tpu.ops import join as J
from trino_tpu.ops.aggregation import AggSpec, group_aggregate
from trino_tpu.parallel.mesh import AXIS, make_mesh, shard_batch, smap
from trino_tpu.parallel import exchange as X
from trino_tpu.planner import plan as P


class DistributedExecutor(LocalExecutor):
    """Executes logical plans SPMD over a device mesh."""

    def __init__(
        self,
        catalogs: CatalogManager,
        session: Session,
        mesh: Optional[Mesh] = None,
        memory_ctx=None,
    ):
        super().__init__(catalogs, session, memory_ctx=memory_ctx)
        self.mesh = mesh or make_mesh()
        # per-query exchange observability (surfaced via /v1/query as
        # exchangeStats); the fused executor adds traced counters, the
        # interpreter path bumps these host-side
        self.exchange_stats: dict = {
            "exchanges": 0,
            "shuffle_rows": 0,
            "padded_shuffle_rows": 0,
            "shuffle_bytes": 0,
            "hot_keys": 0,
            "salted_rows": 0,
            "overflow_retries": 0,
            # dispatched compiled programs on the surviving attempt
            # (whole-pipeline fusion exists to push this toward 1) and
            # fragments that executed inside fused multi-fragment programs
            "dispatchRoundTrips": 0,
            "fusedFragments": 0,
            # RESOURCE_EXHAUSTED compile failures recovered by halving
            # capacities (exec/fragments.py::_Caps.shrink_all)
            "compile_halvings": 0,
        }
        # device-level profiling (obs/profiler.py): per-program XLA
        # cost/memory stats keyed by a stable program label. The fused
        # executor fills this at fragment compile time; this eager path
        # captures its shard_map programs via _profiled_call.
        self.device_stats: dict[str, dict] = {}
        self._device_profiling = bool(session.get("device_profiling"))
        self._profiled_cache: dict = {}  # (label, arg shapes) -> Compiled

    @property
    def n_shards(self) -> int:
        return self.mesh.devices.size

    # === device profiling ===============================================

    def _record_device_stats(
        self, label: str, ds: Optional[dict] = None, compile_ms: float = 0.0
    ) -> None:
        """Fold one program execution's captured XLA stats into the
        per-query map and export the per-program gauges. Called with
        ``ds=None`` for executions of an already-profiled program."""
        ent = self.device_stats.setdefault(
            label, {"executions": 0, "compile_ms": 0.0}
        )
        ent["executions"] += 1
        if compile_ms:
            ent["compile_ms"] = round(ent["compile_ms"] + compile_ms, 3)
        for k, v in (ds or {}).items():
            ent[k] = v
        if ds:
            from trino_tpu.obs.metrics import get_registry

            reg = get_registry()
            if "flops" in ds:
                reg.gauge("trino_tpu_program_flops", fragment=label).set(
                    ds["flops"]
                )
            if "peak_hbm_bytes" in ds:
                reg.gauge(
                    "trino_tpu_program_peak_hbm_bytes", fragment=label
                ).set(ds["peak_hbm_bytes"])

    def device_stats_snapshot(self) -> Optional[dict]:
        """Per-query device-profiling rollup (engine attaches this to the
        statement result; /v1/query serves it as ``deviceStats``)."""
        if not self.device_stats:
            return None
        from trino_tpu.obs.profiler import rollup_device_stats

        snap = rollup_device_stats(self.device_stats)
        snap["programs"] = {k: dict(v) for k, v in self.device_stats.items()}
        return snap

    def _profiled_call(self, label: str, fn, *args):
        """Run one eager shard_map program; with ``device_profiling`` on
        it is AOT-compiled (``jax.jit`` of the same function — identical
        numerics) so XLA cost/memory analysis lands in
        ``device_stats[label]``. Compiled executables are cached per
        argument shapes; any failure falls back to the plain eager call,
        so profiling can never fail a query."""
        if not self._device_profiling:
            return fn(*args)
        import time as _time

        try:
            from trino_tpu.obs.profiler import capture_device_stats

            shapes = tuple(
                (getattr(a, "shape", None), str(getattr(a, "dtype", "")))
                for a in jax.tree_util.tree_leaves(args)
            )
            key = (label, shapes)
            compiled = self._profiled_cache.get(key)
            if compiled is None:
                t0 = _time.perf_counter()
                compiled = jax.jit(fn).lower(*args).compile()
                compile_ms = (_time.perf_counter() - t0) * 1000.0
                self._record_device_stats(
                    label, capture_device_stats(compiled), compile_ms
                )
                if len(self._profiled_cache) >= 64:
                    self._profiled_cache.pop(next(iter(self._profiled_cache)))
                self._profiled_cache[key] = compiled
            else:
                self._record_device_stats(label)
            return compiled(*args)
        except Exception:  # noqa: BLE001 — profiling must never fail a query
            return fn(*args)

    # === scan: splits round-robin over shards ===========================
    def _exec_tablescan(self, node: P.TableScan) -> Result:
        from trino_tpu.columnar import concat_batches

        connector = self.catalogs.get(node.catalog)
        n = self.n_shards
        splits = connector.get_splits(
            node.schema, node.table, target_splits=n * 4, constraint=node.constraint
        )
        if not splits:  # constraint pruned everything
            # shard-compatible empty: one unselected row per shard (a
            # 0-capacity batch would feed zero-sized operands into
            # shard_map programs, which the partitioner rejects)
            from trino_tpu.columnar import Dictionary as _Dict

            parts = []
            for _ in range(n):
                cols = []
                for s in node.symbols:
                    wide = isinstance(s.type, T.DecimalType) and s.type.wide
                    shape = (1, 2) if wide else (1,)
                    cols.append(
                        Column(
                            s.type,
                            np.zeros(shape, dtype=s.type.storage_dtype),
                            None,
                            _Dict([]) if T.is_string(s.type) else None,
                        )
                    )
                parts.append(Batch(cols, 1, np.zeros(1, dtype=np.bool_)))
            return Result(
                shard_batch(self.mesh, parts),
                {s.name: i for i, s in enumerate(node.symbols)},
            )
        layout = {s.name: i for i, s in enumerate(node.symbols)}
        stats = self.ingest_stats
        stats.setdefault("h2d_bytes", 0)

        # device table cache: a warm repeat scan of an unchanged table
        # returns the HBM-resident batch — zero decode, zero H2D
        cache_key, cached = self._cached_scan(node, connector, splits, self.mesh)
        if cached is not None:
            return Result(cached, layout)

        per_shard: list[list[Batch]] = [[] for _ in range(n)]
        with get_tracer().span(
            "ingest.decode",
            attrs={"table": node.table, "splits": len(splits)},
        ):
            for i, b in enumerate(
                self._read_splits(
                    connector, node.schema, node.table, node.column_names,
                    splits,
                )
            ):
                per_shard[i % n].append(b)
        parts = []
        empty_proto = None
        for shard_batches in per_shard:
            if shard_batches:
                parts.append(
                    concat_batches(shard_batches)
                    if len(shard_batches) > 1
                    else shard_batches[0]
                )
                empty_proto = parts[-1]
            else:
                parts.append(None)
        for i, p in enumerate(parts):
            if p is None:
                cols = [
                    Column(c.type, np.zeros(0, dtype=np.asarray(c.data).dtype), None, c.dictionary)
                    for c in empty_proto.columns
                ]
                parts[i] = Batch(cols, 0)
        if self.session.get("coalesced_h2d"):
            from trino_tpu.ingest import shard_batch_coalesced

            batch = shard_batch_coalesced(
                self.mesh,
                parts,
                use_native=bool(self.session.get("native_decode")),
                stats=stats,
                min_bytes=int(self.session.get("coalesce_min_bytes")),
            )
        else:
            batch = shard_batch(self.mesh, parts)

        if cache_key is not None:
            from trino_tpu.memory import batch_nbytes

            peak_hint = max(
                (
                    v.get("peak_hbm_bytes", 0)
                    for v in self.device_stats.values()
                ),
                default=0,
            )
            self._admit_scan(cache_key, batch, batch_nbytes(batch), peak_hint)
        return Result(batch, layout)

    # === partial/final aggregation ======================================
    def _exec_aggregate(self, node: P.Aggregate) -> Result:
        res = self._exec(node.source)
        if not _is_sharded(res.batch):
            return self._aggregate_result(node, res)
        if any(
            fn.distinct or fn.kind == "array_agg" for _, fn in node.aggregates
        ):
            # DISTINCT / array_agg aggregates need a global view — run the
            # single-program path (XLA gathers as needed).
            return self._aggregate_result(node, res)
        if any(
            isinstance(fn.result_type, T.DecimalType) and fn.result_type.wide
            for _, fn in node.aggregates
        ) or any(
            isinstance(k.type, T.DecimalType) and k.type.wide
            for k in node.group_keys
        ):
            # wide DECIMAL sums/keys use 128-bit (hi, lo) lanes whose shapes
            # the stacked partial/combine path below does not carry; the
            # single-program path is exact (XLA shards the segment sums)
            return self._aggregate_result(node, res)
        if not node.group_keys:
            # global agg: compute per-shard partials via masked group-by with
            # a single dummy key, then combine on host
            return self._global_agg_distributed(node, res)

        sel = res.batch.selection_mask()
        keys = [res.pair(k) for k in node.group_keys]
        key_dicts = [res.column(k).dictionary for k in node.group_keys]
        agg_inputs, specs, string_aggs = self._prepare_agg_inputs(node, res)
        n = self.n_shards
        nkeys = len(keys)
        G = 1 << 12
        return self._partial_final_agg(
            node, keys, key_dicts, sel, agg_inputs, specs, string_aggs, G, n, nkeys
        )

    def _partial_final_agg(
        self, node, keys, key_dicts, sel, agg_inputs, specs, string_aggs, G, n, nkeys
    ) -> Result:

        in_specs = tuple(PS(AXIS) for _ in range(2 * nkeys + 1)) + tuple(
            PS(AXIS) for _ in range(sum(2 if p else 0 for p in agg_inputs))
        )

        flat_inputs = []
        for kd, kv in keys:
            flat_inputs.extend([kd, kv])
        flat_inputs.append(sel)
        for p in agg_inputs:
            if p is not None:
                flat_inputs.extend([p[0], p[1]])

        shapes = [bool(p) for p in agg_inputs]

        def partial_agg(*flat):
            i = 0
            local_keys = []
            for _ in range(nkeys):
                local_keys.append((flat[i], flat[i + 1]))
                i += 2
            local_sel = flat[i]
            i += 1
            local_inputs = []
            for has in shapes:
                if has:
                    local_inputs.append((flat[i], flat[i + 1]))
                    i += 2
                else:
                    local_inputs.append(None)
            (kd, kv), results, ng, ovf = group_aggregate(
                local_keys, local_sel, local_inputs, specs, G
            )
            # normalize results to (value, count) pairs — kept as separate
            # arrays (no dtype-unifying stack: int64 sums must stay exact)
            flat_vals = []
            flat_cnts = []
            for spec, r in zip(specs, results):
                if spec.kind in ("count", "count_star"):
                    flat_vals.append(r.astype(jnp.int64))
                    flat_cnts.append(r.astype(jnp.int64))
                else:
                    flat_vals.append(r[0])
                    flat_cnts.append(r[1])
            key_data = jnp.stack([kd[i2].astype(jnp.int64) for i2 in range(nkeys)])
            key_valid = jnp.stack([kv[i2] for i2 in range(nkeys)])
            live = jnp.arange(G) < ng
            ovf_any = jax.lax.pmax(ovf.astype(jnp.int32), AXIS)
            return key_data.T, key_valid.T, tuple(flat_vals), tuple(flat_cnts), live, ovf_any

        mapped = smap(
            partial_agg,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=(
                PS(AXIS),
                PS(AXIS),
                tuple(PS(AXIS) for _ in specs),
                tuple(PS(AXIS) for _ in specs),
                PS(AXIS),
                PS(),
            ),
        )
        key_data_g, key_valid_g, vals_g, cnts_g, live_g, ovf_g = (
            self._profiled_call("partial_agg", mapped, *flat_inputs)
        )
        if bool(np.asarray(ovf_g).max()):
            # some shard exceeded G groups — retry with larger capacity
            if G > (1 << 24):
                raise ExecutionError("per-shard group cardinality too large")
            return self._partial_final_agg(
                node, keys, key_dicts, sel, agg_inputs, specs, string_aggs,
                G << 2, n, nkeys,
            )
        # host-side final combine over n*G partial rows (small)
        kd_np = np.asarray(key_data_g)
        kv_np = np.asarray(key_valid_g)
        vals_np = np.stack([np.asarray(v) for v in vals_g], axis=1)
        cnts_np = np.stack([np.asarray(c) for c in cnts_g], axis=1)
        live_np = np.asarray(live_g)
        return self._final_combine(
            node, kd_np, kv_np, vals_np, cnts_np, live_np, key_dicts, string_aggs
        )

    def _prepare_agg_inputs(self, node, res):
        from trino_tpu.columnar import Dictionary

        agg_inputs = []
        specs = []
        string_aggs: list = []
        for _, fn in node.aggregates:
            if fn.kind == "count_star":
                if fn.filter is not None:
                    fc = res.column(P.Symbol(fn.filter.name, T.BOOLEAN))
                    ones = jnp.ones_like(fc.data, dtype=jnp.int64)
                    agg_inputs.append((ones, fc.data & fc.valid_mask()))
                    specs.append(AggSpec("count"))
                    string_aggs.append(None)
                    continue
                pair = None
                string_aggs.append(None)
            else:
                sym = P.Symbol(fn.argument.name, fn.argument.type)
                c = res.column(sym)
                data, valid = c.data, c.valid_mask()
                if c.dictionary is not None and fn.kind in ("min", "max"):
                    from trino_tpu.exec.local import rank_codes

                    data = rank_codes(c.dictionary, data)
                    string_aggs.append(c.dictionary)
                else:
                    string_aggs.append(None)
                if fn.filter is not None:
                    fc = res.column(P.Symbol(fn.filter.name, T.BOOLEAN))
                    valid = valid & fc.data & fc.valid_mask()
                pair = (data, valid)
            agg_inputs.append(pair)
            specs.append(AggSpec(fn.kind))
        return agg_inputs, specs, string_aggs

    def _final_combine(
        self, node, kd_np, kv_np, vals_np, cnts_np, live_np, key_dicts, string_aggs
    ) -> Result:
        """Combine per-shard partial aggregates (Trino's combine step)."""
        rows = live_np
        kd_np = kd_np[rows]
        kv_np = kv_np[rows]
        vals_np = vals_np[rows]
        cnts_np = cnts_np[rows]
        m = kd_np.shape[0]
        keys = [
            (jnp.asarray(kd_np[:, i]), jnp.asarray(kv_np[:, i]))
            for i in range(len(node.group_keys))
        ]
        combine_inputs = []
        combine_specs = []
        for i, (_, fn) in enumerate(node.aggregates):
            v = jnp.asarray(vals_np[:, i])
            c = jnp.asarray(cnts_np[:, i])
            if fn.kind in ("count", "count_star"):
                combine_inputs.append((v, jnp.ones(m, bool)))
                combine_specs.append(AggSpec("sum"))
            elif fn.kind in ("sum", "avg"):
                combine_inputs.append((v, c > 0))
                combine_specs.append(AggSpec("sum"))
                combine_inputs.append((c, jnp.ones(m, bool)))
                combine_specs.append(AggSpec("sum"))
            else:  # min/max
                combine_inputs.append((v, c > 0))
                combine_specs.append(AggSpec(fn.kind))
                combine_inputs.append((c, jnp.ones(m, bool)))
                combine_specs.append(AggSpec("sum"))
        max_groups = max(1 << 12, bucket_capacity(max(m, 1)))
        sel = jnp.ones(m, bool) if m else jnp.zeros(0, bool)
        if m == 0:
            # no groups anywhere
            cols = [
                Column(k.type, np.zeros(0, dtype=k.type.storage_dtype), None, d)
                for k, d in zip(node.group_keys, key_dicts)
            ]
            for s, fn in node.aggregates:
                cols.append(Column(fn.result_type, np.zeros(0, dtype=fn.result_type.storage_dtype)))
            return Result(
                Batch(cols, 0),
                {s.name: i for i, s in enumerate(node.output_symbols)},
            )
        (fkd, fkv), fres, ng, ovf = group_aggregate(
            keys, sel, combine_inputs, combine_specs, max_groups
        )
        if bool(ovf):
            raise ExecutionError("final aggregation overflow")
        ng = int(ng)
        cols = []
        for i, k in enumerate(node.group_keys):
            valid = np.asarray(fkv[i])[:ng]
            cols.append(
                Column(
                    k.type,
                    np.asarray(fkd[i])[:ng].astype(k.type.storage_dtype),
                    None if valid.all() else valid,
                    key_dicts[i],
                )
            )
        # reassemble per-aggregate results from the combine outputs
        j = 0
        raw_results = []
        for _, fn in node.aggregates:
            if fn.kind in ("count", "count_star"):
                ssum, _cnt = fres[j]
                raw_results.append(np.asarray(ssum)[:ng])
                j += 1
            else:
                vsum, _vcnt = fres[j]
                csum, _ccnt = fres[j + 1]
                raw_results.append((np.asarray(vsum)[:ng], np.asarray(csum)[:ng]))
                j += 2
        cols.extend(
            self._finalize_aggs(node, raw_results, ng, None, string_aggs)
        )
        return Result(
            Batch(cols, ng), {s.name: i for i, s in enumerate(node.output_symbols)}
        )

    def _global_agg_distributed(self, node: P.Aggregate, res: Result) -> Result:
        # add a constant group key, reuse grouped path, then strip it
        dummy = P.Symbol(P.fresh_name("g0"), T.BIGINT)
        ones = jnp.zeros(res.batch.capacity, dtype=jnp.int64)
        cols = list(res.batch.columns) + [Column(T.BIGINT, ones)]
        layout = dict(res.layout)
        layout[dummy.name] = len(cols) - 1
        res2 = Result(Batch(cols, res.batch.num_rows, res.batch.sel), layout)
        node2 = P.Aggregate(node.source, [dummy], node.aggregates, node.step)
        sel = res2.batch.selection_mask()
        keys = [res2.pair(dummy)]
        agg_inputs, specs, string_aggs = self._prepare_agg_inputs(node2, res2)
        out = self._partial_final_agg(
            node2, keys, [None], sel, agg_inputs, specs, string_aggs,
            8, self.n_shards, 1,
        )
        # drop the dummy key column; single row (or zero -> one null row)
        b = out.batch
        agg_cols = b.columns[1:]
        if b.num_rows == 0:
            cols = []
            for (s, fn) in node.aggregates:
                if fn.kind in ("count", "count_star"):
                    cols.append(Column(fn.result_type, np.asarray([0], dtype=np.int64)))
                else:
                    cols.append(
                        Column(
                            fn.result_type,
                            np.zeros(1, dtype=fn.result_type.storage_dtype),
                            np.asarray([False]),
                        )
                    )
            return Result(
                Batch(cols, 1),
                {s.name: i for i, (s, _) in enumerate(node.aggregates)},
            )
        return Result(
            Batch(agg_cols, b.num_rows),
            {s.name: i for i, (s, _) in enumerate(node.aggregates)},
        )

    # === joins ==========================================================
    def _exec_join(self, node: P.Join) -> Result:
        if node.join_type in ("CROSS", "SEMI", "ANTI", "RIGHT", "FULL"):
            return super()._exec_join(node)
        if node.join_type == "LEFT" and node.filter is not None:
            # ON-clause filters on outer joins need the null-extension
            # repair implemented in the local join path
            return super()._exec_join(node)
        if node.single_row:
            # correlated scalar subquery: the local path enforces the
            # one-match-per-row error semantics (EnforceSingleRowNode)
            return super()._exec_join(node)
        right = self._exec(node.right)  # build first: enables dynamic filter
        left = self._exec(self._apply_dynamic_filters(node, right))
        if not (_is_sharded(left.batch) or _is_sharded(right.batch)):
            return self._local_join(node, left, right)
        if not node.criteria:
            return super()._exec_join(node)

        lkeys, rkeys = self._join_keys(left, right, node.criteria)
        ph, pv = J.hash_keys(lkeys)
        bh, bv = J.hash_keys(rkeys)

        threshold = self.session.get("broadcast_join_threshold_rows")
        forced = self.session.get("join_distribution_type")
        build_rows = right.batch.count_rows()
        broadcast = build_rows <= threshold
        if forced == "PARTITIONED":
            broadcast = False
        elif forced == "BROADCAST":
            broadcast = True
        if node.distribution == "partitioned":
            broadcast = False
        elif node.distribution == "replicated":
            broadcast = True

        if broadcast:
            return self._broadcast_join(node, left, right, lkeys, rkeys, ph, pv, bh, bv)
        return self._partitioned_join(node, left, right)

    def _local_join(self, node, left, right):
        return self._join_result(node, left, right)

    def _broadcast_join(self, node, left, right, lkeys, rkeys, ph, pv, bh, bv):
        mesh = self.mesh
        n = self.n_shards
        # replicate build side (arrays + selection)
        build_arrays = []
        build_schema = []
        for s in node.right.output_symbols:
            c = right.column(s)
            build_arrays.append(_as_global(mesh, c.data))
            build_arrays.append(_as_global(mesh, c.valid_mask()))
            build_schema.append((s, c.dictionary))
        build_key_arrays = []
        for kd, kv in rkeys:
            build_key_arrays.append(_as_global(mesh, kd))
            build_key_arrays.append(_as_global(mesh, kv))
        bsel = right.batch.selection_mask()
        all_build, bsel_rep = X.broadcast_all(
            mesh, build_arrays + build_key_arrays + [_as_global(mesh, bh)], _as_global(mesh, bsel)
        )
        nb = len(build_arrays)
        rep_build_cols = all_build[:nb]
        rep_build_keys = all_build[nb:-1]
        rep_bh = all_build[-1]

        probe_sel = left.batch.selection_mask()
        probe_rows = left.batch.count_rows()
        per_shard_cap = bucket_capacity(max(1024, (probe_rows * 3) // max(n, 1)))

        probe_cols = []
        probe_schema = []
        for s in node.left.output_symbols:
            c = left.column(s)
            probe_cols.append(c.data)
            probe_cols.append(c.valid_mask())
            probe_schema.append((s, c.dictionary))
        probe_key_arrays = []
        for kd, kv in lkeys:
            probe_key_arrays.append(kd)
            probe_key_arrays.append(kv)

        join_type = node.join_type
        nlk = len(lkeys)

        while True:
            out = _sharded_probe(
                mesh,
                probe_cols,
                probe_key_arrays,
                ph,
                probe_sel,
                rep_build_cols,
                rep_build_keys,
                rep_bh,
                bsel_rep,
                per_shard_cap,
                join_type,
                nlk,
                profiler=self._profiled_call,
            )
            out_cols, out_sel, overflow = out
            if not bool(np.asarray(overflow).max()):
                break
            per_shard_cap <<= 1
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
        total = out_cols[0].shape[0]
        result = Result(Batch(cols, total, out_sel), layout)
        if node.filter is not None:
            from trino_tpu.compiler import ExprCompiler

            expr = self._bind(node.filter, result.layout)
            mask = ExprCompiler(
                result.batch.columns, params=getattr(self, "_params", None)
            ).predicate_mask(expr)
            result = Result(
                Batch(result.batch.columns, total, mask & out_sel), layout
            )
        return result

    def _partitioned_join(self, node, left, right):
        """Repartition both sides by join-key hash, then shard-local join."""
        mesh = self.mesh
        lkeys, rkeys = self._join_keys(left, right, node.criteria)
        ph, _pv = J.hash_keys(lkeys)
        bh, _bv = J.hash_keys(rkeys)

        def flatten(side_res, side_node, keys, khash):
            arrays = []
            schema = []
            for s in side_node.output_symbols:
                c = side_res.column(s)
                arrays.append(_as_global(mesh, c.data))
                arrays.append(_as_global(mesh, c.valid_mask()))
                schema.append((s, c.dictionary))
            for kd, kv in keys:
                arrays.append(_as_global(mesh, kd))
                arrays.append(_as_global(mesh, kv))
            arrays.append(_as_global(mesh, khash))
            return arrays, schema

        larrs, lschema = flatten(left, node.left, lkeys, ph)
        rarrs, rschema = flatten(right, node.right, rkeys, bh)
        lsel = _as_global(mesh, left.batch.selection_mask())
        rsel = _as_global(mesh, right.batch.selection_mask())

        n = self.n_shards
        hybrid = None
        if node.join_type in ("INNER", "LEFT") and bool(
            self.session.get("skew_handling")
        ):
            hybrid = self._hybrid_repartition(mesh, larrs, lsel, rarrs, rsel)
        if hybrid is not None:
            lout, lsel2, rout, rsel2 = hybrid
        else:
            # size buckets exactly (one cheap counting pass beats overflow
            # retries — each retry re-traces the exchange program)
            lbucket = bucket_capacity(X.needed_bucket(mesh, larrs[-1], lsel), minimum=8)
            rbucket = bucket_capacity(X.needed_bucket(mesh, rarrs[-1], rsel), minimum=8)
            lout, lsel2, lovf = X.hash_repartition(mesh, larrs, larrs[-1], lsel, lbucket)
            rout, rsel2, rovf = X.hash_repartition(mesh, rarrs, rarrs[-1], rsel, rbucket)
            assert not bool(np.asarray(lovf).max()) and not bool(np.asarray(rovf).max())
            st = self.exchange_stats
            st["exchanges"] += 2
            st["padded_shuffle_rows"] += n * n * (lbucket + rbucket)
            st["shuffle_rows"] += int(
                np.asarray(lsel).sum() + np.asarray(rsel).sum()
            )

        # build shard-local Results and delegate to the local join kernel via
        # shard_map: both sides now co-partitioned by key hash
        nlk = len(lkeys)  # wide criteria expand into two lane pairs
        probe_cols = lout[: 2 * len(lschema)]
        probe_keys = lout[2 * len(lschema) : -1]
        ph2 = lout[-1]
        build_cols = rout[: 2 * len(rschema)]
        build_keys = rout[2 * len(rschema) : -1]
        bh2 = rout[-1]
        per_shard_cap = bucket_capacity(
            max(1024, 2 * (left.batch.count_rows() + right.batch.count_rows()) // max(n, 1))
        )
        while True:
            out_cols, out_sel, overflow = _sharded_probe(
                mesh,
                probe_cols,
                probe_keys,
                ph2,
                lsel2,
                build_cols,
                build_keys,
                bh2,
                rsel2,
                per_shard_cap,
                node.join_type,
                nlk,
                build_sharded=True,
                profiler=self._profiled_call,
            )
            if not bool(np.asarray(overflow).max()):
                break
            per_shard_cap <<= 1
        cols: list[Column] = []
        layout: dict[str, int] = {}
        i = 0
        for s, d in lschema:
            cols.append(Column(s.type, out_cols[i], out_cols[i + 1], d))
            layout[s.name] = len(cols) - 1
            i += 2
        for s, d in rschema:
            cols.append(Column(s.type, out_cols[i], out_cols[i + 1], d))
            layout[s.name] = len(cols) - 1
            i += 2
        total = out_cols[0].shape[0]
        result = Result(Batch(cols, total, out_sel), layout)
        if node.filter is not None:
            from trino_tpu.compiler import ExprCompiler

            expr = self._bind(node.filter, result.layout)
            mask = ExprCompiler(
                result.batch.columns, params=getattr(self, "_params", None)
            ).predicate_mask(expr)
            result = Result(Batch(result.batch.columns, total, mask & out_sel), layout)
        return result

    def _hybrid_repartition(self, mesh, larrs, lsel, rarrs, rsel):
        """Skew-aware hybrid exchange for a partitioned join (interpreter
        path, eager): detect heavy hitters over the probe-side key hashes,
        keep hot probe rows on their source shard, replicate just the hot
        build slice, and repartition the cold remainder through exactly
        sized two-tier buckets. Returns None when no key is hot (caller
        falls back to the plain exact-bucket exchange)."""
        from trino_tpu.ops import skew as SK

        k = max(1, int(self.session.get("skew_hot_k")))
        frac = float(self.session.get("skew_hot_threshold_frac"))
        hh, hv, n_hot, _total = SK.hot_key_hashes(mesh, larrs[-1], lsel, k, frac)
        if int(np.asarray(n_hot).max()) == 0:
            return None
        lcold, lhot = X.skew_split_counts(mesh, larrs[-1], lsel, hh, hv)
        rcold, rhot = X.skew_split_counts(mesh, rarrs[-1], rsel, hh, hv)
        lb = bucket_capacity(lcold, minimum=8)
        rb = bucket_capacity(rcold, minimum=8)
        lhot_cap = bucket_capacity(lhot, minimum=8)
        rhot_cap = bucket_capacity(rhot, minimum=8)
        # cold buckets are exact, so the spill tier is vestigial-minimal
        lout, lsel2, lflags, lcnt, _ = X.skewed_repartition(
            mesh, larrs, larrs[-1], lsel, lb, 8,
            hot_mode="local", hot_cap=lhot_cap, hot_set=(hh, hv),
        )
        rout, rsel2, rflags, rcnt, _ = X.skewed_repartition(
            mesh, rarrs, rarrs[-1], rsel, rb, 8,
            hot_mode="replicate", hot_cap=rhot_cap, hot_set=(hh, hv),
        )
        assert not any(
            bool(np.asarray(f).max()) for f in (*lflags, *rflags)
        )
        n = mesh.devices.size
        st = self.exchange_stats
        st["exchanges"] += 2
        st["hot_keys"] += int(np.asarray(n_hot).max())
        st["shuffle_rows"] += int(np.asarray(lcnt[0]).max()) + int(
            np.asarray(rcnt[0]).max()
        )
        st["salted_rows"] += int(np.asarray(lcnt[1]).max()) + int(
            np.asarray(rcnt[1]).max()
        )
        st["padded_shuffle_rows"] += n * (n * lb + 8) + n * (
            n * rb + 8 + rhot_cap
        )
        return lout, lsel2, rout, rsel2


def _is_sharded(batch: Batch) -> bool:
    for c in batch.columns:
        if isinstance(c.data, jax.Array) and len(c.data.sharding.device_set) > 1:
            return True
    return False


def _as_global(mesh: Mesh, arr) -> jax.Array:
    """Ensure an array is a jax Array (shard if it is a host array)."""
    if isinstance(arr, jax.Array) and len(arr.sharding.device_set) > 1:
        return arr
    a = jnp.asarray(arr)
    from trino_tpu.parallel.mesh import row_sharding

    n = mesh.devices.size
    pad = (-a.shape[0]) % n
    if pad:
        # wide DECIMAL columns carry (N, 2) hi/lo lanes: pad rows only
        a = jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], dtype=a.dtype)]
        )
    return jax.device_put(a, row_sharding(mesh))


def _sharded_probe(
    mesh,
    probe_cols,
    probe_keys,
    ph,
    probe_sel,
    build_cols,
    build_keys,
    bh,
    build_sel,
    per_shard_cap,
    join_type,
    nlk,
    build_sharded=False,
    profiler=None,
    strategy="sort",
    table_cap=None,
    lookup=False,
):
    """Per-shard join: build local table from (replicated or co-partitioned)
    build side, probe local rows, expand into fixed capacity.

    ``lookup`` (``sort`` only, INNER or LEFT, ``per_shard_cap`` the shard's
    probe rows; the caller knows the build key unique): no expansion
    (``ops/join.py::lookup_join``). The probe's columns are returned as
    given, output row ``i`` being probe row ``i``; the overflow flag says a
    probe row matched two build rows, which the expansion must answer.

    ``strategy`` picks the join kernel: ``sort`` (ops/join.py bitonic
    build + sort-merge probe), ``dense`` (ops/dense_join.py
    open-addressing table of ``table_cap`` slots), or ``matmul`` (same
    table addressed by identity binning of the single key column).
    Non-sort strategies return a FOURTH element — the table-overflow
    flag whose ``densejoin@…`` capacity site the executor's retry ladder
    doubles (graceful re-hash instead of the spill cliff).

    ``profiler`` (``DistributedExecutor._profiled_call``) optionally wraps
    the shard_map program so its XLA cost/memory analysis is captured."""
    n = mesh.devices.size

    def pad_side(cols, keys, h, sel):
        """Kernels reject 0-capacity arrays; pad an empty relation to n
        unselected rows (one per shard)."""
        if h.shape[0] > 0:
            return cols, keys, h, sel
        cols = [
            jnp.zeros((n,) + c.shape[1:], dtype=c.dtype) for c in cols
        ]
        keys = [
            jnp.zeros((n,) + k.shape[1:], dtype=k.dtype) for k in keys
        ]
        return (
            cols,
            keys,
            jnp.zeros((n,), dtype=h.dtype),
            jnp.zeros((n,), dtype=jnp.bool_),
        )

    probe_cols, probe_keys, ph, probe_sel = pad_side(
        probe_cols, probe_keys, ph, probe_sel
    )
    build_cols, build_keys, bh, build_sel = pad_side(
        build_cols, build_keys, bh, build_sel
    )
    if lookup:
        assert strategy == "sort" and join_type in ("INNER", "LEFT")
        assert per_shard_cap * n == ph.shape[0], (per_shard_cap, ph.shape)
        # output row i is probe row i: the probe's columns never enter
        passed, probe_cols = list(probe_cols), []
    n_probe = len(probe_cols)
    n_build = len(build_cols)
    build_spec = PS(AXIS) if build_sharded else PS()

    in_specs = (
        tuple(PS(AXIS) for _ in probe_cols)
        + tuple(PS(AXIS) for _ in probe_keys)
        + (PS(AXIS), PS(AXIS))
        + tuple(build_spec for _ in build_cols)
        + tuple(build_spec for _ in build_keys)
        + (build_spec, build_spec)
    )

    out_specs = (tuple(PS(AXIS) for _ in range(n_probe + n_build)), PS(AXIS), PS())
    if strategy != "sort":
        out_specs = out_specs + (PS(),)

    @partial(smap, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    def go(*ops):
        i = 0
        p_cols = ops[i : i + n_probe]; i += n_probe
        p_keys = ops[i : i + 2 * nlk]; i += 2 * nlk
        p_hash = ops[i]; i += 1
        p_sel = ops[i]; i += 1
        b_cols = ops[i : i + n_build]; i += n_build
        b_keys = ops[i : i + 2 * nlk]; i += 2 * nlk
        b_hash = ops[i]; i += 1
        b_sel = ops[i]; i += 1

        # key validity: all key columns non-null
        pk_pairs = [(p_keys[2 * k], p_keys[2 * k + 1]) for k in range(nlk)]
        bk_pairs = [(b_keys[2 * k], b_keys[2 * k + 1]) for k in range(nlk)]
        pv = jnp.ones_like(p_sel)
        for _, kv in pk_pairs:
            pv = pv & kv
        bv = jnp.ones_like(b_sel)
        for _, kv in bk_pairs:
            bv = bv & kv
        jt = "left" if join_type == "LEFT" else "inner"
        tovf = None
        if lookup:
            sbk, sbi, bcount = J.build_side(b_hash, bv, b_sel)
            bpos, osel, ovf = J.lookup_join(sbk, sbi, bcount, p_hash, pv, p_sel, jt)
            # a hash match whose keys differ is no match: INNER drops the
            # row, LEFT keeps it as an outer row
            hit = J.verify_equal(pk_pairs, bk_pairs, None, bpos, bpos != J.MISSING)
            if jt == "inner":
                osel = osel & hit
            is_outer = ~hit
        elif strategy == "sort":
            sbk, sbi, bcount = J.build_side(b_hash, bv, b_sel)
            ppos, bpos, osel, total, ovf = J.probe_join(
                sbk, sbi, bcount, p_hash, pv, p_sel, per_shard_cap, jt,
            )
        else:
            from trino_tpu.ops import dense_join as DJ

            if strategy == "matmul":
                # identity binning of the single key column (the caller
                # gates matmul on nlk == 1 and an integer key dtype)
                use_b = bv & b_sel
                kmin = jnp.min(
                    jnp.where(
                        use_b,
                        bk_pairs[0][0].astype(jnp.int64),
                        jnp.iinfo(jnp.int64).max,
                    )
                )
                bbase = DJ.slot_base_binned(bk_pairs[0][0], kmin, table_cap)
                pbase = DJ.slot_base_binned(pk_pairs[0][0], kmin, table_cap)
            else:
                bbase = DJ.slot_base_hash(b_hash, table_cap)
                pbase = DJ.slot_base_hash(p_hash, table_cap)
            table, tovf = DJ.build_table(bbase, bv, b_sel, table_cap)
            ppos, bpos, osel, total, ovf = DJ.probe_table(
                table, b_hash, pbase, p_hash, pv, p_sel, per_shard_cap, jt,
            )
        if not lookup:
            osel = J.verify_equal(pk_pairs, bk_pairs, ppos, bpos, osel)
            is_outer = bpos == J.MISSING
        # the index verify_equal gathered the build's keys through
        safe_bpos = jnp.where(bpos == J.MISSING, 0, bpos)
        outs = []
        for k in range(0, n_probe, 2):
            outs.append(p_cols[k][ppos])
            outs.append(p_cols[k + 1][ppos])
        for k in range(0, n_build, 2):
            outs.append(b_cols[k][safe_bpos])
            outs.append(b_cols[k + 1][safe_bpos] & ~is_outer)
        ovf_any = jax.lax.pmax(ovf.astype(jnp.int32), AXIS)
        if tovf is None:
            return tuple(outs), osel, ovf_any
        tovf_any = jax.lax.pmax(tovf.astype(jnp.int32), AXIS)
        return tuple(outs), osel, ovf_any, tovf_any

    args = (
        list(probe_cols)
        + list(probe_keys)
        + [ph, probe_sel]
        + list(build_cols)
        + list(build_keys)
        + [bh, build_sel]
    )
    if profiler is not None:
        label = "probe_join" + ("_partitioned" if build_sharded else "_broadcast")
        res = profiler(label, go, *args)
    else:
        res = go(*args)
    if strategy == "sort":
        outs, osel, ovf = res
        return (passed if lookup else []) + list(outs), osel, ovf
    outs, osel, ovf, tovf = res
    return list(outs), osel, ovf, tovf
