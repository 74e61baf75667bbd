"""Single-host execution of a logical plan (LocalQueryRunner tier).

Reference: ``core/trino-main/src/main/java/io/trino/testing/LocalQueryRunner.java:631``
— full parse->plan->execute in one process, no RPC. Each plan node is
evaluated to a device :class:`Batch` + symbol layout; expressions are bound
to channels and jit-evaluated. Materialized (operator-at-a-time) in v1 —
the distributed executor fuses per-fragment programs.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.columnar import (
    Batch,
    Column,
    Dictionary,
    bucket_capacity,
    concat_batches,
    pad_batch,
)
from trino_tpu.compiler import ExprCompiler
from trino_tpu.config import Session
from trino_tpu.connectors.api import CatalogManager
from trino_tpu.ir import Call, Constant, InputRef, RowExpr, SpecialForm, Variable, bind_variables
from trino_tpu.obs.trace import get_tracer
from trino_tpu.ops import join as J
from trino_tpu.ops.aggregation import (
    AggSpec,
    domain_slots,
    global_aggregate,
    group_aggregate,
    key_domains_from,
)
from trino_tpu.ops.sort import SortKey, sort_indices
from trino_tpu.planner import plan as P


class ExecutionError(Exception):
    pass


def rank_codes(dictionary, data):
    """Map dictionary codes to lexicographic ranks; safe on empty
    dictionaries (padding rows over empty tables have no real codes)."""
    if dictionary is None or len(dictionary) == 0:
        return jnp.zeros(data.shape, dtype=jnp.int64)
    r = jnp.asarray(dictionary.ranks())
    return r[jnp.maximum(data, 0)].astype(jnp.int64)


def sum_spec_for(fn: P.AggFunction, data) -> AggSpec:
    """Pick the accumulation kernel for a sum/avg: 128-bit limb
    accumulation when the declared result is a wide DECIMAL or the input
    already carries wide (hi, lo) storage (reference:
    DecimalSumAggregation over UnscaledDecimal128 state)."""
    from trino_tpu.ops.decimal128 import is_wide_data

    if fn.kind in ("sum", "avg"):
        if data is not None and is_wide_data(data):
            return AggSpec("sum128w")
        rt = fn.result_type
        if isinstance(rt, T.DecimalType) and rt.wide:
            return AggSpec("sum128")
    return AggSpec(fn.kind if fn.kind != "count_star" else "count_star")


@dataclasses.dataclass
class Result:
    """A materialized intermediate: batch + symbol layout."""

    batch: Batch
    layout: dict[str, int]  # symbol name -> channel

    def column(self, symbol: P.Symbol) -> Column:
        return self.batch.columns[self.layout[symbol.name]]

    def pair(self, symbol: P.Symbol):
        c = self.column(symbol)
        return c.data, c.valid_mask()

    def opt_pair(self, symbol: P.Symbol):
        """(data, valid-or-None): kernels skip null handling for None."""
        c = self.column(symbol)
        return c.data, c.valid


class LocalExecutor:
    def __init__(
        self,
        catalogs: CatalogManager,
        session: Session,
        memory_ctx=None,
    ):
        self.catalogs = catalogs
        self.session = session
        # collected dynamic-filter stats (DynamicFilterService analog)
        self.dynamic_filters: list = []
        # memory accounting (node -> query -> pool; see trino_tpu.memory)
        self.memory_ctx = memory_ctx
        self._reservations: dict[int, int] = {}
        # per-node execution stats for EXPLAIN ANALYZE (OperatorStats chain)
        self.stats_collector = None
        # per-query ingest accounting (split decode, coalesced H2D, table
        # cache; trino_tpu/ingest.py) — served via /v1/query as ingestStats
        self.ingest_stats: dict = {}
        # engine-owned DeviceTableCache (None outside the engine)
        self.table_cache = None
        # id(node) -> (number, node) for the ``op:`` spans: the plan's
        # pre-order where execute() saw the root, visit order for nodes made
        # while executing (a probe side rewritten by a dynamic filter)
        self._node_numbers: dict[int, tuple[int, P.PlanNode]] = {}

    def _node_number(self, node: P.PlanNode) -> int:
        return self._node_numbers.setdefault(
            id(node), (len(self._node_numbers), node)
        )[0]

    def ingest_stats_snapshot(self) -> Optional[dict]:
        return dict(self.ingest_stats) if self.ingest_stats else None

    def _read_splits(self, connector, schema, table, columns, splits):
        """Decode splits through the ingest tier: double-buffered (a
        background thread decodes split k+1 while the caller consumes
        split k), honoring the ``native_decode`` session prop."""
        import contextlib

        from trino_tpu import native
        from trino_tpu.ingest import SplitPrefetcher

        ctx = (
            contextlib.nullcontext()
            if self.session.get("native_decode")
            else native.python_fallback()
        )
        with ctx:
            yield from SplitPrefetcher(
                lambda s: connector.read_split(schema, table, columns, s),
                splits,
                enabled=bool(self.session.get("ingest_prefetch")),
                stats=self.ingest_stats,
            )

    # === entry ==========================================================
    def execute(self, node: P.PlanNode) -> tuple[Batch, list[str]]:
        # ONE span a query, whichever executor: a subclass overrides
        # _execute_plan, never this, so its fall-back to the interpreter
        # stays inside the same span (``executor`` says whose it is)
        with get_tracer().span(
            "execute_plan", attrs={"executor": type(self).__name__}
        ):
            return self._execute_plan(node)

    def _execute_plan(self, node: P.PlanNode) -> tuple[Batch, list[str]]:
        stack = [node]
        while stack:  # pre-order, the order EXPLAIN prints
            n = stack.pop()
            self._node_number(n)
            stack.extend(reversed(n.sources))
        if isinstance(node, P.Output):
            # the root is a plan node too: its span holds the compaction
            with get_tracer().span(
                "op:Output", attrs={"node": self._node_number(node)}
            ):
                res = self._exec(node.source)
                cols = [res.column(s) for s in node.symbols]
                out = Batch(
                    cols, res.batch.num_rows, res.batch.sel
                ).compact()
            return out, node.column_names
        res = self._exec(node)
        return res.batch.compact(), [s.name for s in node.output_symbols]

    @staticmethod
    def _nonempty(res: Result) -> Result:
        """Kernels reject 0-capacity arrays; represent an empty relation as
        one unselected padding row."""
        if res.batch.capacity > 0:
            return res
        from trino_tpu.spill import pad_to_one_unselected

        return Result(pad_to_one_unselected(res.batch), res.layout)

    # === dispatch =======================================================
    def _exec(self, node: P.PlanNode) -> Result:
        method = getattr(self, f"_exec_{type(node).__name__.lower()}", None)
        if method is None:
            raise ExecutionError(f"no executor for {type(node).__name__}")
        # one span per plan node, nesting as the plan does: host wall in the
        # operator, waits on the device included. Never opened while JAX
        # traces a fragment program: _FragmentTracer overrides _exec
        with get_tracer().span(
            "op:" + type(node).__name__,
            attrs={"node": self._node_number(node)},
        ):
            if self.stats_collector is not None:
                import time as _time

                from trino_tpu.memory import batch_nbytes

                t0 = _time.perf_counter()
                res = method(node)
                rows = int(res.batch.count_rows())  # device sync: exact timing
                self.stats_collector.record(
                    node, _time.perf_counter() - t0, rows,
                    batch_nbytes(res.batch),
                )
            else:
                res = method(node)
            if self.memory_ctx is not None:
                from trino_tpu.memory import batch_nbytes

                nbytes = batch_nbytes(res.batch)
                self.memory_ctx.reserve(nbytes, what=type(node).__name__)
                self._reservations[id(node)] = nbytes
                # children's intermediates are dead once this node materialized
                for s in node.sources:
                    self.memory_ctx.free(self._reservations.pop(id(s), 0))
            return res

    # === leaf nodes =====================================================
    def _exec_tablescan(self, node: P.TableScan) -> Result:
        connector = self.catalogs.get(node.catalog)
        splits = connector.get_splits_with_hints(
            node.schema, node.table, 64, node.constraint,
            limit=node.limit, topn=node.topn,
        )
        layout = {s.name: i for i, s in enumerate(node.symbols)}
        if not splits:
            return Result(self._empty_batch(node), layout)
        # tables live on the device: the scan hands on a device-resident
        # batch and keeps it across queries in the engine's table cache
        # (table_cache=false is the host scan: NumPy columns, no cache)
        resident = bool(self.session.get("table_cache"))
        # a scan a pushed limit / topn hint may cut short is not the table
        cache_key = None
        if resident and node.limit is None and node.topn is None:
            cache_key, cached = self._cached_scan(
                node, connector, splits, jax.devices()[0]
            )
            if cached is not None:
                return Result(cached, layout)
        batches = []
        rows_read = 0
        with get_tracer().span(
            "ingest.decode", attrs={"table": node.table}
        ) as span:
            for b in self._read_splits(
                connector, node.schema, node.table, node.column_names, splits
            ):
                batches.append(b)
                rows_read += b.num_rows
                # connector applyLimit hint: stop pulling splits once the
                # pushed row budget is covered (the Limit node still enforces)
                if node.limit is not None and rows_read >= node.limit:
                    break
            span.set("splits", len(batches))
            span.set("rows", rows_read)
        batch = concat_batches(batches) if len(batches) > 1 else batches[0]
        if resident:
            from trino_tpu.ingest import put_batch

            # one upload a column a query even where the cache refuses the
            # table, not one a use by every eager primitive downstream
            batch, nbytes = put_batch(batch, self.ingest_stats)
            self._admit_scan(cache_key, batch, nbytes)
        return Result(batch, layout)

    def _cached_scan(self, node: P.TableScan, connector, splits, placement):
        """(cache key, the resident batch or None) for a scan of the whole
        table on ``placement`` (see ``table_cache_key``); counts the hit or
        miss and marks this scan's ``op:TableScan``. The key is None, and
        nothing is looked up or later admitted, where the session turns
        the cache off or the connector's rows are live state with no
        snapshot token to key on (``supports_result_caching`` false: the
        system tables), which every scan has to read anew."""
        if (
            self.table_cache is None
            or not self.session.get("table_cache")
            or not getattr(connector, "supports_result_caching", True)
        ):
            return None, None
        from trino_tpu.ingest import table_cache_key

        stats = self.ingest_stats
        stats.setdefault("h2d_bytes", 0)
        key = table_cache_key(
            node.catalog,
            node.schema,
            node.table,
            connector.data_version(node.schema, node.table),
            node.column_names,
            splits,
            placement,
        )
        cached = self.table_cache.lookup(key)
        span = get_tracer().current()  # this scan's op:TableScan
        if span is not None:
            span.set("tableCacheHit", cached is not None)
        counter = "table_cache_misses" if cached is None else "table_cache_hits"
        stats[counter] = stats.get(counter, 0) + 1
        return key, cached

    def _admit_scan(self, key, batch: Batch, nbytes: int, peak_hbm_hint: int = 0):
        """Keep a missed scan's device batch for the next query, if
        ``_cached_scan`` gave it a key and the byte budget and the HBM
        headroom allow."""
        if key is not None:
            self.table_cache.admit(
                key,
                batch,
                nbytes,
                max_bytes=int(self.session.get("table_cache_max_bytes")),
                peak_hbm_hint=peak_hbm_hint,
            )

    def _empty_batch(self, node: P.TableScan) -> Batch:
        cols = [
            Column(
                s.type,
                np.zeros(0, dtype=s.type.storage_dtype),
                None,
                Dictionary([]) if T.is_string(s.type) else None,
            )
            for s in node.symbols
        ]
        return Batch(cols, 0)

    def _exec_values(self, node: P.Values) -> Result:
        n = len(node.rows)
        cols = []
        for j, sym in enumerate(node.symbols):
            t = sym.type
            vals = [row[j] for row in node.rows]
            valid = np.asarray([v is not None for v in vals], dtype=np.bool_)
            if T.is_string(t):
                d, codes = Dictionary.from_strings(
                    [v if v is not None else "" for v in vals]
                )
                codes = np.where(valid, codes, -1).astype(np.int32)
                cols.append(Column(t, codes, None if valid.all() else valid, d))
            else:
                data = np.asarray(
                    [v if v is not None else 0 for v in vals], dtype=t.storage_dtype
                )
                cols.append(Column(t, data, None if valid.all() else valid))
        return Result(
            Batch(cols, n), {s.name: i for i, s in enumerate(node.symbols)}
        )

    # === row-preserving nodes ==========================================
    def _exec_filter(self, node: P.Filter) -> Result:
        from trino_tpu.strings import lower_string_calls

        res = self._exec(node.source)
        expr = self._bind(node.predicate, res.layout)
        cols = list(res.batch.columns)
        from trino_tpu.datetimefmt import lower_datetime_format_calls

        expr = lower_datetime_format_calls(expr, cols)
        expr = lower_string_calls(expr, cols)
        mask = ExprCompiler(
            cols, params=getattr(self, "_params", None)
        ).predicate_mask(expr)
        sel = mask if res.batch.sel is None else (mask & res.batch.sel)
        return Result(
            Batch(res.batch.columns, res.batch.num_rows, sel), res.layout
        )

    def _exec_project(self, node: P.Project) -> Result:
        from trino_tpu.strings import lower_string_calls

        res = self._exec(node.source)
        work_cols = list(res.batch.columns)
        cols: list[Column] = []
        from trino_tpu.datetimefmt import lower_datetime_format_calls

        for sym, expr in node.assignments:
            bound = self._bind(expr, res.layout)
            bound = lower_datetime_format_calls(bound, work_cols)
            bound = lower_string_calls(bound, work_cols)
            ec = ExprCompiler(work_cols, params=getattr(self, "_params", None))
            if isinstance(bound, InputRef):
                cols.append(work_cols[bound.channel])
                continue
            if isinstance(sym.type, (T.ArrayType, T.MapType, T.RowType)):
                if isinstance(bound, Constant):
                    n = res.batch.capacity
                    if bound.value is None:
                        cols.append(
                            Column(
                                sym.type,
                                np.full(n, -1, dtype=np.int32),
                                np.zeros(n, dtype=np.bool_),
                                Dictionary([]),
                            )
                        )
                    else:
                        cols.append(
                            Column(
                                sym.type,
                                np.zeros(n, dtype=np.int32),
                                None,
                                Dictionary([bound.value]),
                            )
                        )
                    continue
                raise ExecutionError(
                    "computed ARRAY/MAP/ROW expressions are not supported yet"
                )
            if T.is_string(sym.type):
                if isinstance(bound, Constant):
                    n = res.batch.capacity
                    if bound.value is None:
                        cols.append(
                            Column(
                                sym.type,
                                np.full(n, -1, dtype=np.int32),
                                np.zeros(n, dtype=np.bool_),
                                Dictionary([]),
                            )
                        )
                    else:
                        cols.append(
                            Column(
                                sym.type,
                                np.zeros(n, dtype=np.int32),
                                None,
                                Dictionary([str(bound.value)]),
                            )
                        )
                    continue
                # general string-valued expression (CASE/COALESCE/...):
                # unify all referenced dictionaries + literals, evaluate
                # as codes in the unified dictionary
                new_cols, union = _unify_strings(bound, work_cols)
                ec2 = ExprCompiler(
                    new_cols,
                    string_dictionary=union,
                    params=getattr(self, "_params", None),
                )
                data, valid = ec2.evaluate(bound)
                cols.append(
                    Column(sym.type, data.astype(np.int32), valid, union)
                )
                continue
            data, valid = ec.evaluate(bound)
            data = data.astype(sym.type.storage_dtype)
            cols.append(Column(sym.type, data, valid))
        layout = {s.name: i for i, (s, _) in enumerate(node.assignments)}
        return Result(Batch(cols, res.batch.num_rows, res.batch.sel), layout)

    def _exec_unnest(self, node: P.Unnest) -> Result:
        """Expand array values into rows (UnnestOperator.java:39). A
        row-count-changing host boundary: arrays are pool tuples, so the
        expansion is np.repeat over row indices + typed element columns."""
        res = self._exec(node.source)
        b = res.batch.compact()
        n = b.num_rows
        per_expr: list[tuple[list, np.ndarray]] = []  # (pool tuples per row)
        for expr in node.array_exprs:
            bound = self._bind(expr, res.layout)
            if isinstance(bound, Constant):
                tuples = [
                    bound.value if bound.value is not None else () for _ in range(n)
                ]
            else:
                work = list(b.columns)
                ec = ExprCompiler(work)
                data, valid = ec.evaluate(bound)
                pool = None
                if isinstance(bound, InputRef):
                    pool = work[bound.channel].dictionary
                if pool is None:
                    raise ExecutionError("UNNEST argument has no array pool")
                data_np = np.asarray(data)
                valid_np = np.asarray(valid)
                tuples = [
                    pool.values[int(data_np[i])] if valid_np[i] else ()
                    for i in range(n)
                ]
            per_expr.append(tuples)
        lengths = np.asarray(
            [
                max((len(tuples[i]) for tuples in per_expr), default=0)
                for i in range(n)
            ],
            dtype=np.int64,
        )
        row_idx = np.repeat(np.arange(n), lengths)
        cols: list[Column] = []
        layout: dict[str, int] = {}
        for s in node.source.output_symbols:
            c = b.columns[res.layout[s.name]]
            data, valid = c.to_numpy()
            cols.append(
                Column(
                    c.type,
                    data[row_idx],
                    None if valid[row_idx].all() else valid[row_idx],
                    c.dictionary,
                )
            )
            layout[s.name] = len(cols) - 1
        for sym, tuples in zip(node.element_symbols, per_expr):
            vals: list = []
            for i in range(n):
                t_ = tuples[i]
                ln = int(lengths[i])
                for j in range(ln):
                    v = t_[j] if j < len(t_) else None
                    if v is not None and isinstance(sym.type, T.DecimalType):
                        # pool holds storage ints; from_values wants logical
                        from decimal import Decimal as _D

                        v = _D(int(v)) / sym.type.unscale
                    elif v is not None and isinstance(sym.type, T.DateType):
                        v = int(v)
                    vals.append(v)
            cols.append(Column.from_values(sym.type, vals))
            layout[sym.name] = len(cols) - 1
        if node.ordinality is not None:
            ords = np.concatenate(
                [np.arange(1, ln + 1, dtype=np.int64) for ln in lengths]
            ) if len(lengths) else np.zeros(0, dtype=np.int64)
            cols.append(Column(T.BIGINT, ords))
            layout[node.ordinality.name] = len(cols) - 1
        return Result(Batch(cols, int(lengths.sum())), layout)

    def _exec_limit(self, node: P.Limit) -> Result:
        res = self._exec(node.source)
        b = res.batch.compact()
        lo = min(node.offset, b.num_rows)
        hi = b.num_rows if node.count is None else min(b.num_rows, lo + node.count)
        cols = []
        for c in b.columns:
            data, valid = c.to_numpy()
            cols.append(
                Column(c.type, data[lo:hi], None if valid[lo:hi].all() else valid[lo:hi], c.dictionary)
            )
        return Result(Batch(cols, hi - lo), res.layout)

    # === sorting ========================================================
    def _sorted_result(self, res: Result, order_by: Sequence[P.Ordering], keep: Optional[int]) -> Result:
        b = res.batch
        key_pairs = []
        keys = []
        ranks = []
        for o in order_by:
            c = res.column(o.symbol)
            key_pairs.append((c.data, c.valid_mask()))
            keys.append(o.sort_key())
            ranks.append(c.dictionary.ranks() if c.dictionary is not None else None)
        sel = b.selection_mask()
        perm = sort_indices(key_pairs, keys, sel, ranks)
        n_valid = int(np.asarray(sel).sum())
        take = n_valid if keep is None else min(keep, n_valid)
        perm_np = np.asarray(perm)[:take]
        cols = []
        for c in b.columns:
            data, valid = c.to_numpy()
            cols.append(
                Column(
                    c.type,
                    data[perm_np],
                    None if valid[perm_np].all() else valid[perm_np],
                    c.dictionary,
                )
            )
        return Result(Batch(cols, take), res.layout)

    def _exec_sort(self, node: P.Sort) -> Result:
        res = self._exec(node.source)
        if self._should_spill_sort(res, node.order_by):
            return self._spill_sort(res, node.order_by, None)
        return self._sorted_result(res, node.order_by, None)

    def _exec_topn(self, node: P.TopN) -> Result:
        res = self._exec(node.source)
        if self._should_spill_sort(res, node.order_by):
            return self._spill_sort(res, node.order_by, node.count)
        return self._sorted_result(res, node.order_by, node.count)

    def _should_spill_sort(self, res: Result, order_by) -> bool:
        if not self.session.get("spill_enabled") or not order_by:
            return False
        if res.batch.capacity <= int(self.session.get("spill_threshold_rows")):
            return False
        first = res.column(order_by[0].symbol)
        # wide-decimal (two-lane) leading keys have no scalar range domain
        return getattr(first.data, "ndim", 1) == 1

    def _spill_sort(self, res: Result, order_by, keep: Optional[int]) -> Result:
        """Bounded-HBM external sort: range-partition by a sampled leading
        key, device-sort each partition, concatenate in range order.

        Reference: ``OrderByOperator``/``TopNOperator`` memory revocation
        (``spiller/FileSingleStreamSpiller.java:55``) — the reference
        spills sorted runs and merge-reads them; the TPU-shaped analog is
        a sample sort, which needs no merge pass because ranges are
        disjoint (rows with EQUAL leading keys land in one partition, so
        secondary keys still order correctly within it)."""
        from trino_tpu.spill import slice_rows

        b = res.batch
        o0 = order_by[0]
        c0 = res.column(o0.symbol)
        data, valid = c0.to_numpy()
        if c0.dictionary is not None:
            ranks = np.asarray(c0.dictionary.ranks())
            data = ranks[np.clip(data, 0, max(len(ranks) - 1, 0))]
        sel = np.asarray(b.selection_mask())
        n_part = max(2, int(self.session.get("spill_partitions")))
        live = sel & valid
        vals = data[live]
        if vals.size == 0:
            return self._sorted_result(res, order_by, keep)
        sample = np.sort(vals[:: max(1, vals.size // 65536)])
        bounds = sample[
            np.linspace(0, sample.size - 1, n_part + 1)[1:-1].astype(np.int64)
        ]
        part = np.searchsorted(np.unique(bounds), data, side="right")
        n_ranges = int(part.max(initial=0)) + 1
        null_rows = np.nonzero(sel & ~valid)[0]
        # bucket visit order = final output order: NULL bucket at the end
        # the ordering spec puts it, value ranges ascending or descending
        range_order = list(
            range(n_ranges) if o0.ascending else range(n_ranges - 1, -1, -1)
        )
        buckets: list = (
            ["null", *range_order] if o0.nulls_first else [*range_order, "null"]
        )
        batches: list[Batch] = []
        total = 0
        for bk in buckets:
            rows = (
                null_rows if bk == "null" else np.nonzero(live & (part == bk))[0]
            )
            if rows.size == 0:
                continue
            sub = Result(slice_rows(b, rows), dict(res.layout))
            piece = self._sorted_result(sub, order_by, keep).batch
            batches.append(piece)
            total += piece.num_rows
            if keep is not None and total >= keep:
                break
        out = concat_batches(batches) if len(batches) > 1 else batches[0]
        if keep is not None and out.num_rows > keep:
            out = slice_rows(out, np.arange(keep))
        return Result(out, dict(res.layout))

    # === aggregation ====================================================
    def _exec_aggregate(self, node: P.Aggregate) -> Result:
        if node.step == "partial" and node.acc_symbols is not None:
            return self._aggregate_partial(node, self._exec(node.source))
        if node.step == "final" and node.acc_symbols is not None:
            return self._aggregate_final(node, self._exec(node.source))
        return self._aggregate_result(node, self._exec(node.source))

    def _group_aggregate(self, keys, sel, agg_inputs, specs, key_dicts):
        """``group_aggregate`` up its capacity ladder: a run whose groups
        overflow ``max_groups`` is made again with four times the room. The
        operator's span says how often (``attempts``), where it ended and
        which way the rows were grouped: the batch is whole here, so its
        dictionaries are final and their lengths are the keys' domains."""
        max_groups = 1 << 12
        capacities = [max_groups]
        key_domains = key_domains_from(keys, key_dicts)
        slots = domain_slots(keys, agg_inputs, specs, max_groups, key_domains)
        while True:
            keys_out, results, ng, overflow = group_aggregate(
                keys, sel, agg_inputs, specs, max_groups, key_domains
            )
            if not bool(overflow):
                break
            # the run counted its groups: room for them at once, where that
            # is more than the next rung
            max_groups = bucket_capacity(max(max_groups << 2, int(ng)))
            capacities.append(max_groups)
            if max_groups > (1 << 26):
                raise ExecutionError("group-by cardinality too large")
        span = get_tracer().current()
        if span is not None:
            span.set("groupBy", "domain" if slots else "sort")
            span.add("attempts", len(capacities))
            span.add("aggAttempts", len(capacities))
            span.add("groupBudgetGrowths", len(capacities) - 1)
            span.set("maxGroups", max_groups)
            span.set(
                "capacities", span.attrs.get("capacities", []) + capacities
            )
            if slots:
                span.set("slots", slots)
        return keys_out, results, int(ng)

    def _aggregate_partial(self, node: P.Aggregate, res: Result) -> Result:
        """PARTIAL step: emit accumulator columns (value, count) per agg —
        the wire representation between fragments (reference:
        AccumulatorStateSerializer). String min/max values travel as
        lexicographic ranks with the dictionary attached to the column."""
        res = self._nonempty(res)
        sel = res.batch.selection_mask()
        agg_inputs, specs, string_aggs = self._prepare_partial_inputs(node, res)
        key_dicts = [res.column(k).dictionary for k in node.group_keys]
        if not node.group_keys:
            raw = global_aggregate(sel, agg_inputs, specs)
            cols, layout = self._acc_columns(node, raw, 1, string_aggs)
            return Result(Batch(cols, 1), layout)
        keys = [res.pair(k) for k in node.group_keys]
        (kd, kv), raw, ng = self._group_aggregate(
            keys, sel, agg_inputs, specs, key_dicts
        )
        cols: list[Column] = []
        layout: dict[str, int] = {}
        for i, k in enumerate(node.group_keys):
            valid = np.asarray(kv[i])[:ng]
            cols.append(
                Column(
                    k.type,
                    np.asarray(kd[i])[:ng].astype(k.type.storage_dtype),
                    None if valid.all() else valid,
                    key_dicts[i],
                )
            )
            layout[k.name] = len(cols) - 1
        acc_cols, acc_layout = self._acc_columns(node, raw, ng, string_aggs)
        for name, i in acc_layout.items():
            layout[name] = len(cols) + i
        cols.extend(acc_cols)
        return Result(Batch(cols, ng), layout)

    def _prepare_partial_inputs(self, node: P.Aggregate, res: Result):
        """Like the single-step input prep but without DISTINCT handling
        (the fragmenter never splits DISTINCT aggregates)."""
        agg_inputs, specs, string_aggs = [], [], []
        for _, fn in node.aggregates:
            if fn.kind == "count_star":
                if fn.filter is not None:
                    fc = res.column(P.Symbol(fn.filter.name, T.BOOLEAN))
                    ones = jnp.ones(res.batch.capacity, dtype=jnp.int64)
                    agg_inputs.append((ones, fc.data & fc.valid_mask()))
                    specs.append(AggSpec("count"))
                    string_aggs.append(None)
                    continue
                agg_inputs.append(None)
                specs.append(AggSpec("count_star"))
                string_aggs.append(None)
                continue
            sym = P.Symbol(fn.argument.name, fn.argument.type)
            c = res.column(sym)
            data, valid = c.data, c.valid_mask()
            if c.dictionary is not None and fn.kind in ("min", "max"):
                data = rank_codes(c.dictionary, data)
                string_aggs.append(c.dictionary)
            else:
                string_aggs.append(None)
            if fn.filter is not None:
                fc = res.column(P.Symbol(fn.filter.name, T.BOOLEAN))
                valid = valid & fc.data & fc.valid_mask()
            agg_inputs.append((data, valid))
            specs.append(sum_spec_for(fn, data))
        return agg_inputs, specs, string_aggs

    def _acc_columns(self, node: P.Aggregate, raw, n, string_aggs):
        cols: list[Column] = []
        layout: dict[str, int] = {}
        for (vsym, csym), (_, fn), r, sdict in zip(
            node.acc_symbols, node.aggregates, raw, string_aggs
        ):
            if fn.kind in ("count", "count_star"):
                data = np.asarray(r).reshape(-1)[:n].astype(np.int64)
                cols.append(Column(T.BIGINT, data))
                layout[vsym.name] = len(cols) - 1
                continue
            val, cnt = r
            val_arr = np.asarray(val)
            cnt = np.asarray(cnt).reshape(-1)[:n].astype(np.int64)
            if val_arr.ndim == 2 and val_arr.shape[1] in (3, 5):
                # limb accumulator -> wide (hi, lo) acc column on the wire
                from trino_tpu.ops import decimal128 as D128

                if val_arr.shape[1] == 3:
                    ints = D128.narrow_sums_to_ints(val_arr[:n])
                else:
                    ints = D128.wide_sums_to_ints(val_arr[:n])
                cols.append(Column(vsym.type, D128.wide_from_ints(ints), None))
                layout[vsym.name] = len(cols) - 1
                cols.append(Column(T.BIGINT, cnt))
                layout[csym.name] = len(cols) - 1
                continue
            if val_arr.ndim == 2 and val_arr.shape[1] == 2:
                # wide min/max extrema: already (hi, lo)
                cols.append(Column(vsym.type, val_arr[:n], None))
                layout[vsym.name] = len(cols) - 1
                cols.append(Column(T.BIGINT, cnt))
                layout[csym.name] = len(cols) - 1
                continue
            val = val_arr.reshape(-1)[:n]
            if sdict is not None:
                # string min/max computed over local ranks — convert the
                # winning rank back to a CODE for the wire: ranks are only
                # meaningful against this node's dictionary, codes travel
                # with it (page serde / concat merge remap codes correctly)
                order = np.argsort(sdict.ranks(), kind="stable")
                if len(order):
                    val = order[np.clip(val, 0, len(order) - 1)].astype(np.int32)
                else:
                    val = np.full(val.shape, -1, dtype=np.int32)
                val = np.where(cnt > 0, val, -1).astype(np.int32)
                cols.append(Column(vsym.type, val, cnt > 0, sdict))
            else:
                cols.append(Column(vsym.type, val, None, None))
            layout[vsym.name] = len(cols) - 1
            cols.append(Column(T.BIGINT, cnt))
            layout[csym.name] = len(cols) - 1
        return cols, layout

    def _aggregate_final(self, node: P.Aggregate, res: Result) -> Result:
        """FINAL step: combine accumulator rows shipped from partials."""
        res = self._nonempty(res)
        sel = res.batch.selection_mask()
        combine_inputs: list = []
        combine_specs: list[AggSpec] = []
        dicts = []
        for (vsym, csym), (_, fn) in zip(node.acc_symbols, node.aggregates):
            vcol = res.column(vsym)
            dicts.append(vcol.dictionary)
            if fn.kind in ("count", "count_star"):
                combine_inputs.append((vcol.data, vcol.valid_mask()))
                combine_specs.append(AggSpec("sum"))
            else:
                ccol = res.column(csym)
                nonempty = ccol.data > 0
                vdata = vcol.data
                if vcol.dictionary is not None and fn.kind in ("min", "max"):
                    # codes -> ranks against the (possibly merged) local
                    # dictionary before order-based combining
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
                combine_inputs.append((ccol.data, ccol.valid_mask()))
                combine_specs.append(AggSpec("sum"))

        def fold(raw):
            out = []
            j = 0
            for _, fn in node.aggregates:
                if fn.kind in ("count", "count_star"):
                    v = raw[j]
                    out.append(v[0] if isinstance(v, tuple) else v)
                    j += 1
                else:
                    v, c = raw[j], raw[j + 1]
                    out.append(
                        (
                            v[0] if isinstance(v, tuple) else v,
                            c[0] if isinstance(c, tuple) else c,
                        )
                    )
                    j += 2
            return out

        if not node.group_keys:
            raw = fold(global_aggregate(sel, combine_inputs, combine_specs))
            cols = self._finalize_aggs(node, raw, 1, None, dicts)
            return Result(
                Batch(cols, 1),
                {s.name: i for i, s in enumerate(node.output_symbols)},
            )
        keys = [res.pair(k) for k in node.group_keys]
        key_dicts = [res.column(k).dictionary for k in node.group_keys]
        (kd, kv), raw, ng = self._group_aggregate(
            keys, sel, combine_inputs, combine_specs, key_dicts
        )
        cols = []
        for i, k in enumerate(node.group_keys):
            valid = np.asarray(kv[i])[:ng]
            cols.append(
                Column(
                    k.type,
                    np.asarray(kd[i])[:ng].astype(k.type.storage_dtype),
                    None if valid.all() else valid,
                    key_dicts[i],
                )
            )
        cols.extend(self._finalize_aggs(node, fold(raw), ng, None, dicts))
        return Result(
            Batch(cols, ng), {s.name: i for i, s in enumerate(node.output_symbols)}
        )

    def _aggregate_with_array_agg(self, node: P.Aggregate, res: Result) -> Result:
        """array_agg collects values into pool-coded arrays host-side
        (groups are small relative to rows; the per-row work stayed on
        device in the feeding operators). Other aggregates in the same
        GROUP BY run through the normal kernels and are stitched back."""
        others = [
            (s, fn) for s, fn in node.aggregates if fn.kind != "array_agg"
        ]
        base = P.Aggregate(node.source, node.group_keys, others, node.step)
        out = self._aggregate_result(base, res)
        ng = out.batch.num_rows

        # host view of the input rows
        sel = np.asarray(res.batch.selection_mask())
        key_vals = []
        for k in node.group_keys:
            c = res.column(k)
            d, v = c.to_numpy()
            key_vals.append((d, v))

        def key_of(i):
            return tuple(
                (int(d[i]), bool(v[i])) for d, v in key_vals
            )

        # group membership in output order
        out_keys = {}
        for gi in range(ng):
            parts = []
            for k in node.group_keys:
                c = out.batch.columns[out.layout[k.name]]
                d, v = c.to_numpy()
                parts.append((int(d[gi]), bool(v[gi])))
            out_keys[tuple(parts)] = gi

        from trino_tpu.columnar import Dictionary as _Dict

        cols = list(out.batch.columns)
        layout = dict(out.layout)
        for sym, fn in node.aggregates:
            if fn.kind != "array_agg":
                continue
            c = res.column(P.Symbol(fn.argument.name, fn.argument.type))
            d, v = c.to_numpy()
            fmask = np.ones(len(d), dtype=bool)
            if fn.filter is not None:
                fc = res.column(P.Symbol(fn.filter.name, T.BOOLEAN))
                fd, fv = fc.to_numpy()
                fmask = fd & fv
            groups: dict = {k: [] for k in out_keys}
            dvals = d.tolist()  # python scalars in one pass, not per-row
            for i in np.nonzero(sel & fmask)[0]:
                k = key_of(i)
                if k not in groups:
                    continue
                if not v[i]:
                    groups[k].append(None)  # array_agg keeps NULLs
                elif c.dictionary is not None:
                    groups[k].append(c.dictionary.decode(int(d[i])))
                else:
                    groups[k].append(dvals[i])
            tuples: list = [()] * max(ng, 1)
            valid_out = np.zeros(max(ng, 1), dtype=bool)
            for k, gi in out_keys.items():
                vals = groups.get(k, [])
                tuples[gi] = tuple(vals)
                valid_out[gi] = bool(vals)
            if not node.group_keys:
                # global: exactly one row; empty input -> NULL array
                vals = groups.get((), [])
                tuples = [tuple(vals)]
                valid_out = np.asarray([bool(vals)])
            pool_index: dict = {}
            pool_vals: list = []
            codes = np.empty(len(tuples), dtype=np.int32)
            for gi, t_ in enumerate(tuples):
                code = pool_index.get(t_)
                if code is None:
                    code = len(pool_vals)
                    pool_index[t_] = code
                    pool_vals.append(t_)
                codes[gi] = code
            codes = np.where(valid_out, codes, -1).astype(np.int32)
            pool = _Dict(pool_vals)
            cols.append(
                Column(
                    sym.type, codes,
                    None if valid_out.all() else valid_out, pool,
                )
            )
            layout[sym.name] = len(cols) - 1
        # reorder to the node's declared output order
        ordered = []
        final_layout = {}
        for s in node.output_symbols:
            ordered.append(cols[layout[s.name]])
            final_layout[s.name] = len(ordered) - 1
        return Result(Batch(ordered, out.batch.num_rows), final_layout)

    def _spill_aggregate(self, node: P.Aggregate, res: Result) -> Result:
        """Partitioned (spill-to-host) group-by: rows hash-partitioned by
        group keys; each partition aggregated on device independently
        (disjoint key sets -> plain concat, no re-merge). Reference:
        HashAggregationOperator revocable-state spill."""
        from trino_tpu.spill import partitioned_run

        n_part = int(self.session.get("spill_partitions"))
        keys = [res.pair(k) for k in node.group_keys]
        kh, _ = J.hash_keys(keys)

        def run(subs, p):
            if subs[0].num_rows == 0:
                return None
            sub = Result(subs[0], dict(res.layout))
            out = self._aggregate_result(node, sub, allow_spill=False)
            return out.batch.compact()

        parts = partitioned_run([(res.batch, np.asarray(kh))], n_part, run)
        layout = {s.name: i for i, s in enumerate(node.output_symbols)}
        if not parts:
            cols = [
                Column(
                    s.type,
                    np.zeros(0, dtype=s.type.storage_dtype),
                    None,
                    res.column(s).dictionary
                    if s.name in res.layout and T.is_string(s.type)
                    else (Dictionary([]) if T.is_string(s.type) else None),
                )
                for s in node.output_symbols
            ]
            return Result(Batch(cols, 0), layout)
        merged = concat_batches(parts) if len(parts) > 1 else parts[0]
        return Result(merged, layout)

    def _aggregate_result(
        self, node: P.Aggregate, res: Result, allow_spill: bool = True
    ) -> Result:
        if any(fn.kind == "array_agg" for _, fn in node.aggregates):
            return self._aggregate_with_array_agg(node, res)
        res = self._nonempty(res)
        if (
            allow_spill
            and node.group_keys
            and self.session.get("spill_enabled")
            and int(res.batch.count_rows())
            > int(self.session.get("spill_threshold_rows"))
        ):
            return self._spill_aggregate(node, res)
        sel = res.batch.selection_mask()
        key_pairs_for_distinct = [res.pair(k) for k in node.group_keys]
        agg_inputs = []
        specs = []
        string_aggs: list[Optional[Dictionary]] = []
        for _, fn in node.aggregates:
            if fn.kind == "count_star":
                if fn.filter is not None:
                    # count(*) FILTER (WHERE f) == count over the f mask
                    fsym = P.Symbol(fn.filter.name, T.BOOLEAN)
                    fc = res.column(fsym)
                    ones = jnp.ones(res.batch.capacity, dtype=jnp.int64)
                    pair = (ones, fc.data & fc.valid_mask())
                    string_aggs.append(None)
                    agg_inputs.append(pair)
                    specs.append(AggSpec("count"))
                    continue
                pair = None
                string_aggs.append(None)
            else:
                assert isinstance(fn.argument, Variable)
                sym = P.Symbol(fn.argument.name, fn.argument.type)
                c = res.column(sym)
                data, valid = c.data, c.valid_mask()
                if c.dictionary is not None and fn.kind in ("min", "max"):
                    # strings: min/max over lexicographic ranks, map back after
                    data = rank_codes(c.dictionary, data)
                    string_aggs.append(c.dictionary)
                else:
                    string_aggs.append(None)
                if fn.filter is not None:
                    fsym = P.Symbol(fn.filter.name, T.BOOLEAN)
                    fc = res.column(fsym)
                    valid = valid & fc.data & fc.valid_mask()
                if fn.distinct and fn.kind in ("count", "sum", "avg"):
                    # DISTINCT: keep only the first occurrence of each
                    # (group keys, value) combination
                    from trino_tpu.ops.aggregation import distinct_first_mask

                    first = distinct_first_mask(
                        key_pairs_for_distinct, (data, valid), sel & valid
                    )
                    valid = valid & first
                pair = (data, valid)
            agg_inputs.append(pair)
            specs.append(sum_spec_for(fn, pair[0] if pair else None))

        if not node.group_keys:
            results = global_aggregate(sel, agg_inputs, specs)
            cols = self._finalize_aggs(node, results, 1, None, string_aggs)
            return Result(
                Batch(cols, 1),
                {s.name: i for i, s in enumerate(node.output_symbols)},
            )

        keys = [res.pair(k) for k in node.group_keys]
        key_dicts = [res.column(k).dictionary for k in node.group_keys]
        (kd, kv), results, ng = self._group_aggregate(
            keys, sel, agg_inputs, specs, key_dicts
        )
        cols = []
        for i, k in enumerate(node.group_keys):
            valid = np.asarray(kv[i])[:ng]
            cols.append(
                Column(
                    k.type,
                    np.asarray(kd[i])[:ng].astype(k.type.storage_dtype),
                    None if valid.all() else valid,
                    key_dicts[i],
                )
            )
        cols.extend(self._finalize_aggs(node, results, ng, None, string_aggs))
        return Result(
            Batch(cols, ng), {s.name: i for i, s in enumerate(node.output_symbols)}
        )

    def _finalize_aggs(self, node, results, n, _unused, string_aggs) -> list[Column]:
        cols = []
        for (sym, fn), raw, sdict in zip(node.aggregates, results, string_aggs):
            t = fn.result_type
            if fn.kind in ("count", "count_star"):
                data = np.asarray(raw).reshape(-1)[:n].astype(np.int64)
                cols.append(Column(t, data))
                continue
            ssum, cnt = raw
            cnt_np = np.asarray(cnt).reshape(-1)[:n]
            valid = cnt_np > 0
            ssum_arr = np.asarray(ssum)
            if ssum_arr.ndim == 2 and ssum_arr.shape[1] in (3, 5):
                # 128-bit limb accumulation: exact host reconstruction
                from trino_tpu.ops import decimal128 as D128

                if ssum_arr.shape[1] == 3:
                    ints = D128.narrow_sums_to_ints(ssum_arr[:n])
                else:
                    ints = D128.wide_sums_to_ints(ssum_arr[:n])
                if fn.kind == "avg":
                    vals = []
                    for s_i, c_i in zip(ints, cnt_np):
                        c_i = max(int(c_i), 1)
                        q, r = divmod(abs(s_i), c_i)
                        q = q + (1 if 2 * r >= c_i else 0)
                        vals.append(q if s_i >= 0 else -q)
                    ints = vals
                wide_t = isinstance(t, T.DecimalType) and t.wide
                if wide_t:
                    data = D128.wide_from_ints(ints)
                else:
                    data = np.asarray(ints, dtype=np.int64)
                cols.append(Column(t, data, None if valid.all() else valid))
                continue
            if fn.kind == "sum":
                data = np.asarray(ssum).reshape(-1)[:n].astype(t.storage_dtype)
                cols.append(Column(t, data, None if valid.all() else valid))
            elif fn.kind == "avg":
                s_np = np.asarray(ssum).reshape(-1)[:n]
                safe = np.maximum(cnt_np, 1)
                if isinstance(t, T.DecimalType):
                    # round half up at result scale
                    data = np.where(
                        s_np >= 0,
                        (s_np + safe // 2) // safe,
                        -((-s_np + safe // 2) // safe),
                    ).astype(np.int64)
                else:
                    data = (s_np / safe).astype(t.storage_dtype)
                cols.append(Column(t, data, None if valid.all() else valid))
            else:  # min / max
                ssum_mm = np.asarray(ssum)
                if ssum_mm.ndim == 2 and ssum_mm.shape[1] == 2:
                    # wide (hi, lo) extrema pass through as wide storage
                    cols.append(
                        Column(t, ssum_mm[:n], None if valid.all() else valid)
                    )
                    continue
                data = ssum_mm.reshape(-1)[:n]
                if sdict is not None:
                    # map ranks back to codes
                    order = np.argsort(sdict.ranks(), kind="stable")
                    if len(order):
                        data = order[np.clip(data, 0, len(order) - 1)].astype(np.int32)
                    else:
                        data = np.full(data.shape, -1, dtype=np.int32)
                    cols.append(
                        Column(t, data, None if valid.all() else valid, sdict)
                    )
                else:
                    cols.append(
                        Column(
                            t,
                            data.astype(t.storage_dtype),
                            None if valid.all() else valid,
                        )
                    )
        return cols

    # === window functions ==============================================
    def _exec_window(self, node: P.Window) -> Result:
        res = self._exec(node.source)
        if (
            self.session.get("spill_enabled")
            and node.partition_by
            and res.batch.capacity
            > int(self.session.get("spill_threshold_rows"))
        ):
            return self._spill_window(node, res)
        return self._window_result(node, res)

    def _spill_window(self, node: P.Window, res: Result) -> Result:
        """Partitioned (spill-to-host) windows: rows hash-partitioned by
        the PARTITION BY keys — window frames never cross partition-key
        boundaries, so per-spill-partition computation is exact; results
        scatter back to the original row positions. Reference:
        WindowOperator memory revocation (the 4th revocable operator)."""
        from trino_tpu.spill import partition_assignment, slice_rows

        b = res.batch
        n_part = int(self.session.get("spill_partitions"))
        keys = [res.pair(s) for s in node.partition_by]
        kh, _ = J.hash_keys(keys)
        sel = np.asarray(b.selection_mask())
        assign = partition_assignment(np.asarray(kh), sel, n_part)
        n_fns = len(node.functions)
        out_data = [None] * n_fns
        out_valid = [np.zeros(b.capacity, dtype=np.bool_) for _ in range(n_fns)]
        out_cols_proto: list[Optional[Column]] = [None] * n_fns
        for p in range(n_part):
            rows = np.nonzero(assign == p)[0]
            if rows.size == 0:
                continue
            sub = Result(slice_rows(b, rows), dict(res.layout))
            sub_out = self._window_result(node, sub)
            base_width = len(b.columns)
            for j in range(n_fns):
                col = sub_out.batch.columns[base_width + j]
                data, valid = col.to_numpy()
                if data.ndim != 1:
                    # 2-D (wide DECIMAL) outputs can't scatter into the
                    # 1-D merge buffer: recompute without spilling
                    return self._window_result(node, res)
                if out_data[j] is None:
                    out_data[j] = np.zeros(b.capacity, dtype=data.dtype)
                    out_cols_proto[j] = col
                elif (
                    col.dictionary is not out_cols_proto[j].dictionary
                    or data.dtype != out_data[j].dtype
                ):
                    # a partition-local dictionary (or dtype drift) would
                    # decode wrong strings through the shared buffer:
                    # fall back to the unspilled path
                    return self._window_result(node, res)
                out_data[j][rows] = data
                out_valid[j][rows] = valid
        cols = list(b.columns)
        layout = dict(res.layout)
        for j, (sym, _wf) in enumerate(node.functions):
            proto = out_cols_proto[j]
            if proto is None:  # no selected rows at all
                data = np.zeros(b.capacity, dtype=sym.type.storage_dtype)
                cols.append(Column(sym.type, data, out_valid[j]))
            else:
                cols.append(
                    Column(sym.type, out_data[j], out_valid[j], proto.dictionary)
                )
            layout[sym.name] = len(cols) - 1
        return Result(Batch(cols, b.num_rows, b.sel), layout)

    def _window_result(self, node: P.Window, res: Result) -> Result:
        from trino_tpu.ops.window import WindowFn, WindowSpecKernel, compute_windows

        b = res.batch
        sel = b.selection_mask()

        part_pairs, part_ranks = [], []
        for s in node.partition_by:
            c = res.column(s)
            part_pairs.append((c.data, c.valid_mask()))
            part_ranks.append(c.dictionary.ranks() if c.dictionary else None)
        order_pairs, order_specs, order_ranks = [], [], []
        for o in node.order_by:
            c = res.column(o.symbol)
            order_pairs.append((c.data, c.valid_mask()))
            order_specs.append(o.sort_key())
            order_ranks.append(c.dictionary.ranks() if c.dictionary else None)

        # frame selection (SQL defaults; ranking fns ignore it)
        preceding = 0
        if not node.order_by:
            kframe = "partition"
        elif node.frame is None:
            kframe = "running_range"
        else:
            ftype, fstart, fend = node.frame
            if fend == "UNBOUNDED FOLLOWING":
                kframe = "partition"
            elif ftype == "ROWS" and fstart.endswith(" PRECEDING") and fstart.split()[0].isdigit():
                kframe = "rows_preceding"
                preceding = int(fstart.split()[0])
            elif ftype == "ROWS":
                kframe = "running_rows"
            else:
                kframe = "running_range"

        fns, args, defaults = [], [], []
        out_dicts: list[Optional[Dictionary]] = []
        minmax_dicts: list[Optional[Dictionary]] = []
        for _, wf in node.functions:
            fns.append(WindowFn(wf.kind, wf.offset, wf.default is not None))
            out_dict = None
            mm_dict = None
            if wf.argument is None:
                args.append(None)
                defaults.append(None)
            else:
                sym = P.Symbol(wf.argument.name, wf.argument.type)
                c = res.column(sym)
                data, valid = c.data, c.valid_mask()
                if getattr(data, "ndim", 1) == 2:
                    # window kernels run in int64 lanes; narrow at runtime
                    # (errors if wide values genuinely exceed 18 digits)
                    from trino_tpu.compiler import _narrow_checked

                    data = _narrow_checked(data, "window over DECIMAL(38)")
                if c.dictionary is not None and wf.kind in ("min", "max"):
                    data = rank_codes(c.dictionary, data)
                    mm_dict = c.dictionary
                elif c.dictionary is not None:
                    out_dict = c.dictionary
                args.append((data, valid))
                d = None
                if wf.default is not None:
                    n = b.capacity
                    if isinstance(wf.default, Constant):
                        if wf.default.value is None:
                            d = (
                                jnp.zeros(n, dtype=data.dtype),
                                jnp.zeros(n, dtype=jnp.bool_),
                            )
                        elif out_dict is not None:
                            code = out_dict.encode(str(wf.default.value))
                            if code < 0:
                                out_dict = Dictionary(
                                    out_dict.values + [str(wf.default.value)]
                                )
                                code = len(out_dict.values) - 1
                            d = (
                                jnp.full(n, code, dtype=data.dtype),
                                jnp.ones(n, dtype=jnp.bool_),
                            )
                        else:
                            d = (
                                jnp.full(n, wf.default.value, dtype=data.dtype),
                                jnp.ones(n, dtype=jnp.bool_),
                            )
                    else:
                        dsym = P.Symbol(wf.default.name, wf.default.type)
                        dc = res.column(dsym)
                        d = (dc.data, dc.valid_mask())
                defaults.append(d)
            out_dicts.append(out_dict)
            minmax_dicts.append(mm_dict)

        results = compute_windows(
            part_pairs, part_ranks, order_pairs, order_specs, order_ranks,
            sel, fns, args, defaults, WindowSpecKernel(kframe, preceding),
        )

        cols = list(b.columns)
        layout = dict(res.layout)
        for (sym, wf), (data, valid), odict, mmdict in zip(
            node.functions, results, out_dicts, minmax_dicts
        ):
            valid_np = np.asarray(valid)
            if mmdict is not None:
                # min/max over strings: ranks back to codes
                order = np.argsort(mmdict.ranks(), kind="stable")
                data = order[np.clip(np.asarray(data), 0, len(order) - 1)].astype(
                    np.int32
                )
                col = Column(sym.type, data, valid_np, mmdict)
            elif odict is not None:
                col = Column(
                    sym.type, np.asarray(data).astype(np.int32), valid_np, odict
                )
            else:
                col = Column(
                    sym.type,
                    np.asarray(data).astype(sym.type.storage_dtype),
                    None if valid_np.all() else valid_np,
                )
            cols.append(col)
            layout[sym.name] = len(cols) - 1
        return Result(Batch(cols, b.num_rows, b.sel), layout)

    def _exec_distinct(self, node: P.Distinct) -> Result:
        res = self._exec(node.source)
        syms = node.output_symbols
        keys = [res.pair(s) for s in syms]
        dicts = [res.column(s).dictionary for s in syms]
        sel = res.batch.selection_mask()
        max_groups = max(1 << 12, bucket_capacity(res.batch.capacity))
        (kd, kv), _, ng, overflow = group_aggregate(keys, sel, [], [], max_groups)
        if bool(overflow):
            raise ExecutionError("distinct cardinality exceeded capacity")
        ng = int(ng)
        cols = []
        for i, s in enumerate(syms):
            valid = np.asarray(kv[i])[:ng]
            cols.append(
                Column(
                    s.type,
                    np.asarray(kd[i])[:ng].astype(s.type.storage_dtype),
                    None if valid.all() else valid,
                    dicts[i],
                )
            )
        return Result(Batch(cols, ng), {s.name: i for i, s in enumerate(syms)})

    # === joins ==========================================================
    def _exec_join(self, node: P.Join) -> Result:
        span = get_tracer().current()
        if span is not None:  # a RIGHT join re-enters flipped: keep the first
            span.attrs.setdefault("joinKind", node.join_type)
        if node.join_type == "CROSS":
            return self._exec_cross_join(node)
        if node.join_type in ("SEMI", "ANTI"):
            return self._exec_semi_join(node)
        if node.join_type == "RIGHT":
            flipped = P.Join(
                "LEFT",
                node.right,
                node.left,
                [(b, a) for a, b in node.criteria],
                node.filter,
            )
            res = self._exec_join(flipped)
            return res  # layout covers both sides; order fixed by Output
        if node.join_type not in ("INNER", "LEFT", "FULL"):
            raise ExecutionError(f"join type {node.join_type} not supported yet")
        if node.join_type == "FULL" and node.filter is not None:
            raise ExecutionError("FULL OUTER JOIN with a non-equi ON filter")
        right = self._exec(node.right)  # build first: enables dynamic filter
        left_plan = self._apply_dynamic_filters(node, right)
        left = self._exec(left_plan)  # probe
        if left_plan is not node.left and id(left_plan) in self._reservations:
            # rekey the probe reservation so the parent free (which walks
            # node.sources) finds it
            self._reservations[id(node.left)] = self._reservations.pop(id(left_plan))
        if (
            node.criteria
            and node.join_type != "FULL"  # spill drops empty-probe partitions
            and self.session.get("spill_enabled")
            and int(left.batch.count_rows()) + int(right.batch.count_rows())
            > int(self.session.get("spill_threshold_rows"))
        ):
            if span is not None:
                span.set("spilled", True)
            return self._spill_join(node, left, right)
        return self._join_result(node, left, right)

    def _spill_join(self, node: P.Join, left: Result, right: Result) -> Result:
        """Partitioned (spill-to-host) join: hash-partition both sides so
        HBM holds one partition's working set at a time (reference:
        HashBuilderOperator spill states + GenericPartitioningSpiller)."""
        from trino_tpu.spill import partitioned_run

        n_part = int(self.session.get("spill_partitions"))
        lkeys, rkeys = self._join_keys(left, right, node.criteria)
        ph, _ = J.hash_keys(lkeys)
        bh, _ = J.hash_keys(rkeys)

        def run(subs, p):
            from trino_tpu.spill import pad_to_one_unselected

            if subs[0].num_rows == 0:
                return None  # no probe rows: inner AND left produce nothing
            rb = subs[1] if subs[1].num_rows > 0 else pad_to_one_unselected(subs[1])
            sub_left = Result(subs[0], dict(left.layout))
            sub_right = Result(rb, dict(right.layout))
            out = self._join_result(node, sub_left, sub_right)
            return out.batch.compact()

        parts = partitioned_run(
            [(left.batch, np.asarray(ph)), (right.batch, np.asarray(bh))],
            n_part,
            run,
        )
        layout: dict[str, int] = {}
        for s in node.left.output_symbols:
            layout[s.name] = len(layout)
        for s in node.right.output_symbols:
            layout[s.name] = len(layout)
        if not parts:
            cols = []
            srcs = [(node.left, left), (node.right, right)]
            for src_node, src_res in srcs:
                for s in src_node.output_symbols:
                    c = src_res.column(s)
                    data, valid = c.to_numpy()
                    cols.append(Column(c.type, data[:0], valid[:0], c.dictionary))
            return Result(Batch(cols, 0), layout)
        merged = concat_batches(parts) if len(parts) > 1 else parts[0]
        return Result(merged, layout)

    def _apply_dynamic_filters(self, node: P.Join, build: Result) -> P.PlanNode:
        """Collect build-side key domains and push them into the probe plan
        (reference: DynamicFilterSourceOperator -> DynamicFilterService ->
        probe scans; here synchronous since the build is materialized)."""
        from trino_tpu.dynfilter import collect_and_push

        left_plan = node.left
        if (
            node.join_type != "INNER"
            or not node.criteria
            or not self.session.get("enable_dynamic_filtering")
        ):
            return left_plan
        build_rows = int(build.batch.count_rows())
        if build_rows > int(self.session.get("dynamic_filtering_max_build_rows")):
            return left_plan
        sel = np.asarray(build.batch.selection_mask())
        for lsym, rsym in node.criteria:
            col = build.column(rsym)
            data = np.asarray(col.data)
            valid = np.asarray(col.valid_mask()) & sel
            left_plan = collect_and_push(
                left_plan, lsym, rsym, data, valid, build_rows,
                self.dynamic_filters,
            )
        return left_plan

    def _join_result(self, node: P.Join, left: Result, right: Result) -> Result:
        left = self._nonempty(left)
        right = self._nonempty(right)
        lkeys, rkeys = self._join_keys(left, right, node.criteria)
        bh, bv = J.hash_keys(rkeys)
        ph, pv = J.hash_keys(lkeys)
        sbk, sbi, bcount = J.build_side(bh, bv, right.batch.selection_mask())
        probe_sel = left.batch.selection_mask()
        est = max(1024, left.batch.count_rows() * 2, right.batch.count_rows())
        ppos, bpos, osel, out_capacity = self._probe_join(
            sbk, sbi, bcount, ph, pv, probe_sel, bucket_capacity(est),
            "left" if node.join_type in ("LEFT", "FULL") else "inner",
        )
        osel = J.verify_equal(lkeys, rkeys, ppos, bpos, osel)
        if node.join_type == "LEFT":
            # verify may drop hash-collision rows; outer padding rows keep
            pass
        ppos_np = np.asarray(ppos)
        bpos_np = np.asarray(bpos)
        osel_np = np.asarray(osel)
        is_outer = bpos_np == J.MISSING
        if node.single_row:
            # scalar subquery: each outer row may match at most one row
            # (reference: EnforceSingleRowNode)
            matched_probe = ppos_np[osel_np & ~is_outer]
            if matched_probe.size and np.bincount(matched_probe).max() > 1:
                raise ExecutionError(
                    "Scalar sub-query has returned multiple rows"
                )
        cols: list[Column] = []
        layout: dict[str, int] = {}
        for s in node.left.output_symbols:
            c = left.column(s)
            data, valid = c.to_numpy()
            cols.append(
                Column(c.type, data[ppos_np], valid[ppos_np], c.dictionary)
            )
            layout[s.name] = len(cols) - 1
        safe_bpos = np.where(is_outer, 0, bpos_np)
        for s in node.right.output_symbols:
            c = right.column(s)
            data, valid = c.to_numpy()
            v = valid[safe_bpos] & ~is_outer
            cols.append(Column(c.type, data[safe_bpos], v, c.dictionary))
            layout[s.name] = len(cols) - 1
        out = Result(
            Batch(cols, out_capacity, osel_np), layout
        )
        if node.join_type == "FULL":
            # append null-extended unmatched build rows (the reference's
            # LookupJoinOperator FULL mode replays unvisited positions,
            # LookupJoinOperator.java:71)
            build_n = right.batch.capacity
            matched = np.zeros(build_n, dtype=bool)
            matched[bpos_np[osel_np & ~is_outer]] = True
            build_sel = np.asarray(right.batch.selection_mask())
            unmatched = np.nonzero(build_sel & ~matched)[0]
            if unmatched.size:
                n_left = len(node.left.output_symbols)
                cols2 = []
                for j, c in enumerate(out.batch.columns):
                    data, valid = c.to_numpy()
                    if j < n_left:  # probe columns: NULL
                        add_shape = (unmatched.size,) + data.shape[1:]
                        add = np.zeros(add_shape, dtype=data.dtype)
                        addv = np.zeros(unmatched.size, dtype=bool)
                    else:  # build columns: gather the unmatched rows
                        rc = right.column(node.right.output_symbols[j - n_left])
                        rd, rv = rc.to_numpy()
                        add, addv = rd[unmatched], rv[unmatched]
                    cols2.append(
                        Column(
                            c.type,
                            np.concatenate([data, add]),
                            np.concatenate([valid, addv]),
                            c.dictionary,
                        )
                    )
                keep = np.concatenate(
                    [osel_np, np.ones(unmatched.size, dtype=bool)]
                )
                return Result(
                    Batch(cols2, out.batch.num_rows + unmatched.size, keep),
                    out.layout,
                )
            return out
        if node.filter is not None:
            from trino_tpu.strings import lower_string_calls

            expr = self._bind(node.filter, out.layout)
            fcols = list(out.batch.columns)
            expr = lower_string_calls(expr, fcols)
            mask = ExprCompiler(
                fcols, params=getattr(self, "_params", None)
            ).predicate_mask(expr)
            mask_np = np.asarray(mask)
            if node.join_type == "LEFT":
                # ON-clause filter applies to MATCHES, not probe rows: a
                # probe row whose matches all fail must still appear once,
                # null-extended (the kernel emitted outer padding only for
                # rows with zero raw matches)
                sel_np = mask_np & osel_np
                keep = sel_np | (osel_np & is_outer)
                probe_n = left.batch.capacity
                raw_match = np.zeros(probe_n, dtype=bool)
                raw_match[ppos_np[osel_np & ~is_outer]] = True
                surviving = np.zeros(probe_n, dtype=bool)
                surviving[ppos_np[sel_np & ~is_outer]] = True
                need_outer = np.nonzero(raw_match & ~surviving)[0]
                if need_outer.size:
                    n_left = len(node.left.output_symbols)
                    cols2 = []
                    for j, c in enumerate(out.batch.columns):
                        data, valid = c.to_numpy()
                        if j < n_left:  # probe columns: gather the rows
                            lc = left.column(node.left.output_symbols[j])
                            ld, lv = lc.to_numpy()
                            add, addv = ld[need_outer], lv[need_outer]
                        else:  # build columns: null-extended
                            add = np.zeros(need_outer.size, dtype=data.dtype)
                            addv = np.zeros(need_outer.size, dtype=bool)
                        cols2.append(
                            Column(
                                c.type,
                                np.concatenate([data, add]),
                                np.concatenate([valid, addv]),
                                c.dictionary,
                            )
                        )
                    keep = np.concatenate(
                        [keep, np.ones(need_outer.size, dtype=bool)]
                    )
                    return Result(
                        Batch(cols2, out.batch.num_rows + need_outer.size, keep),
                        out.layout,
                    )
                return Result(
                    Batch(out.batch.columns, out.batch.num_rows, keep), out.layout
                )
            out = Result(
                Batch(out.batch.columns, out.batch.num_rows, mask_np & osel_np),
                out.layout,
            )
        return out

    def _probe_join(
        self, sbk, sbi, bcount, ph, pv, probe_sel, out_capacity: int, kind: str
    ):
        """``probe_join`` into ``out_capacity`` rows; where the matches
        overflow it, once more into the capacity they need. The operator's
        span says how often (``attempts``) and at which capacities."""
        capacities = [out_capacity]
        while True:
            ppos, bpos, osel, total, ovf = J.probe_join(
                sbk, sbi, bcount, ph, pv, probe_sel, out_capacity, kind
            )
            if not bool(ovf):
                break
            out_capacity = bucket_capacity(int(total))
            capacities.append(out_capacity)
        span = get_tracer().current()
        if span is not None:
            span.set("strategy", "sort-merge-probe")
            span.add("attempts", len(capacities))
            span.set(
                "capacities", span.attrs.get("capacities", []) + capacities
            )
        return ppos, bpos, osel, out_capacity

    def _join_keys(self, left: Result, right: Result, criteria):
        lkeys, rkeys = [], []
        for ls, rs in criteria:
            lc = left.column(ls)
            rc = right.column(rs)
            if getattr(lc.data, "ndim", 1) == 2 or getattr(rc.data, "ndim", 1) == 2:
                # wide DECIMAL join keys: one (hi) + one (lo) int64 key
                # pair per criterion — hashing and equality verification
                # treat the lanes as two ordinary keys
                if isinstance(ls.type, (T.DoubleType, T.RealType)) or isinstance(
                    rs.type, (T.DoubleType, T.RealType)
                ):
                    raise ExecutionError(
                        "join between DECIMAL(38) and floating point"
                    )
                from trino_tpu.ops import decimal128 as D128

                ls_s = ls.type.scale if isinstance(ls.type, T.DecimalType) else 0
                rs_s = rs.type.scale if isinstance(rs.type, T.DecimalType) else 0
                s = max(ls_s, rs_s)

                def lanes(col, scale):
                    if getattr(col.data, "ndim", 1) == 2:
                        hi, lo = col.data[:, 0], col.data[:, 1]
                    else:
                        hi, lo = D128.widen_i64(col.data.astype(jnp.int64))
                    if s > scale:
                        hi, lo = D128.rescale_up_wide(hi, lo, s - scale)
                    return hi, lo

                lhi, llo = lanes(lc, ls_s)
                rhi, rlo = lanes(rc, rs_s)
                lv = lc.valid_mask()
                rv = rc.valid_mask()
                lkeys.append((lhi, lv))
                lkeys.append((llo, lv))
                rkeys.append((rhi, rv))
                rkeys.append((rlo, rv))
                continue
            ld, lv = lc.data, lc.valid_mask()
            rd, rv = rc.data, rc.valid_mask()
            if lc.dictionary is not None or rc.dictionary is not None:
                if lc.dictionary is not rc.dictionary:
                    merged, remap = lc.dictionary.merged(rc.dictionary)
                    remap_j = jnp.asarray(remap)
                    rd = jnp.where(rd >= 0, remap_j[jnp.maximum(rd, 0)], -1)
            l_float = isinstance(ls.type, (T.DoubleType, T.RealType))
            r_float = isinstance(rs.type, (T.DoubleType, T.RealType))
            ls_scale = ls.type.scale if isinstance(ls.type, T.DecimalType) else 0
            rs_scale = rs.type.scale if isinstance(rs.type, T.DecimalType) else 0
            if l_float or r_float:
                # decimal/integer vs double: compare in double space, keyed
                # on the float64 bit pattern (exact per-value equality)
                if not l_float:
                    ld = ld.astype(jnp.float64) / (10**ls_scale)
                if not r_float:
                    rd = rd.astype(jnp.float64) / (10**rs_scale)
                ld = _f64_key(ld)
                rd = _f64_key(rd)
            elif ls_scale != rs_scale:
                # align scales: decimal-vs-decimal and decimal-vs-integer
                # joins must compare equal values equal
                s = max(ls_scale, rs_scale)
                ld = ld.astype(jnp.int64) * (10 ** (s - ls_scale))
                rd = rd.astype(jnp.int64) * (10 ** (s - rs_scale))
            lkeys.append((ld.astype(jnp.int64), lv))
            rkeys.append((rd.astype(jnp.int64), rv))
        return lkeys, rkeys

    def _exec_semi_join(self, node: P.Join) -> Result:
        left = self._nonempty(self._exec(node.left))
        right = self._nonempty(self._exec(node.right))
        if not node.criteria:
            if node.filter is not None:
                raise ExecutionError(
                    "non-equi correlated EXISTS without equality criteria "
                    "is not supported yet"
                )
            # uncorrelated EXISTS: right side non-empty?
            nonempty = right.batch.count_rows() > 0
            mark = nonempty if node.join_type == "SEMI" else not nonempty
            mark_val = np.full(left.batch.capacity, mark, dtype=np.bool_)
            cols = list(left.batch.columns) + [Column(T.BOOLEAN, mark_val)]
            layout = dict(left.layout)
            layout[node.mark_symbol.name] = len(cols) - 1
            return Result(Batch(cols, left.batch.num_rows, left.batch.sel), layout)
        lkeys, rkeys = self._join_keys(left, right, node.criteria)
        bh, bv = J.hash_keys(rkeys)
        ph, pv = J.hash_keys(lkeys)
        sbk, sbi, bcount = J.build_side(bh, bv, right.batch.selection_mask())
        # exact: expand matches, verify, then scatter-mark probe rows
        probe_sel = left.batch.selection_mask()
        ppos, bpos, osel, _ = self._probe_join(
            sbk, sbi, bcount, ph, pv, probe_sel,
            bucket_capacity(max(1024, left.batch.count_rows() * 2)),
            "inner",
        )
        osel = J.verify_equal(lkeys, rkeys, ppos, bpos, osel)
        if node.filter is not None:
            # residual correlated condition: evaluate over (probe row,
            # build row) pairs and drop non-qualifying matches
            safe_b = jnp.where(bpos == J.MISSING, 0, bpos)
            fcols: list[Column] = []
            flayout: dict[str, int] = {}
            for s in node.left.output_symbols:
                c = left.column(s)
                data, valid = c.to_numpy()
                p_np = np.asarray(ppos)
                fcols.append(Column(c.type, data[p_np], valid[p_np], c.dictionary))
                flayout[s.name] = len(fcols) - 1
            for s in node.right.output_symbols:
                c = right.column(s)
                data, valid = c.to_numpy()
                b_np = np.asarray(safe_b)
                fcols.append(Column(c.type, data[b_np], valid[b_np], c.dictionary))
                flayout[s.name] = len(fcols) - 1
            from trino_tpu.strings import lower_string_calls

            fexpr = self._bind(node.filter, flayout)
            fexpr = lower_string_calls(fexpr, fcols)
            fmask = ExprCompiler(
                fcols, params=getattr(self, "_params", None)
            ).predicate_mask(fexpr)
            osel = osel & fmask
        matched = (
            jnp.zeros(left.batch.capacity, dtype=jnp.bool_)
            .at[jnp.where(osel, ppos, left.batch.capacity)]
            .set(True, mode="drop")
        )
        # three-valued IN semantics (x IN S / x NOT IN S):
        #   matched            -> TRUE / FALSE
        #   S empty            -> FALSE / TRUE
        #   x NULL, S nonempty -> NULL
        #   no match, S has NULL -> NULL
        bsel_mask = right.batch.selection_mask()
        build_nonempty = bool(np.asarray(bsel_mask).any())
        any_null_build = bool(np.asarray((~bv) & bsel_mask).any())
        pv = jnp.ones(left.batch.capacity, dtype=jnp.bool_)
        for _, kv in lkeys:
            pv = pv & kv
        if not node.null_aware or not build_nonempty:
            # EXISTS semantics: strictly TRUE/FALSE (NULL keys never match)
            valid = jnp.ones(left.batch.capacity, dtype=jnp.bool_)
        else:
            valid = matched | (pv & (not any_null_build))
        value = matched if node.join_type == "SEMI" else ~matched
        mark_col = Column(T.BOOLEAN, value, None if bool(np.asarray(valid).all()) else valid)
        cols = list(left.batch.columns) + [mark_col]
        layout = dict(left.layout)
        layout[node.mark_symbol.name] = len(cols) - 1
        return Result(Batch(cols, left.batch.num_rows, left.batch.sel), layout)

    def _exec_cross_join(self, node: P.Join) -> Result:
        left = self._exec(node.left)
        right = self._exec(node.right)
        lb = left.batch.compact()
        rb = right.batch.compact()
        nl, nr = lb.num_rows, rb.num_rows
        if node.single_row and nr > 1:
            raise ExecutionError("Scalar sub-query has returned multiple rows")
        if node.single_row and nr == 0:
            # scalar over empty subquery yields NULL: pad one all-NULL row
            from trino_tpu.spill import pad_to_one_unselected

            padded = pad_to_one_unselected(rb)
            rb = Batch(
                [
                    Column(c.type, np.asarray(c.data), np.zeros(1, dtype=np.bool_), c.dictionary)
                    for c in padded.columns
                ],
                1,
            )
            nr = 1
        if nl * nr > (1 << 24):
            raise ExecutionError("cross join too large")
        cols: list[Column] = []
        layout: dict[str, int] = {}
        li = np.repeat(np.arange(nl), nr)
        ri = np.tile(np.arange(nr), nl)
        for s in node.left.output_symbols:
            c = lb.columns[left.layout[s.name]]
            data, valid = c.to_numpy()
            cols.append(Column(c.type, data[li], None if valid[li].all() else valid[li], c.dictionary))
            layout[s.name] = len(cols) - 1
        for s in node.right.output_symbols:
            c = rb.columns[right.layout[s.name]]
            data, valid = c.to_numpy()
            cols.append(Column(c.type, data[ri], None if valid[ri].all() else valid[ri], c.dictionary))
            layout[s.name] = len(cols) - 1
        return Result(Batch(cols, nl * nr), layout)

    # === set operations =================================================
    def _exec_groupid(self, node: P.GroupId) -> Result:
        """Replicate input once per grouping set, nulling absent key
        columns; appends the group-id column (GroupIdOperator analog)."""
        res = self._exec(node.source)
        base = res.batch.compact()
        parts: list[Batch] = []
        all_key_names = {s.name for s in node.all_keys}
        for gidx, group in enumerate(node.groups):
            present = {s.name for s in group}
            cols = []
            for s in node.source.output_symbols:
                c = base.columns[res.layout[s.name]]
                if s.name in all_key_names and s.name not in present:
                    data, _valid = c.to_numpy()
                    c = Column(
                        c.type, data, np.zeros(base.num_rows, dtype=np.bool_),
                        c.dictionary,
                    )
                cols.append(c)
            cols.append(
                Column(T.BIGINT, np.full(base.num_rows, gidx, dtype=np.int64))
            )
            parts.append(Batch(cols, base.num_rows))
        merged = concat_batches(parts) if len(parts) > 1 else parts[0]
        layout = {s.name: i for i, s in enumerate(node.source.output_symbols)}
        layout[node.gid.name] = len(node.source.output_symbols)
        return Result(merged, layout)

    def _exec_setop(self, node: P.SetOp) -> Result:
        parts = []
        for inp in node.inputs:
            r = self._exec(inp)
            b = r.batch.compact()
            # reorder columns to this input's output symbol order
            cols = [b.columns[r.layout[s.name]] for s in inp.output_symbols]
            parts.append(Batch(cols, b.num_rows))
        # coerce every input's column types to the setop's output types
        coerced = []
        for p in parts:
            cols = []
            for j, s in enumerate(node.symbols):
                c = p.columns[j]
                if c.type != s.type:
                    data, valid = c.to_numpy()
                    data = _host_cast(data, c.type, s.type)
                    c = Column(s.type, data, None if valid.all() else valid, c.dictionary)
                cols.append(c)
            coerced.append(Batch(cols, p.num_rows))
        if node.op == "UNION":
            merged = concat_batches(coerced)
            res = Result(
                merged, {s.name: i for i, s in enumerate(node.symbols)}
            )
            if node.distinct:
                return self._exec_distinct(P.Distinct(_FixedNode(node.symbols, res)))
            return res
        if node.op in ("INTERSECT", "EXCEPT"):
            # set semantics (reference: ALL variants unsupported in v1 too):
            # dedupe left, then keep rows [not] present in the right side —
            # a distinct + null-aware membership test on all columns
            return self._exec_setop_membership(node, coerced)
        raise ExecutionError(f"{node.op} not supported yet")

    def _exec_setop_membership(self, node: P.SetOp, parts: list[Batch]) -> Result:
        left, right = parts[0], parts[1]
        # host-side: row tuples (NULL-safe via sentinel) — set ops are
        # usually small (DISTINCT results); device path is a later optim
        def keys(b: Batch) -> list[tuple]:
            # one device->host conversion per column, then row tuples
            col_data = []
            for c in b.columns:
                data, valid = c.to_numpy()
                if c.dictionary is not None:
                    values = [
                        c.dictionary.decode(int(code)) if ok else None
                        for code, ok in zip(data.tolist(), valid.tolist())
                    ]
                else:
                    values = [
                        v if ok else None
                        for v, ok in zip(data.tolist(), valid.tolist())
                    ]
                col_data.append(values)
            return list(zip(*col_data)) if col_data else []

        lkeys = keys(left)
        rows: list[int] = []
        if node.distinct:
            rset = set(keys(right))
            seen: set[tuple] = set()
            for i, k in enumerate(lkeys):
                if k in seen:
                    continue
                seen.add(k)
                member = k in rset
                if (node.op == "INTERSECT") == member:
                    rows.append(i)
        else:
            # ALL variants: bag semantics — INTERSECT ALL keeps
            # min(mult_l, mult_r) copies; EXCEPT ALL keeps mult_l - mult_r
            from collections import Counter

            rcount = Counter(keys(right))
            if node.op == "INTERSECT":
                taken: Counter = Counter()
                for i, k in enumerate(lkeys):
                    if taken[k] < rcount.get(k, 0):
                        taken[k] += 1
                        rows.append(i)
            else:  # EXCEPT ALL
                skipped: Counter = Counter()
                for i, k in enumerate(lkeys):
                    if skipped[k] < rcount.get(k, 0):
                        skipped[k] += 1
                    else:
                        rows.append(i)
        idx = np.asarray(rows, dtype=np.int64)
        cols = []
        for c in left.columns:
            data, valid = c.to_numpy()
            cols.append(Column(c.type, data[idx], valid[idx], c.dictionary))
        return Result(
            Batch(cols, len(rows)),
            {s.name: i for i, s in enumerate(node.symbols)},
        )

    def _exec__fixednode(self, node: "_FixedNode") -> Result:
        return node.result

    # === misc ===========================================================
    def _bind(self, expr: RowExpr, layout: dict[str, int]) -> RowExpr:
        return bind_variables(expr, layout)


@dataclasses.dataclass
class _FixedNode(P.PlanNode):
    """Adapter: present an already-computed Result as a plan source."""

    symbols: list[P.Symbol]
    result: Result

    @property
    def output_symbols(self):
        return self.symbols


def _unify_strings(expr: RowExpr, columns: Sequence[Column]):
    """Build a unified dictionary over every string column/literal referenced
    by ``expr``; return (columns with string cols remapped, unified dict)."""
    from trino_tpu.ir import SpecialForm

    channels: list[int] = []
    literals: list[str] = []

    def walk(e: RowExpr):
        if isinstance(e, InputRef) and T.is_string(e.type):
            channels.append(e.channel)
        elif isinstance(e, Constant) and T.is_string(e.type) and e.value is not None:
            literals.append(str(e.value))
        elif isinstance(e, (Call, SpecialForm)):
            for a in e.args:
                walk(a)

    walk(expr)
    union = Dictionary([])
    remaps: dict[int, np.ndarray] = {}
    for ch in dict.fromkeys(channels):
        d = columns[ch].dictionary or Dictionary([])
        union, remap = union.merged(d)
        remaps[ch] = remap
    if literals:
        union, _ = union.merged(Dictionary(list(dict.fromkeys(literals))))
    new_cols = list(columns)
    for ch, remap in remaps.items():
        c = new_cols[ch]
        codes = jnp.asarray(np.asarray(remap, dtype=np.int32))[
            jnp.maximum(c.data, 0)
        ]
        codes = jnp.where(c.data >= 0, codes, -1)
        new_cols[ch] = Column(c.type, codes, c.valid, union)
    return new_cols, union


def _f64_key(x: jnp.ndarray) -> jnp.ndarray:
    """Exact int64 equality key for float64 values (+0/-0 normalized).
    f64->i64 bitcast is unsupported under TPU x64 rewriting, so bitcast to
    two int32 lanes and recombine."""
    x = jnp.where(x == 0.0, 0.0, x.astype(jnp.float64))
    parts = jax.lax.bitcast_convert_type(x, jnp.int32)  # (..., 2)
    lo = parts[..., 0].astype(jnp.int64) & 0xFFFFFFFF
    hi = parts[..., 1].astype(jnp.int64)
    return (hi << 32) | lo


def _host_cast(data: np.ndarray, from_t: T.SqlType, to_t: T.SqlType) -> np.ndarray:
    if isinstance(to_t, T.DecimalType):
        if isinstance(from_t, T.DecimalType):
            return data * 10 ** (to_t.scale - from_t.scale)
        if T.is_integer(from_t):
            return data.astype(np.int64) * to_t.unscale
    if isinstance(to_t, (T.DoubleType, T.RealType)):
        if isinstance(from_t, T.DecimalType):
            return (data / from_t.unscale).astype(to_t.storage_dtype)
        return data.astype(to_t.storage_dtype)
    return data.astype(to_t.storage_dtype)
