"""Statement execution engine: the coordinator's analyze/plan/execute core.

Reference: ``execution/SqlQueryExecution.java:373`` (the DQL path) plus the
``DataDefinitionTask`` short-circuit family (``execution/CreateTableTask.java``,
``DataDefinitionExecution.java``) for DDL/utility statements, and
``testing/LocalQueryRunner.java`` which drives the same core in-process.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

from trino_tpu import types as T
from trino_tpu.analyzer import Analyzer, SemanticError
from trino_tpu.columnar import Batch
from trino_tpu.config import Session
from trino_tpu.connectors.api import CatalogManager, ColumnSchema, TableSchema
from trino_tpu.exec.local import ExecutionError, LocalExecutor
from trino_tpu.planner import plan as P
from trino_tpu.sql import parse_statement
from trino_tpu.sql import tree as t


@dataclasses.dataclass
class StatementResult:
    """What a statement produced (protocol-ready, host-side)."""

    rows: list[tuple]
    column_names: list[str]
    column_types: list[T.SqlType]
    update_type: Optional[str] = None  # e.g. "CREATE TABLE", "INSERT"
    update_count: Optional[int] = None
    set_session: dict[str, Any] = dataclasses.field(default_factory=dict)
    peak_memory_bytes: int = 0
    dynamic_filters: int = 0
    # prepared-statement session mutations (ride X-Trino-*-Prepare headers)
    added_prepare: Optional[tuple[str, str]] = None  # (name, sql)
    deallocated_prepare: Optional[str] = None
    # transaction mutations (X-Trino-Started-Transaction-Id / Clear-...)
    started_transaction_id: Optional[str] = None
    cleared_transaction: bool = False
    # cluster-mode retry/attempt counters (trino_tpu/ft): retry_policy,
    # task_retries, task_attempts, query_attempts — surfaced in /v1/query
    cluster_stats: dict[str, Any] = dataclasses.field(default_factory=dict)
    # skew-aware exchange counters (shuffle rows/bytes, padding ratio,
    # overflow retries, hot/salted keys) — surfaced in /v1/query
    exchange_stats: Optional[dict[str, Any]] = None
    # compile-time telemetry (cross-query program cache; Trino's
    # CacheStatsMBean analog) — surfaced in /v1/query
    compile_ms: float = 0.0  # trace+lower+compile wall paid by this query
    trace_count: int = 0  # programs traced (0 on a fully warm run)
    program_cache_hits: int = 0
    program_cache_misses: int = 0
    # device-level profiling rollup (obs/profiler.py): per-program XLA
    # FLOPs / bytes accessed / peak HBM + query totals — surfaced in
    # /v1/query as ``deviceStats``; None when profiling is off or the
    # backend reports nothing
    device_stats: Optional[dict[str, Any]] = None
    # columnar ingest tier (trino_tpu/ingest.py): split decode wall,
    # coalesced H2D bytes/transfers, device-table-cache hits/misses —
    # surfaced in /v1/query as ``ingestStats``; None when no scan ran
    ingest_stats: Optional[dict[str, Any]] = None
    # cross-query batching (exec/batching.py): batchedQueries/batchSize/
    # batchWaitMs for queries that shared a stacked dispatch — surfaced
    # in /v1/query queryStats; None when the query ran alone
    batch_stats: Optional[dict[str, Any]] = None
    # semantic result cache (trino_tpu/cache): resultCacheHit plus
    # incrementalMaintenance/deltaSplits when a statement was served (or
    # maintained) from the coordinator result cache; None on real runs
    result_cache_stats: Optional[dict[str, Any]] = None
    # in-program operator telemetry (exec/fragments.py op! channel):
    # {stable_site: {kind, rows_in, rows_out}} — surfaced in /v1/query as
    # ``operatorStats`` and as per-operator EXPLAIN ANALYZE rows; None
    # when operator_stats is off or nothing traced
    operator_stats: Optional[dict[str, Any]] = None
    # SLO sentinel verdict (obs/slo.py): regression/violation record vs
    # the fingerprint's history baseline — surfaced as
    # ``queryStats.regression``; None when within baseline or cold
    regression: Optional[dict[str, Any]] = None


class Engine:
    """Catalogs + memory pool + statement dispatch. One per server."""

    def __init__(
        self,
        catalogs: Optional[CatalogManager] = None,
        hbm_bytes: int = 16 << 30,
        mesh=None,
    ):
        from trino_tpu.memory import MemoryPool

        if catalogs is None:
            from trino_tpu.connectors.blackhole import BlackHoleConnector
            from trino_tpu.connectors.h2o import H2oConnector
            from trino_tpu.connectors.memory import MemoryConnector
            from trino_tpu.connectors.tpch import TpchConnector

            from trino_tpu.connectors.tpcds import TpcdsConnector

            catalogs = CatalogManager()
            catalogs.register("tpch", TpchConnector())
            catalogs.register("tpcds", TpcdsConnector())
            catalogs.register("h2o", H2oConnector())
            catalogs.register("memory", MemoryConnector())
            catalogs.register("blackhole", BlackHoleConnector())
        self.catalogs = catalogs
        self.memory_pool = MemoryPool(hbm_bytes)
        self.mesh = mesh  # used by execution_mode=distributed
        import threading

        self._query_seq = 0
        self._seq_lock = threading.Lock()
        # observability (QueryMonitor -> EventListenerManager; system catalog)
        from trino_tpu.events import EventListenerManager

        self.event_listeners = EventListenerManager()
        from collections import deque

        self._recent_queries: "deque[dict]" = deque(maxlen=200)
        self._runtime_nodes_fn = None  # server installs live node info
        # transactions + access control (SURVEY §2 Transactions / Security)
        from trino_tpu.security import AccessControlManager
        from trino_tpu.transaction import TransactionManager

        self.transaction_manager = TransactionManager(self.catalogs)
        self.access_control = AccessControlManager()
        # multi-host scheduling (server/cluster.py installs this on
        # coordinator servers; execution_mode=cluster routes through it)
        self.cluster_scheduler = None
        # multi-host SPMD (server installs SpmdRunner + peer discovery when
        # booted inside a jax.distributed group; fusable cluster queries
        # then run as one pjit program spanning every process)
        self.spmd = None
        self.spmd_peers = None
        try:
            from trino_tpu.connectors.system import SystemConnector

            self.catalogs.register("system", SystemConnector(self))
        except Exception:  # noqa: BLE001 — system catalog is best-effort
            pass
        # plan + compiled-program reuse for repeated read-only queries
        # (keyed by SQL text, session fingerprint, and catalog data
        # versions; jax.jit re-traces on its own if input shapes change)
        from collections import OrderedDict

        self._query_cache: "OrderedDict[tuple, dict]" = OrderedDict()
        self._query_cache_lock = threading.Lock()
        # device-resident table cache (trino_tpu/ingest.py): scanned
        # tables stay HBM-resident across queries, keyed by catalog data
        # version + projection + splits, so a warm repeat scan issues
        # zero H2D bytes; engine-owned so every executor shares it
        from trino_tpu.ingest import DeviceTableCache

        self.table_cache = DeviceTableCache()
        # cross-query batch collector (exec/batching.py): when
        # batch_window_ms > 0, compatible queries (same canonical-plan
        # fingerprint, differing only in hoisted literals) wait here for
        # a short window and share ONE stacked device dispatch
        from trino_tpu.exec.batching import BatchCollector

        self.batch_collector = BatchCollector(self)
        # query-history stores (obs/history.py): per-fingerprint observed
        # execution truth, keyed by the session's history_dir ("" = the
        # in-memory per-process store). Engine-owned so every query of a
        # dir shares one store object (and its lock)
        self._history_stores: dict[str, Any] = {}
        self._history_lock = threading.Lock()
        # semantic result cache (trino_tpu/cache): final result sets keyed
        # by (canonical fingerprint, hoisted-param vector) and validated
        # against data versions + ACL generation; the result_cache session
        # knob gates both probe and store
        from trino_tpu.cache.result_cache import ResultCache

        self.result_cache = ResultCache()

    _QUERY_CACHE_MAX = 64
    # statements whose results depend on evaluation time/randomness must
    # not reuse a cached plan; matched against whole lexer identifiers —
    # NOT substrings — so a function `brand()` or a column `randomness`
    # doesn't silently disable caching (`current_timestamp` and friends
    # lex as single IDENT tokens, underscores included)
    _UNCACHEABLE_IDENTS = frozenset({
        "random", "rand", "now", "uuid", "current_time", "current_date",
        "current_timestamp", "localtime", "localtimestamp",
    })

    def _sql_cacheable(self, sql: str) -> bool:
        from trino_tpu.sql.lexer import SqlSyntaxError, tokenize

        try:
            tokens = tokenize(sql)
        except SqlSyntaxError:
            return False  # let the parser produce the real error, uncached
        return not any(
            tok.kind in ("IDENT", "KW")
            and tok.text.lower() in self._UNCACHEABLE_IDENTS
            for tok in tokens
        )

    def _query_cache_entry(self, fingerprint: str) -> dict:
        """Cache slot for this (plan fingerprint, data-version) pair.

        The fingerprint already folds in plan shape, dtypes, mesh, and the
        codegen-relevant session properties (planner/canonicalize.py), so
        the key only adds what the fingerprint cannot see: catalog data
        versions (string dictionaries are trace-time constants, so new
        data must retrace) and the access-control generation (rule changes
        must drop entries immediately). The user is deliberately absent —
        per-user literals ride the parameter vector, and plans that differ
        structurally per user fingerprint differently on their own.
        """
        import threading

        versions = tuple(
            (name, getattr(self.catalogs.get(name), "_version", 0))
            for name in sorted(self.catalogs.names())
        )
        key = (fingerprint, versions, self.access_control.generation)
        with self._query_cache_lock:
            entry = self._query_cache.get(key)
            if entry is None:
                entry = {"plan": None, "programs": {}, "lock": threading.Lock()}
                self._query_cache[key] = entry
                while len(self._query_cache) > self._QUERY_CACHE_MAX:
                    self._query_cache.popitem(last=False)
            else:
                self._query_cache.move_to_end(key)
        return entry

    # --- runtime introspection (system connector backend) -----------------

    def runtime_queries(self) -> list[dict]:
        import time as _time

        out = []
        for rec in list(self._recent_queries):
            rec = dict(rec)
            if rec["state"] == "RUNNING":  # live elapsed for in-flight queries
                rec["elapsedTimeMillis"] = int(
                    (_time.monotonic() - rec["_start"]) * 1000
                )
            rec.pop("_start", None)
            out.append(rec)
        return out

    def _next_query_id(self) -> str:
        with self._seq_lock:
            self._query_seq += 1
            return f"q{self._query_seq}"

    def runtime_nodes(self) -> list[tuple]:
        if self._runtime_nodes_fn is not None:
            return self._runtime_nodes_fn()
        return [("local", "local://", "trino-tpu-0.1", True, "ACTIVE")]

    def runtime_tasks(self) -> list[dict]:
        """Live worker-task info for ``system.runtime.tasks``. The server
        installs ``_runtime_tasks_fn`` (its SqlTaskManager registry);
        standalone engines have no tasks."""
        fn = getattr(self, "_runtime_tasks_fn", None)
        if fn is not None:
            return fn()
        return []

    def runtime_metrics(self) -> list[tuple]:
        """Live metrics-registry snapshot for ``system.runtime.metrics``:
        one row per (name{labels}, kind, value) — histograms expose their
        count/sum/p50/p99 as separate rows."""
        from trino_tpu.obs.metrics import get_registry

        snap = get_registry().snapshot()
        rows: list[tuple] = []
        for key, val in sorted(snap.get("counters", {}).items()):
            rows.append((key, "counter", float(val)))
        for key, val in sorted(snap.get("gauges", {}).items()):
            rows.append((key, "gauge", float(val)))
        for key, h in sorted(snap.get("histograms", {}).items()):
            for field in ("count", "sum", "p50", "p99"):
                v = h.get(field)
                if v is not None:
                    rows.append((f"{key}.{field}", "histogram", float(v)))
        return rows

    def runtime_programs(self) -> list[dict]:
        """Cross-query program-cache contents for
        ``system.runtime.programs``: one row per cached compiled program,
        with the store's cumulative compile counters (the same numbers
        /v1/query reports per query) and the profiler's captured XLA
        cost/memory stats where the backend provided them."""
        from trino_tpu.exec.fragments import program_label

        with self._query_cache_lock:
            items = [
                (key[0], entry["programs"])
                for key, entry in self._query_cache.items()
            ]
        rows: list[dict] = []
        for fingerprint, programs in items:
            store_stats = programs.get("__stats__") or {}
            for key, val in programs.items():
                if not (
                    isinstance(key, tuple)
                    and len(key) == 2
                    and isinstance(key[0], tuple)
                    and isinstance(val, tuple)
                    and len(val) == 2
                ):
                    continue
                meta = val[1]
                ds = getattr(meta, "device_stats", None) or {}
                rows.append(
                    {
                        "fingerprint": fingerprint,
                        "program": program_label(key[0]),
                        "hits": int(store_stats.get("hits", 0)),
                        "misses": int(store_stats.get("misses", 0)),
                        "compile_ms": float(store_stats.get("compile_ms", 0.0)),
                        "flops": ds.get("flops"),
                        "peak_hbm_bytes": ds.get("peak_hbm_bytes"),
                        "bytes_accessed": ds.get("bytes_accessed"),
                    }
                )
        return rows

    # --- query history (obs/history.py) -----------------------------------

    def history_store(self, session: Session):
        """The :class:`QueryHistoryStore` this session resolves to, or
        None when ``query_history`` is off. One store per ``history_dir``
        ("" keeps it in-memory, the tier-1-safe default)."""
        try:
            if not bool(session.get("query_history")):
                return None
            hdir = str(session.get("history_dir") or "")
            max_entries = int(session.get("history_max_entries"))
            max_bytes = int(session.get("history_max_bytes"))
        except KeyError:
            return None
        import os

        from trino_tpu.obs.history import QueryHistoryStore

        path = os.path.join(hdir, "query_history.json") if hdir else ""
        with self._history_lock:
            store = self._history_stores.get(hdir)
            if store is None:
                store = QueryHistoryStore(
                    path=path, max_entries=max_entries, max_bytes=max_bytes
                )
                self._history_stores[hdir] = store
            return store

    def history_snapshot(self) -> dict:
        """Every history store this engine has resolved, merged — the
        ``GET /v1/history`` body."""
        with self._history_lock:
            stores = sorted(self._history_stores.items())
        return {"stores": [s.snapshot() for _, s in stores]}

    def runtime_history(self) -> list[dict]:
        """Flat per-fingerprint rows for ``system.runtime.history``."""
        with self._history_lock:
            stores = [s for _, s in sorted(self._history_stores.items())]
        rows: list[dict] = []
        for store in stores:
            for fp, ent in store.entries():
                rec = dict(ent)
                rec["fingerprint"] = fp
                rec["path"] = store.path
                rows.append(rec)
        return rows

    @staticmethod
    def _history_record(hist, fp, res, elapsed_ms: float) -> None:
        """Fold one finished query's observed stats into the history
        store. Best-effort by contract: history must never fail (or slow
        down observably) the query that feeds it."""
        if hist is None or fp is None or res is None:
            return
        try:
            ex = (
                res.exchange_stats
                if isinstance(res.exchange_stats, dict)
                else {}
            )
            ds = (
                res.device_stats if isinstance(res.device_stats, dict) else {}
            )
            bs = res.batch_stats if isinstance(res.batch_stats, dict) else {}
            caps: dict[str, dict] = {}
            for val in (ex.get("capacities") or {}).values():
                if not isinstance(val, dict):
                    continue
                site = val.get("site")
                # only restart-stable names persist — raw tracer names
                # embed id(node) and mean nothing to the next process
                if not isinstance(site, str) or "@" not in site:
                    continue
                caps[site] = {
                    "value": val.get("value"),
                    "provenance": val.get("provenance", ""),
                }
            observed: dict[str, Any] = {
                "elapsed_ms": round(float(elapsed_ms), 3),
                "rows": len(res.rows),
                "overflow_retries": int(ex.get("overflow_retries", 0) or 0),
                "compile_halvings": int(ex.get("compile_halvings", 0) or 0),
                "padding_ratio": float(ex.get("padding_ratio", 0.0) or 0.0),
                "shuffle_rows": int(ex.get("shuffle_rows", 0) or 0),
                "capacities": caps,
            }
            ops: dict[str, dict] = {}
            for site, ent in (ex.get("operators") or {}).items():
                if not isinstance(ent, dict) or "@" not in str(site):
                    continue
                ops[str(site)] = {
                    "kind": str(ent.get("kind", "")),
                    "rows_in": int(ent.get("rows_in", 0) or 0),
                    "rows_out": int(ent.get("rows_out", 0) or 0),
                }
            if ops:
                # the partial-agg reduction-ratio seed the mid-query
                # adaptivity roadmap item reads (EWMA'd per site in
                # obs/history.py)
                observed["operators"] = ops
            flops = ds.get("total_flops")
            if isinstance(flops, (int, float)):
                observed["flops"] = float(flops)
            peak = ds.get("peak_hbm_bytes")
            if isinstance(peak, (int, float)) and peak > 0:
                observed["peak_hbm_bytes"] = int(peak)
            if bs.get("batchSize"):
                observed["batch_size"] = int(bs["batchSize"])
            hist.record(fp, observed)
        except Exception:  # noqa: BLE001
            pass

    @staticmethod
    def _sentinel_check(
        session, fp, res, elapsed_ms: float, hist_entry, query_id=None
    ) -> None:
        """Judge this completion against the fingerprint's PRE-run history
        baseline and configured SLOs (obs/slo.py); the verdict rides the
        result as ``regression`` → queryStats. Best-effort like history:
        the sentinel must never fail the query it observes."""
        try:
            from trino_tpu.obs.slo import get_sentinel

            verdict = get_sentinel().evaluate(
                session,
                fp,
                elapsed_ms,
                hist_entry,
                query_id=query_id,
            )
            if res is not None and verdict is not None:
                res.regression = verdict
        except Exception:  # noqa: BLE001
            pass

    # === entry ============================================================

    def execute_statement(
        self,
        sql: str,
        session: Session,
        query_id: Optional[str] = None,
        fire_events: bool = True,
    ) -> StatementResult:
        """Run one statement.

        ``query_id`` lets a caller that already owns the query lifecycle
        (ManagedQuery on the server) pin its id so traces/events/system
        tables all agree; ``fire_events=False`` hands event ownership to
        that caller too, so server terminal paths (kill/cancel/reject)
        can fire exactly one completed event themselves.
        """
        import time as _time

        from trino_tpu.events import QueryCompletedEvent, QueryCreatedEvent
        from trino_tpu.obs.metrics import get_registry
        from trino_tpu.obs.trace import get_tracer

        qid = query_id or self._next_query_id()
        t0 = _time.time()  # epoch: event create_time / display only
        t0m = _time.monotonic()  # interval math
        if fire_events:
            self.event_listeners.fire_created(
                QueryCreatedEvent(qid, sql, session.user, t0)
            )
        tracer = get_tracer()
        # root span when standalone; child "execute" span when a server
        # ManagedQuery already opened the query root on this thread
        span = tracer.start_span(
            "query" if tracer.current() is None else "execute",
            trace_id=qid if tracer.current() is None else None,
            attrs={"queryId": qid, "user": session.user},
        )
        record = {
            "queryId": qid, "state": "RUNNING", "user": session.user,
            "source": session.source, "query": sql, "elapsedTimeMillis": 0,
            "peakMemoryBytes": 0, "outputRows": 0, "_start": t0m,
        }
        self._recent_queries.append(record)
        error: Optional[str] = None
        exc: Optional[BaseException] = None
        res: Optional[StatementResult] = None
        # Validate + pin the session's explicit transaction for the duration
        # of this statement: a stale/expired __txn must error (reference
        # errors on unknown transaction ids), and a live one must not be
        # idle-expired mid-statement.
        txn_info = None
        try:
            txn_id = session.properties.get("__txn")
            if txn_id:
                try:
                    txn_info = self.transaction_manager.get(txn_id)  # touches
                    txn_info.busy += 1
                except Exception:
                    session.properties.pop("__txn", None)
                    raise
            with tracer.activate(span):
                res = self._execute_statement_inner(sql, session, qid)
            return res
        except Exception as e:  # noqa: BLE001
            error = str(e)
            exc = e
            raise
        finally:
            if txn_info is not None:
                txn_info.busy -= 1
                txn_info.last_access = _time.time()
            end = _time.time()
            wall = _time.monotonic() - t0m
            record["state"] = "FINISHED" if error is None else "FAILED"
            record["elapsedTimeMillis"] = int(wall * 1000)
            if res is not None:
                record["peakMemoryBytes"] = res.peak_memory_bytes
                record["outputRows"] = len(res.rows)
            span.finish(
                status="OK" if error is None else "ERROR",
                state=record["state"],
                rows=record["outputRows"],
            )
            self._record_query_metrics(get_registry(), record, res, wall)
            if fire_events:
                err_code = err_type = None
                if exc is not None:
                    from trino_tpu.errors import classify_error

                    err_code, _, err_type = classify_error(exc)
                self.event_listeners.fire_completed(
                    QueryCompletedEvent(
                        qid, sql, session.user, t0, end,
                        record["state"],
                        output_rows=record["outputRows"],
                        peak_memory_bytes=record["peakMemoryBytes"],
                        error_message=error,
                        wall_seconds=wall,
                        error_code=err_code,
                        error_type=err_type,
                    )
                )

    @staticmethod
    def _record_query_metrics(reg, record: dict, res, wall_s: float) -> None:
        """Fold one statement's counters into the process registry."""
        reg.counter("trino_tpu_queries_total", state=record["state"]).inc()
        reg.histogram("trino_tpu_query_elapsed_ms").observe(wall_s * 1000.0)
        reg.counter("trino_tpu_output_rows_total").inc(record["outputRows"])
        if res is None:
            return
        reg.counter("trino_tpu_compile_ms_total").inc(res.compile_ms)
        reg.counter("trino_tpu_trace_count_total").inc(res.trace_count)
        reg.counter("trino_tpu_program_cache_hits_total").inc(
            res.program_cache_hits
        )
        reg.counter("trino_tpu_program_cache_misses_total").inc(
            res.program_cache_misses
        )
        for key, val in (res.exchange_stats or {}).items():
            # batchedQueries is shared verbatim by every member of a
            # batched dispatch — summing K copies of K is meaningless;
            # trino_tpu_batched_dispatches_total{size} is the real counter
            if key == "batchedQueries":
                continue
            if isinstance(val, (int, float)) and not isinstance(val, bool):
                # key = exchange stat field names, a closed vocabulary
                reg.counter(f"trino_tpu_exchange_{key}_total").inc(val)  # lint: ignore[OBS002]
        for ent in (res.operator_stats or {}).values():
            # kind is a closed vocabulary minted by the tracer
            # (scan/filter/join/semijoin/partial-agg/final-agg/agg/exchange)
            if isinstance(ent, dict) and ent.get("kind"):
                reg.counter(
                    "trino_tpu_operator_rows_total",
                    kind=ent["kind"], io="in",
                ).inc(int(ent.get("rows_in", 0) or 0))
                reg.counter(
                    "trino_tpu_operator_rows_total",
                    kind=ent["kind"], io="out",
                ).inc(int(ent.get("rows_out", 0) or 0))
        ds = res.device_stats or {}
        if isinstance(ds.get("total_flops"), (int, float)):
            reg.counter("trino_tpu_query_flops_total").inc(ds["total_flops"])
        if isinstance(ds.get("peak_hbm_bytes"), (int, float)):
            reg.gauge("trino_tpu_query_peak_hbm_bytes").set(
                ds["peak_hbm_bytes"]
            )

    # --- semantic result cache (trino_tpu/cache) --------------------------

    def _result_cache_on(self, session: Session) -> bool:
        try:
            if not bool(session.get("result_cache")):
                return False
        except KeyError:
            return False
        # snapshot semantics inside explicit transactions are per-txn
        return "__txn" not in session.properties

    def try_cached_result(
        self, sql: str, session: Session, allow_maintenance: bool = True
    ) -> Optional[StatementResult]:
        """Serve this statement from the semantic result cache, or None.

        Pure-hit lookups are microseconds and safe anywhere off the event
        loop; ``allow_maintenance`` additionally permits an incremental
        delta merge, which executes a scan and therefore belongs on a
        worker/dispatch thread only (the QueryManager admission fast path
        passes False)."""
        if not self._result_cache_on(session):
            return None
        try:
            return self.result_cache.lookup(
                self, sql, session, allow_maintenance=allow_maintenance
            )
        except Exception:  # noqa: BLE001 — the cache must never fail a query
            return None

    def _result_cache_begin(
        self, sql_text: Optional[str], session: Session, plan: P.PlanNode
    ) -> Optional[dict]:
        """Pre-execution snapshot for the store: referenced tables + their
        data versions, captured BEFORE execution so a write landing during
        the run leaves the entry conservatively stale, never wrong."""
        if sql_text is None or not self._result_cache_on(session):
            return None
        if not self._sql_cacheable(sql_text):
            return None
        try:
            from trino_tpu.cache.result_cache import (
                referenced_tables,
                versions_snapshot,
            )

            tables = referenced_tables(plan)
            if not tables:
                return None  # literal-only results are not worth an entry
            for cat in dict.fromkeys(c for c, _, _ in tables):
                conn = self.catalogs.get(cat)
                if not getattr(conn, "supports_result_caching", True):
                    return None  # live state (system tables): never cache
            versions = versions_snapshot(self.catalogs, tables)
        except Exception:  # noqa: BLE001
            return None
        return {"tables": tables, "versions": versions}

    def _result_cache_store(
        self,
        ctx: Optional[dict],
        sql_text: str,
        session: Session,
        plan: P.PlanNode,
        res: Optional[StatementResult],
    ) -> None:
        if ctx is None or res is None or res.update_type is not None:
            return
        try:
            from trino_tpu.planner.canonicalize import canonicalize_plan

            mesh_n = (
                int(self.mesh.devices.size) if self.mesh is not None else 1
            )
            # recompute the (fingerprint, params) pair from the BAKED plan
            # here rather than reusing the dispatch path's: cluster mode
            # computes a record-only fingerprint with the param vector
            # discarded, and aliasing two literal variants onto one
            # entry key would serve one query's rows for the other
            _, params, fp = canonicalize_plan(plan, session, mesh_n)
            if fp is None:
                return
            maintain = None
            try:
                if bool(session.get("incremental_maintenance")):
                    from trino_tpu.planner.canonicalize import (
                        classify_maintainability,
                    )

                    maintain = classify_maintainability(plan)
            except KeyError:
                maintain = None
            try:
                max_bytes = int(session.get("result_cache_max_bytes"))
            except KeyError:
                max_bytes = None
            self.result_cache.store(
                sql=sql_text,
                session=session,
                fingerprint=fp,
                params=params,
                tables=ctx["tables"],
                versions=ctx["versions"],
                acl_generation=self.access_control.generation,
                res=res,
                maintain=maintain,
                plan=plan,
                max_bytes=max_bytes,
            )
        except Exception:  # noqa: BLE001 — the cache must never fail a query
            pass

    def _execute_statement_inner(
        self, sql: str, session: Session, query_id: Optional[str] = None
    ) -> StatementResult:
        # result-cache probe BEFORE parse: sub-millisecond hits cannot
        # afford parse+plan, so the cache's SQL-text memo (populated at
        # store time, validated against data versions + ACL generation)
        # routes repeat texts straight to host-resident rows
        cached = self.try_cached_result(sql, session)
        if cached is not None:
            return cached
        from trino_tpu.obs.trace import get_tracer

        with get_tracer().span("parse") as span:
            stmt = parse_statement(sql)
            span.set("statement", type(stmt).__name__)
        if isinstance(stmt, t.Prepare):
            # keep the statement's SQL text: it must survive the stateless
            # HTTP protocol via X-Trino-Added-Prepare
            import re as _re

            m = _re.match(
                r"\s*prepare\s+\S+\s+from\s+(.*)$",
                sql.strip().rstrip(";"),
                _re.IGNORECASE | _re.DOTALL,
            )
            if m:
                stmt = dataclasses.replace(stmt, sql=m.group(1).strip())
        return self._dispatch_parsed(stmt, session, query_id, sql_text=sql)

    def _dispatch_parsed(
        self,
        stmt: t.Node,
        session: Session,
        query_id: Optional[str] = None,
        sql_text: Optional[str] = None,
    ) -> StatementResult:
        handler = getattr(self, f"_do_{type(stmt).__name__.lower()}", None)
        if handler is not None:
            return handler(stmt, session)
        if isinstance(stmt, t.Query):
            # always (re-)plan: planning is cheap host work, and the
            # canonical fingerprint of the optimized plan — not the SQL
            # text — keys the program cache, so `x < 24` and `x < 25`
            # land on the same entry with different parameter vectors
            plan = self.plan(stmt, session)
            # one span round what happens between the optimizer and the
            # executor: the result-cache snapshot, the canonical fingerprint
            # and the program-cache / history probes
            from trino_tpu.obs.trace import get_tracer

            with get_tracer().span("canonicalize") as cspan:
                # result-cache store context (tables + PRE-execution data
                # versions); None when the cache is off or the shape refuses
                rc_ctx = self._result_cache_begin(sql_text, session, plan)
                exec_plan, params, entry, fp = plan, [], None, None
                mode = session.get("execution_mode")
                try:
                    wants_batch = int(session.get("batch_window_ms")) > 0
                except KeyError:
                    wants_batch = False
                mesh_n = (
                    int(self.mesh.devices.size) if self.mesh is not None else 1
                )
                if (
                    sql_text is not None
                    # cluster queries canonicalize only to join the batch
                    # collector (grouping needs the fingerprint); each
                    # member binds its own literals back before the
                    # scheduler ships fragments (_execute_query_plan)
                    and (
                        mode == "distributed"
                        or (mode == "cluster" and wants_batch)
                    )
                    and session.get("fragment_execution")
                    and bool(session.get("program_cache"))
                    and self._sql_cacheable(sql_text)
                ):
                    from trino_tpu.planner.canonicalize import canonicalize_plan

                    canonical, params, fp = canonicalize_plan(
                        plan, session, mesh_n
                    )
                    if fp is not None:
                        exec_plan = canonical
                        entry = self._query_cache_entry(fp)
                    else:
                        params = []  # unserializable shape: run baked, uncached
                elif (
                    sql_text is not None
                    and mode == "cluster"
                    and self._sql_cacheable(sql_text)
                ):
                    # record-only fingerprint: cluster queries execute the
                    # baked plan, but the history store still keys their
                    # observed truth (and the admission gate their peak HBM)
                    # by the same canonical fingerprint
                    try:
                        from trino_tpu.planner.canonicalize import (
                            canonicalize_plan,
                        )

                        _, _, fp = canonicalize_plan(plan, session, mesh_n)
                    except Exception:  # noqa: BLE001
                        fp = None
                hist = self.history_store(session) if fp is not None else None
                hist_entry = hist.get(fp) if hist is not None else None
                cspan.set("fingerprint", fp)
                cspan.set(
                    "cacheHit",
                    entry is not None and entry["plan"] is not None,
                )
            # cross-query batching: when the session opts in, compatible
            # queries (same fingerprint + same session signature) wait in
            # the collector for a short window and share ONE stacked
            # device dispatch through the cached programs. Transactions
            # are excluded (snapshot semantics are per-statement), and
            # window=0 — the default — keeps the path below verbatim.
            import time as _time

            if (
                entry is not None
                and wants_batch
                and "__txn" not in session.properties
            ):
                t0 = _time.monotonic()
                res = self.batch_collector.submit(
                    entry,
                    exec_plan,
                    session,
                    params,
                    query_id or self._next_query_id(),
                )
                elapsed_ms = (_time.monotonic() - t0) * 1000.0
                self._sentinel_check(
                    session, fp, res, elapsed_ms, hist_entry,
                    query_id=query_id,
                )
                self._history_record(hist, fp, res, elapsed_ms)
                if isinstance(res.exchange_stats, dict):
                    res.exchange_stats["history_hits"] = (
                        1 if hist_entry is not None else 0
                    )
                self._result_cache_store(rc_ctx, sql_text, session, plan, res)
                return res
            # shared program stores and capacity objects are not safe for
            # concurrent executors: a second in-flight run of the same
            # fingerprint executes uncached instead of waiting
            if entry is not None and not entry["lock"].acquire(blocking=False):
                entry = None
            try:
                programs = None
                if entry is not None:
                    if entry["plan"] is None:
                        entry["plan"] = exec_plan
                    # same fingerprint == same shape: execute the FIRST
                    # cached plan object so fragment node identities (and
                    # with them program keys and caps sites) stay stable
                    # across queries; this query's literals ride in via
                    # the parameter vector
                    exec_plan = entry["plan"]
                    programs = entry["programs"]
                t0 = _time.monotonic()
                res = self._execute_query_plan(
                    exec_plan, session, query_id=query_id,
                    programs=programs, params=params, history=hist_entry,
                )
                elapsed_ms = (_time.monotonic() - t0) * 1000.0
                self._sentinel_check(
                    session, fp, res, elapsed_ms, hist_entry,
                    query_id=query_id,
                )
                self._history_record(hist, fp, res, elapsed_ms)
                if isinstance(res.exchange_stats, dict):
                    # did a prior run of this fingerprint inform this one?
                    # (surfaced as queryStats.historyHits on /v1/query)
                    res.exchange_stats["history_hits"] = (
                        1 if hist_entry is not None else 0
                    )
                self._result_cache_store(rc_ctx, sql_text, session, plan, res)
                return res
            finally:
                if entry is not None:
                    entry["lock"].release()
        raise SemanticError(f"unsupported statement: {type(stmt).__name__}")

    def fingerprint(
        self, sql: str, session: Session
    ) -> tuple[Optional[str], list]:
        """Canonical-plan fingerprint + hoisted params for a SELECT —
        None for uncacheable statements (prewarm/test helper)."""
        from trino_tpu.planner.canonicalize import canonicalize_plan

        stmt = parse_statement(sql)
        if not isinstance(stmt, t.Query) or not self._sql_cacheable(sql):
            return None, []
        plan = self.plan(stmt, session)
        mesh_n = int(self.mesh.devices.size) if self.mesh is not None else 1
        _, params, fp = canonicalize_plan(plan, session, mesh_n)
        return fp, params

    def plan(self, stmt: t.Node, session: Session) -> P.PlanNode:
        from trino_tpu.obs.trace import get_tracer
        from trino_tpu.planner.optimizer import optimize

        tracer = get_tracer()
        analyzer = Analyzer(self.catalogs, session, self.access_control)
        with tracer.span("plan"):
            plan = analyzer.plan_statement(stmt)
        with tracer.span("optimize"):
            return optimize(plan, session, self.catalogs)

    # === DQL ==============================================================

    def _execute_query_plan(
        self,
        plan: P.PlanNode,
        session: Session,
        collector=None,
        query_id: Optional[str] = None,
        programs: Optional[dict] = None,
        params: Optional[list] = None,
        history: Optional[dict] = None,
    ) -> StatementResult:
        from trino_tpu.memory import QueryMemoryContext

        if session.get("execution_mode") == "cluster" and (
            self.cluster_scheduler is not None or self.spmd is not None
        ):
            if params:
                # a canonical (hoisted) plan reached the cluster path — a
                # batch member, or its sequential fallback. The wire serde
                # drops hoisted values, so bake this query's literals back
                from trino_tpu.planner.canonicalize import bind_params

                plan = bind_params(plan, params)
            batch = None
            if self.spmd is not None and self.spmd_peers is not None:
                from trino_tpu.parallel.spmd import SpmdUnsupported

                try:
                    batch, names = self.spmd.execute(
                        plan, session, self.spmd_peers()
                    )
                except SpmdUnsupported:
                    batch = None  # non-fusable: per-task scheduling below
            cluster_stats: dict[str, Any] = {}
            if batch is None and self.cluster_scheduler is not None:
                batch, names = self.cluster_scheduler.execute(
                    plan, session, stats_sink=cluster_stats,
                    query_id=query_id,
                )
            if batch is not None:
                return StatementResult(
                    batch.to_pylist(),
                    names,
                    [c.type for c in batch.columns],
                    cluster_stats=cluster_stats,
                    device_stats=cluster_stats.get("deviceStats"),
                    exchange_stats=cluster_stats.get("exchangeStats"),
                    ingest_stats=cluster_stats.get("ingestStats"),
                    operator_stats=(
                        cluster_stats.get("exchangeStats") or {}
                    ).get("operators"),
                )
        ctx = QueryMemoryContext(
            self.memory_pool,
            query_id or self._next_query_id(),
            max_bytes=int(session.get("query_max_memory_bytes")),
        )
        try:
            executor = self._executor(
                session, ctx, programs=programs, params=params,
                history=history,
            )
            executor.stats_collector = collector
            batch, names = executor.execute(plan)
            snap = getattr(executor, "exchange_stats_snapshot", None)
            exchange_stats = snap() if callable(snap) else (
                dict(executor.exchange_stats)
                if getattr(executor, "exchange_stats", None)
                else None
            )
            cs = getattr(executor, "compile_stats", None) or {}
            dsnap = getattr(executor, "device_stats_snapshot", None)
            from trino_tpu.obs.trace import get_tracer

            # the device->host pull of the answer and the typing of its rows
            with get_tracer().span("result.pull") as span:
                rows = batch.to_pylist()
                span.set("rows", len(rows))
            return StatementResult(
                rows,
                names,
                [c.type for c in batch.columns],
                peak_memory_bytes=ctx.peak_bytes,
                dynamic_filters=len(executor.dynamic_filters),
                exchange_stats=exchange_stats,
                compile_ms=round(float(cs.get("compile_ms", 0.0)), 3),
                trace_count=int(cs.get("trace_count", 0)),
                program_cache_hits=int(cs.get("program_cache_hits", 0)),
                program_cache_misses=int(cs.get("program_cache_misses", 0)),
                device_stats=dsnap() if callable(dsnap) else None,
                ingest_stats=executor.ingest_stats_snapshot(),
                operator_stats=(exchange_stats or {}).get("operators"),
            )
        finally:
            ctx.close()

    def _execute_query_plan_batched(
        self,
        plan: P.PlanNode,
        session: Session,
        query_ids: list[str],
        param_lists: list[list],
        programs: Optional[dict] = None,
    ) -> list[StatementResult]:
        """Run K literal-variant queries of the SAME cached plan as one
        stacked device dispatch, one StatementResult per member in
        submission order.

        One memory context and one FragmentedExecutor serve the whole
        batch, so exchange/compile/device snapshots are shared across the
        K results (each member reports the batch's dispatch, not a
        pro-rated share). Raises BatchUnsupported — or any execution
        error — for exec/batching.py to fall back to sequential runs.
        """
        from trino_tpu.exec.fragments import (
            BatchUnsupported,
            FragmentedExecutor,
        )
        from trino_tpu.memory import QueryMemoryContext

        ctx = QueryMemoryContext(
            self.memory_pool,
            query_ids[0],
            max_bytes=int(session.get("query_max_memory_bytes")),
        )
        try:
            executor = self._executor(
                session, ctx, programs=programs, params=param_lists[0]
            )
            if not isinstance(executor, FragmentedExecutor):
                raise BatchUnsupported("fragment execution disabled")
            param_sets = [[v for v, _ in pl] for pl in param_lists]
            batches, names = executor.execute_batched(plan, param_sets)
            snap = getattr(executor, "exchange_stats_snapshot", None)
            exchange_stats = snap() if callable(snap) else (
                dict(executor.exchange_stats)
                if getattr(executor, "exchange_stats", None)
                else None
            )
            cs = getattr(executor, "compile_stats", None) or {}
            dsnap = getattr(executor, "device_stats_snapshot", None)
            device_stats = dsnap() if callable(dsnap) else None
            ingest_stats = executor.ingest_stats_snapshot()
            return [
                StatementResult(
                    batch.to_pylist(),
                    list(names),
                    [c.type for c in batch.columns],
                    peak_memory_bytes=ctx.peak_bytes,
                    exchange_stats=exchange_stats,
                    compile_ms=round(float(cs.get("compile_ms", 0.0)), 3),
                    trace_count=int(cs.get("trace_count", 0)),
                    program_cache_hits=int(cs.get("program_cache_hits", 0)),
                    program_cache_misses=int(
                        cs.get("program_cache_misses", 0)
                    ),
                    device_stats=device_stats,
                    ingest_stats=ingest_stats,
                    operator_stats=(exchange_stats or {}).get("operators"),
                )
                for batch in batches
            ]
        finally:
            ctx.close()

    def _executor(
        self,
        session: Session,
        ctx,
        programs: Optional[dict] = None,
        params: Optional[list] = None,
        history: Optional[dict] = None,
    ) -> LocalExecutor:
        mode = session.get("execution_mode")
        if mode == "distributed":
            if session.get("fragment_execution"):
                from trino_tpu.exec.fragments import FragmentedExecutor

                ex = FragmentedExecutor(
                    self.catalogs, session, self.mesh, memory_ctx=ctx,
                    programs=programs, params=params, history=history,
                )
            else:
                from trino_tpu.parallel.distributed import (
                    DistributedExecutor,
                )

                ex = DistributedExecutor(
                    self.catalogs, session, self.mesh, memory_ctx=ctx
                )
        else:
            ex = LocalExecutor(self.catalogs, session, memory_ctx=ctx)
        # every executor shares the engine-wide device table cache: a warm
        # repeat scan of an unchanged table decodes and uploads nothing
        ex.table_cache = self.table_cache
        return ex

    def _run_query_rows(self, query: t.Query, session: Session) -> tuple[Batch, list[str]]:
        plan = self.plan(query, session)
        from trino_tpu.memory import QueryMemoryContext

        ctx = QueryMemoryContext(
            self.memory_pool,
            self._next_query_id(),
            max_bytes=int(session.get("query_max_memory_bytes")),
        )
        try:
            return self._executor(session, ctx).execute(plan)
        finally:
            ctx.close()

    # === session control ==================================================

    def _do_setsession(self, stmt: t.SetSession, session: Session) -> StatementResult:
        value = stmt.value
        v: Any = value.value if isinstance(value, t.Literal) else None
        session.set(stmt.name, v)
        return StatementResult(
            [], ["result"], [T.BOOLEAN],
            update_type="SET SESSION", set_session={stmt.name: v},
        )

    # === metadata / SHOW ==================================================

    def _do_showcatalogs(self, stmt, session) -> StatementResult:
        names = self.access_control.filter_catalogs(
            session.user, self.catalogs.names()
        )
        return StatementResult([(n,) for n in names], ["Catalog"], [T.VARCHAR])

    def _do_showschemas(self, stmt, session) -> StatementResult:
        catalog = stmt.catalog or session.catalog
        conn = self.catalogs.get(catalog)
        return StatementResult(
            [(s,) for s in conn.list_schemas()], ["Schema"], [T.VARCHAR]
        )

    def _do_showtables(self, stmt, session) -> StatementResult:
        parts = list(stmt.schema or ())
        if len(parts) == 2:
            catalog, schema = parts
        elif len(parts) == 1:
            catalog, schema = session.catalog, parts[0]
        else:
            catalog, schema = session.catalog, session.schema
        conn = self.catalogs.get(catalog)
        return StatementResult(
            [(x,) for x in conn.list_tables(schema)], ["Table"], [T.VARCHAR]
        )

    def _do_showcolumns(self, stmt, session) -> StatementResult:
        catalog, schema, table = self._qualify(stmt.table, session)
        conn = self.catalogs.get(catalog)
        ts = conn.get_table(schema, table)
        if ts is None:
            raise SemanticError(f"table not found: {catalog}.{schema}.{table}")
        rows = [(c.name, str(c.type), "", "") for c in ts.columns]
        return StatementResult(
            rows, ["Column", "Type", "Extra", "Comment"], [T.VARCHAR] * 4
        )

    # === EXPLAIN ==========================================================

    def _do_explain(self, stmt: t.Explain, session: Session) -> StatementResult:
        if getattr(stmt, "analyze", False):
            inner = stmt.statement
            if not isinstance(inner, t.Query):
                raise SemanticError("EXPLAIN ANALYZE supports queries only")
            from trino_tpu.stats import StatsCollector, render_plan_with_stats

            collector = StatsCollector()
            plan = self.plan(inner, session)
            res = self._execute_query_plan(plan, session, collector=collector)
            stages = (res.cluster_stats or {}).get("stages")
            if stages:
                # cluster execution: render the Trino-style distributed
                # plan from the per-stage stats the coordinator merged out
                # of every worker's shipped task stats
                from trino_tpu.stats import render_distributed_plan

                text = render_distributed_plan(
                    plan, res.cluster_stats, res.device_stats
                )
                wall_ms = max(
                    (s.get("elapsedMs", 0.0) for s in stages), default=0.0
                )
            else:
                text = render_plan_with_stats(plan, collector)
                if collector.fragments:
                    from trino_tpu.stats import render_fragment_stats

                    text += "\n\n" + render_fragment_stats(collector.fragments)
                if res.device_stats:
                    from trino_tpu.stats import render_device_stats

                    text += "\n\n" + render_device_stats(res.device_stats)
                ex_caps = (res.exchange_stats or {}).get("capacities")
                if isinstance(ex_caps, dict) and ex_caps:
                    from trino_tpu.stats import render_capacity_stats

                    text += "\n\n" + render_capacity_stats(ex_caps)
                if res.operator_stats:
                    from trino_tpu.stats import render_operator_stats

                    text += "\n\n" + render_operator_stats(
                        res.operator_stats
                    )
                wall_ms = collector.total_wall() * 1000
            text += (
                f"\n\npeak memory: {res.peak_memory_bytes} bytes"
                f"\ndynamic filters: {res.dynamic_filters}"
                f"\noutput rows: {len(res.rows)}"
                f"\nwall time: {wall_ms:.1f}ms"
            )
            return StatementResult(
                [(line,) for line in text.splitlines()], ["Query Plan"], [T.VARCHAR]
            )
        plan = self.plan(stmt.statement, session)
        from trino_tpu.planner.fragmenter import fragment_plan, subplan_text

        # EXPLAIN shows the distributed (fragmented) plan, like the
        # reference's default EXPLAIN output
        text = subplan_text(fragment_plan(plan))
        return StatementResult(
            [(line,) for line in text.splitlines()], ["Query Plan"], [T.VARCHAR]
        )

    # === DDL / DML ========================================================


    def _scaled_insert(
        self, conn, catalog: str, schema: str, table: str, batch, session
    ):
        """Distributed scaled writers, or None to insert locally.

        Reference: ``execution/scheduler/ScaledWriterScheduler.java`` +
        round-robin ``FIXED_ARBITRARY_DISTRIBUTION`` writer placement
        (``SystemPartitioningHandle.java:61,63``). ADR: the reference
        grows writers from runtime buffer-utilization signals; our
        exchanges prefetch, so the writer count scales statically from
        the materialized size (one writer per ~32MB, capped at the
        worker count) — same knob, compile-time signal. The coordinator
        writes the first chunk itself (file-format connectors anchor the
        table schema in the first part file), then ships the rest to
        workers over ``POST /v1/write`` as serialized pages.
        """
        if not session.get("scaled_writers"):
            return None
        if not getattr(conn, "supports_distributed_writes", False):
            return None
        if self.cluster_scheduler is None:
            return None
        nodes = self.cluster_scheduler.node_manager.active_nodes()
        if not nodes:
            return None
        from trino_tpu.memory import batch_nbytes

        batch = batch.compact()
        target = int(session.get("writer_target_bytes"))
        writers = max(1, min(len(nodes) + 1, -(-batch_nbytes(batch) // target)))
        if writers <= 1 or batch.num_rows < 2:
            return None
        from trino_tpu.exec.streaming import _slice_rows
        from trino_tpu.serde import serialize_batch
        from trino_tpu.server import auth

        rows_per = -(-batch.num_rows // writers)
        chunks = [
            _slice_rows(batch, lo, min(lo + rows_per, batch.num_rows))
            for lo in range(0, batch.num_rows, rows_per)
        ]
        if hasattr(conn, "insert_part"):
            total, anchor_part = conn.insert_part(schema, table, chunks[0])
        else:
            total, anchor_part = conn.insert(schema, table, chunks[0]), ""
        import threading
        import urllib.parse
        import urllib.request

        placements = self.cluster_scheduler.node_scheduler.select(
            nodes, len(chunks) - 1
        )
        errors: list[Exception] = []
        counts: list[int] = []
        parts: list[str] = [anchor_part]

        def write(node, chunk):
            try:
                import json as _json

                qs = urllib.parse.urlencode(
                    {"catalog": catalog, "schema": schema, "table": table}
                )
                req = urllib.request.Request(
                    f"{node.uri}/v1/write?{qs}",
                    data=serialize_batch(chunk),
                    method="POST",
                    headers=auth.headers(),
                )
                with urllib.request.urlopen(req, timeout=300) as r:
                    reply = _json.loads(r.read().decode())
                    counts.append(reply["rows"])
                    if reply.get("part"):
                        parts.append(reply["part"])
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=write, args=(n, c), daemon=True)
            for n, c in zip(placements, chunks[1:])
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        for node in placements:
            self.cluster_scheduler.node_scheduler.release(node)

        def abort(msg):
            # a failed scaled INSERT must not leave partial rows behind
            # (a retry would duplicate them): best-effort delete of every
            # part the successful writers committed — shared storage, so
            # the coordinator's connector can remove them directly
            if hasattr(conn, "delete_parts"):
                try:
                    conn.delete_parts(schema, table, parts)
                except Exception:  # noqa: BLE001
                    pass
            raise ExecutionError(msg)

        if any(t.is_alive() for t in threads):
            abort("scaled write failed: a writer task did not complete")
        if errors:
            abort(f"scaled write failed: {errors[0]}")
        if len(counts) != len(threads):
            abort(
                f"scaled write failed: {len(threads) - len(counts)} writer "
                f"tasks reported no row count"
            )
        return total + sum(counts)

    def _do_createtableasselect(
        self, stmt: t.CreateTableAsSelect, session: Session
    ) -> StatementResult:
        catalog, schema, table = self._qualify(stmt.name, session)
        self.access_control.check_can_create(session.user, catalog, schema, table)
        conn = self.catalogs.get(catalog)
        self._check_txn_writable(session, conn, catalog)
        batch, names = self._run_query_rows(stmt.query, session)
        cols = tuple(
            ColumnSchema(n.lower(), c.type) for n, c in zip(names, batch.columns)
        )
        with self._write_guard(session):
            conn.create_table(schema, table, TableSchema(table, cols))
            n = self._scaled_insert(conn, catalog, schema, table, batch, session)
            if n is None:
                n = conn.insert(schema, table, batch)
        return StatementResult(
            [], ["rows"], [T.BIGINT], update_type="CREATE TABLE", update_count=n
        )

    def _do_insertinto(self, stmt: t.InsertInto, session: Session) -> StatementResult:
        catalog, schema, table = self._qualify(stmt.name, session)
        self.access_control.check_can_insert(session.user, catalog, schema, table)
        conn = self.catalogs.get(catalog)
        self._check_txn_writable(session, conn, catalog)
        ts = conn.get_table(schema, table)
        if ts is None:
            raise SemanticError(f"table not found: {catalog}.{schema}.{table}")
        with self._write_guard(session):
            return self._do_insert_locked(stmt, session, conn, schema, table, ts)

    def _do_insert_locked(self, stmt, session, conn, schema, table, ts) -> StatementResult:
        batch, names = self._run_query_rows(stmt.query, session)
        ncols = len(stmt.columns) if stmt.columns else len(ts.columns)
        if len(batch.columns) != ncols:
            raise SemanticError(
                f"INSERT has {len(batch.columns)} columns, expected {ncols}"
            )
        if stmt.columns:
            # reorder/complete to table column order, NULL-filling the rest
            import numpy as np

            from trino_tpu.columnar import Column, Dictionary

            by_name = {c.lower(): i for i, c in enumerate(stmt.columns)}
            n = batch.num_rows
            cols = []
            for cs in ts.columns:
                if cs.name in by_name:
                    cols.append(batch.columns[by_name[cs.name]])
                else:
                    cols.append(
                        Column(
                            cs.type,
                            np.zeros(n, dtype=cs.type.storage_dtype),
                            np.zeros(n, dtype=np.bool_),
                            Dictionary([]) if T.is_string(cs.type) else None,
                        )
                    )
            batch = Batch(cols, n, batch.sel)
        n = self._scaled_insert(
            conn, self._qualify(stmt.name, session)[0], schema, table, batch,
            session,
        )
        if n is None:
            n = conn.insert(schema, table, batch)
        return StatementResult(
            [], ["rows"], [T.BIGINT], update_type="INSERT", update_count=n
        )

    def _do_droptable(self, stmt: t.DropTable, session: Session) -> StatementResult:
        catalog, schema, table = self._qualify(stmt.name, session)
        self.access_control.check_can_drop(session.user, catalog, schema, table)
        conn = self.catalogs.get(catalog)
        self._check_txn_writable(session, conn, catalog)
        if conn.get_table(schema, table) is None and stmt.if_exists:
            return StatementResult([], ["result"], [T.BOOLEAN], update_type="DROP TABLE")
        with self._write_guard(session):
            conn.drop_table(schema, table)
        return StatementResult([], ["result"], [T.BOOLEAN], update_type="DROP TABLE")

    def _do_createtable(self, stmt: t.CreateTable, session: Session) -> StatementResult:
        catalog, schema, table = self._qualify(stmt.name, session)
        self.access_control.check_can_create(session.user, catalog, schema, table)
        conn = self.catalogs.get(catalog)
        self._check_txn_writable(session, conn, catalog)
        if conn.get_table(schema, table) is not None:
            if stmt.not_exists:
                return StatementResult(
                    [], ["result"], [T.BOOLEAN], update_type="CREATE TABLE"
                )
            raise SemanticError(f"table already exists: {catalog}.{schema}.{table}")
        cols = tuple(
            ColumnSchema(n.lower(), T.parse_type(ty)) for n, ty in stmt.columns
        )
        with self._write_guard(session):
            conn.create_table(schema, table, TableSchema(table, cols))
        return StatementResult([], ["result"], [T.BOOLEAN], update_type="CREATE TABLE")

    def _do_delete(self, stmt: t.Delete, session: Session) -> StatementResult:
        """DELETE removes rows where the predicate is TRUE; rows where it is
        FALSE or NULL remain (reference DELETE semantics). Implemented as
        keep-filter + truncate + reinsert (connector-neutral)."""
        catalog, schema, table = self._qualify(stmt.name, session)
        self.access_control.check_can_insert(session.user, catalog, schema, table)
        conn = self.catalogs.get(catalog)
        self._check_txn_writable(session, conn, catalog)
        ts = conn.get_table(schema, table)
        if ts is None:
            raise SemanticError(f"table not found: {catalog}.{schema}.{table}")
        if not hasattr(conn, "truncate"):
            raise SemanticError(f"{conn.name}: DELETE not supported")
        with self._write_guard(session):
            return self._do_delete_locked(stmt, session, conn, catalog, schema, table)

    def _do_delete_locked(self, stmt, session, conn, catalog, schema, table) -> StatementResult:
        before = conn.estimate_rows(schema, table) or 0
        if stmt.where is None:
            conn.truncate(schema, table)
            return StatementResult(
                [], ["rows"], [T.BIGINT], update_type="DELETE", update_count=before
            )
        keep_pred = t.BinaryOp(
            "OR", t.UnaryOp("NOT", stmt.where), t.IsNull(stmt.where)
        )
        keep_query = t.Query(
            body=t.QuerySpec(
                select_items=(t.SelectItem(t.Star()),),
                from_=t.Table((catalog, schema, table)),
                where=keep_pred,
            )
        )
        batch, _names = self._run_query_rows(keep_query, session)
        if hasattr(conn, "replace_data"):
            # durable stores swap data atomically: truncate-then-insert
            # would lose kept rows on a crash between the two steps
            conn.replace_data(schema, table, batch)
        else:
            conn.truncate(schema, table)
            if batch.num_rows:
                conn.insert(schema, table, batch)
        return StatementResult(
            [], ["rows"], [T.BIGINT],
            update_type="DELETE", update_count=before - batch.num_rows,
        )



    def _check_txn_writable(self, session: Session, conn, catalog: str) -> None:
        """Connectors without snapshot/restore cannot participate in
        explicit transactions (reference: 'Catalog only supports writes
        using autocommit')."""
        if session.properties.get("__txn") and not hasattr(conn, "snapshot_state"):
            raise SemanticError(
                f"Catalog '{catalog}' only supports writes using autocommit"
            )

    def _write_guard(self, session: Session):
        """Single-writer enforcement for autocommit writes: inside an
        explicit transaction the session already holds the write lock;
        otherwise hold it for the duration of this statement."""
        import contextlib

        if session.properties.get("__txn"):
            return contextlib.nullcontext()
        self.transaction_manager.expire_idle()
        lock = self.transaction_manager.write_lock

        @contextlib.contextmanager
        def guard():
            if not lock.acquire(timeout=60):
                from trino_tpu.transaction import TransactionError

                raise TransactionError("timed out waiting for the write lock")
            try:
                yield
            finally:
                lock.release()

        return guard()

    # === transactions =====================================================

    def _do_starttransaction(self, stmt, session: Session) -> StatementResult:
        if session.properties.get("__txn"):
            raise SemanticError("transaction already in progress")
        txn_id = self.transaction_manager.begin()
        session.properties["__txn"] = txn_id
        return StatementResult(
            [], ["result"], [T.BOOLEAN], update_type="START TRANSACTION",
            started_transaction_id=txn_id,
        )

    def _do_commit(self, stmt, session: Session) -> StatementResult:
        txn = session.properties.get("__txn")
        if not txn:
            raise SemanticError("no transaction in progress")
        self.transaction_manager.commit(txn)
        session.properties.pop("__txn", None)
        return StatementResult(
            [], ["result"], [T.BOOLEAN], update_type="COMMIT",
            cleared_transaction=True,
        )

    def _do_rollback(self, stmt, session: Session) -> StatementResult:
        txn = session.properties.get("__txn")
        if not txn:
            raise SemanticError("no transaction in progress")
        self.transaction_manager.rollback(txn)
        session.properties.pop("__txn", None)
        return StatementResult(
            [], ["result"], [T.BOOLEAN], update_type="ROLLBACK",
            cleared_transaction=True,
        )

    # === prepared statements (reference: Session.preparedStatements) ======

    def _do_prepare(self, stmt: t.Prepare, session: Session) -> StatementResult:
        # store SQL text when available (portable across protocol requests);
        # fall back to the AST for purely in-process sessions
        session.prepared[stmt.name.lower()] = stmt.sql or stmt.statement
        return StatementResult(
            [], ["result"], [T.BOOLEAN], update_type="PREPARE",
            added_prepare=(stmt.name.lower(), stmt.sql or ""),
        )

    def _do_execute(self, stmt: t.Execute, session: Session) -> StatementResult:
        inner = session.prepared.get(stmt.name.lower())
        if inner is None:
            raise SemanticError(f"prepared statement not found: {stmt.name}")
        if isinstance(inner, str):
            inner = parse_statement(inner)
        bound = _bind_parameters(inner, stmt.parameters)
        return self._dispatch_parsed(bound, session)

    def _do_deallocate(self, stmt: t.Deallocate, session: Session) -> StatementResult:
        session.prepared.pop(stmt.name.lower(), None)
        return StatementResult(
            [], ["result"], [T.BOOLEAN], update_type="DEALLOCATE",
            deallocated_prepare=stmt.name.lower(),
        )

    def _qualify(self, name_parts, session: Session) -> tuple[str, str, str]:
        parts = list(name_parts)
        if len(parts) == 1:
            return session.catalog, session.schema, parts[0]
        if len(parts) == 2:
            return session.catalog, parts[0], parts[1]
        return parts[0], parts[1], parts[2]


def _bind_parameters(stmt: t.Node, params: tuple) -> t.Node:
    """Replace ? placeholders with the EXECUTE ... USING expressions."""
    import dataclasses as _dc

    def walk(node):
        if isinstance(node, t.Parameter):
            if node.index >= len(params):
                raise SemanticError(
                    f"no value provided for parameter {node.index + 1}"
                )
            return params[node.index]
        if _dc.is_dataclass(node) and isinstance(node, t.Node):
            changes = {}
            for f in _dc.fields(node):
                v = getattr(node, f.name)
                if isinstance(v, t.Node):
                    changes[f.name] = walk(v)
                elif isinstance(v, tuple):
                    changes[f.name] = tuple(
                        walk(x) if isinstance(x, t.Node)
                        else (
                            tuple(
                                walk(y) if isinstance(y, t.Node) else y
                                for y in x
                            )
                            if isinstance(x, tuple)
                            else x
                        )
                        for x in v
                    )
            return _dc.replace(node, **changes) if changes else node
        return node

    return walk(stmt)
