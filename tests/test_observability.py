"""Observability: spans, metrics, EXPLAIN ANALYZE, events, system tables.

Mirrors reference tests ``execution/TestEventListenerBasic.java``,
PlanPrinter stats rendering, and system connector tests; the tracing
tests mirror the OpenTelemetry span assertions in
``testing/trino-testing/.../TestingTelemetry`` usage (span parentage
across coordinator → worker HTTP dispatch).
"""

import json
import urllib.error
import urllib.request

import pytest

from trino_tpu.events import EventListener
from trino_tpu.testing import LocalQueryRunner, MultiProcessQueryRunner


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner()


class TestExplainAnalyze:
    def test_annotated_plan(self, runner):
        rows, _ = runner.execute(
            "explain analyze select o_orderpriority, count(*) "
            "from tpch.tiny.orders where o_orderkey <= 1000 group by o_orderpriority"
        )
        text = "\n".join(r[0] for r in rows)
        assert "wall:" in text and "rows:" in text
        assert "Aggregate" in text and "TableScan" in text
        assert "peak memory:" in text
        assert "wall time:" in text

    def test_explain_analyze_join_shows_all_nodes(self, runner):
        rows, _ = runner.execute(
            "explain analyze select count(*) from tpch.tiny.orders o "
            "join tpch.tiny.customer c on o.o_custkey = c.c_custkey"
        )
        text = "\n".join(r[0] for r in rows)
        assert "Join" in text
        assert text.count("wall:") >= 3


class TestEventListeners:
    def test_created_and_completed(self, runner):
        events = []

        class Recorder(EventListener):
            def query_created(self, e):
                events.append(("created", e))

            def query_completed(self, e):
                events.append(("completed", e))

        runner.engine.event_listeners.add(Recorder())
        runner.execute("select count(*) from tpch.tiny.nation")
        kinds = [k for k, _ in events]
        assert kinds == ["created", "completed"]
        done = events[1][1]
        assert done.state == "FINISHED"
        assert done.output_rows == 1
        assert done.wall_seconds >= 0

    def test_failed_query_event(self, runner):
        events = []

        class Recorder(EventListener):
            def query_completed(self, e):
                events.append(e)

        runner.engine.event_listeners.add(Recorder())
        with pytest.raises(Exception):
            runner.execute("select bad_column from tpch.tiny.nation")
        assert events and events[-1].state == "FAILED"
        assert events[-1].error_message

    def test_listener_exception_does_not_fail_query(self, runner):
        class Bad(EventListener):
            def query_created(self, e):
                raise RuntimeError("boom")

        runner.engine.event_listeners.add(Bad())
        rows, _ = runner.execute("select 1")
        assert rows == [(1,)]


class TestSystemTables:
    def test_runtime_queries(self, runner):
        runner.execute("select 123456789")
        rows, names = runner.execute(
            "select query, state from system.runtime.queries"
        )
        assert any("123456789" in r[0] for r in rows)
        assert all(r[1] in ("FINISHED", "FAILED", "RUNNING") for r in rows)

    def test_runtime_nodes(self, runner):
        rows, _ = runner.execute(
            "select node_id, coordinator from system.runtime.nodes"
        )
        assert rows and rows[0][1] is True

    def test_metadata_catalogs(self, runner):
        rows, _ = runner.execute("select catalog_name from system.metadata.catalogs")
        names = [r[0] for r in rows]
        assert "tpch" in names and "system" in names

    def test_system_tables_over_http(self):
        from trino_tpu.client import Connection
        from trino_tpu.server.http import TrinoTpuServer

        s = TrinoTpuServer().start()
        try:
            c = Connection(s.base_uri)
            c.execute("select 1")
            rows, _ = c.execute("select state from system.runtime.queries")
            assert rows
            rows, _ = c.execute("select http_uri from system.runtime.nodes")
            assert rows[0][0].startswith("http://")
        finally:
            s.stop()


class TestTracer:
    """Unit coverage for trino_tpu.obs.trace (no server)."""

    def test_noop_when_no_sink(self):
        from trino_tpu.obs.trace import NOOP_SPAN, Tracer

        t = Tracer()
        s = t.start_span("query")
        assert s is NOOP_SPAN  # shared singleton: zero alloc when dark
        s.set("k", "v")
        s.finish(status="ERROR")
        assert s.context() is None
        with t.span("child"):
            assert t.current() is None

    def test_nesting_and_sink(self):
        from trino_tpu.obs.trace import InMemorySpanSink, Tracer

        t = Tracer()
        sink = InMemorySpanSink()
        t.add_sink(sink)
        with t.span("query", trace_id="q1") as root:
            with t.span("plan"):
                pass
            t.record("compile", 12.5, attrs={"key": "k"})
        spans = {s["name"]: s for s in sink.spans_for("q1")}
        assert set(spans) == {"query", "plan", "compile"}
        assert spans["plan"]["parentId"] == root.span_id
        assert spans["compile"]["parentId"] == root.span_id
        assert spans["compile"]["durationMs"] == 12.5
        assert spans["query"]["parentId"] is None
        assert all(s["traceId"] == "q1" for s in spans.values())

    def test_error_status_on_exception(self):
        from trino_tpu.obs.trace import InMemorySpanSink, Tracer

        t = Tracer()
        sink = InMemorySpanSink()
        t.add_sink(sink)
        with pytest.raises(ValueError):
            with t.span("query", trace_id="q2"):
                raise ValueError("boom")
        (s,) = sink.spans_for("q2")
        assert s["status"] == "ERROR"
        assert "boom" in s["attrs"].get("error", "")

    def test_header_roundtrip(self):
        from trino_tpu.obs.trace import format_trace_header, parse_trace_header

        assert format_trace_header(None) is None
        assert parse_trace_header(None) is None
        assert parse_trace_header("garbage") is None
        hdr = format_trace_header(("q7", "s42"))
        assert hdr == "q7;s42"
        assert parse_trace_header(hdr) == ("q7", "s42")

    def test_explicit_parent_crosses_threads(self):
        import threading

        from trino_tpu.obs.trace import InMemorySpanSink, Tracer

        t = Tracer()
        sink = InMemorySpanSink()
        t.add_sink(sink)
        root = t.start_span("query", trace_id="q3")
        ctx = root.context()

        def worker():
            # fresh thread: no ambient context, explicit handoff required
            assert t.current() is None
            t.start_span(
                "task_execute", trace_id=ctx[0], parent_id=ctx[1]
            ).finish()

        th = threading.Thread(target=worker)
        th.start()
        th.join()
        root.finish()
        spans = {s["name"]: s for s in sink.spans_for("q3")}
        assert spans["task_execute"]["parentId"] == root.span_id


class TestMetricsRegistry:
    """Unit coverage for trino_tpu.obs.metrics (no server)."""

    def test_counter_gauge_histogram(self):
        from trino_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.counter("q_total", state="FINISHED").inc()
        reg.counter("q_total", state="FINISHED").inc(2)
        reg.counter("q_total", state="FAILED").inc()
        reg.gauge("running").set(3)
        h = reg.histogram("lat_ms", buckets=(10, 100, 1000))
        for v in (5, 50, 50, 500):
            h.observe(v)
        assert reg.counter("q_total", state="FINISHED").value == 3
        assert reg.gauge("running").value == 3
        assert h.count == 4 and h.sum == 605

    def test_type_mismatch_rejected(self):
        from trino_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.counter("x_total").inc()
        with pytest.raises(ValueError):
            reg.gauge("x_total")

    def test_prometheus_render(self):
        from trino_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.counter("q_total", state="FINISHED").inc()
        reg.histogram("lat_ms", buckets=(10, 100)).observe(42)
        text = reg.render_prometheus()
        assert "# TYPE q_total counter" in text
        assert 'q_total{state="FINISHED"} 1' in text
        assert "# TYPE lat_ms histogram" in text
        # cumulative buckets end with +Inf; _sum/_count ride along
        assert 'lat_ms_bucket{le="10"} 0' in text
        assert 'lat_ms_bucket{le="100"} 1' in text
        assert 'lat_ms_bucket{le="+Inf"} 1' in text
        assert "lat_ms_sum 42" in text
        assert "lat_ms_count 1" in text

    def test_percentile_exact(self):
        from trino_tpu.obs.metrics import percentile

        assert percentile([], 50) is None
        assert percentile([7.0], 99) == 7.0
        vals = [10.0, 20.0, 30.0, 40.0]
        assert percentile(vals, 50) == 25.0
        assert percentile(vals, 0) == 10.0
        assert percentile(vals, 100) == 40.0
        assert percentile(vals, 50) <= percentile(vals, 99)

    def test_snapshot_shape(self):
        from trino_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.counter("c_total").inc(5)
        reg.histogram("h_ms").observe(10)
        snap = reg.snapshot()
        assert snap["counters"]["c_total"] == 5
        h = next(iter(snap["histograms"].values()))
        assert h["count"] == 1 and h["sum"] == 10


class TestTracingIsInert:
    def test_rows_identical_with_tracer_on(self, runner):
        """Acceptance: tracer-enabled and disabled runs are bit-identical
        — all instrumentation is host-side, outside compiled programs."""
        from trino_tpu.obs.trace import InMemorySpanSink, get_tracer

        sql = (
            "select l_returnflag, sum(l_extendedprice * (1 - l_discount)) "
            "from tpch.tiny.lineitem group by l_returnflag "
            "order by l_returnflag"
        )
        dark, _ = runner.execute(sql)
        sink = InMemorySpanSink()
        get_tracer().add_sink(sink)
        try:
            lit, _ = runner.execute(sql)
        finally:
            get_tracer().remove_sink(sink)
        assert lit == dark
        assert sink.trace_ids()  # and it actually traced something


# --- program spans on the profiler's clock (served default path) ---------


def _span(sid, parent, name, start, end, **attrs):
    return {
        "spanId": sid, "parentId": parent, "name": name,
        "startNs": start, "endNs": end, "durationMs": (end - start) / 1e6,
        "attrs": attrs,
    }


class TestSelfTimes:
    """obs/trace.py::self_times / query_phases on hand-made timelines."""

    def test_overlapping_and_cross_thread_children(self):
        from trino_tpu.obs.trace import self_times

        ms = 1_000_000
        spans = [
            _span("root", None, "query", 0, 100 * ms),
            # two children that overlap (20..50 and 40..70: union 50 ms),
            # the second as another thread's span would be
            _span("a", "root", "plan", 20 * ms, 50 * ms),
            _span("b", "root", "task", 40 * ms, 70 * ms),
            # one that outlives its parent: only 90..100 counts
            _span("c", "root", "pull", 90 * ms, 130 * ms),
            _span("a1", "a", "inner", 25 * ms, 30 * ms),
            # no clock stamps (another process's old span): left out
            {"spanId": "old", "parentId": "root", "name": "x", "durationMs": 5.0},
        ]
        own = self_times(spans)
        assert own == {"root": 40.0, "a": 25.0, "b": 30.0, "c": 40.0, "a1": 5.0}

    def test_elided_spans_leave_their_time_with_the_kept_ancestor(self):
        from trino_tpu.obs.trace import self_times

        ms = 1_000_000
        spans = [
            _span("e", None, "execute_plan", 0, 100 * ms),
            _span("j", "e", "op:Join", 0, 100 * ms),
            _span("s", "j", "op:TableScan", 10 * ms, 40 * ms),
            _span("d", "s", "ingest.decode", 10 * ms, 35 * ms),
            # an operator below a span that is no operator: the join's child
            _span("w", "j", "wrapper", 50 * ms, 90 * ms),
            _span("f", "w", "op:Filter", 60 * ms, 80 * ms),
        ]
        own = self_times(spans, keep=lambda n: n.startswith("op:"))
        assert own == {"j": 50.0, "s": 30.0, "f": 20.0}

    def test_query_phases_names_phases_operators_and_compiles(self):
        from trino_tpu.obs.trace import query_phases

        ms = 1_000_000
        spans = [
            _span("q", None, "query", 0, 200 * ms, xlaCompiles=1, xlaCompileMs=2.5),
            _span("p", "q", "parse", 1 * ms, 3 * ms),
            _span("pl", "q", "plan", 3 * ms, 7 * ms),
            _span("o", "q", "optimize", 7 * ms, 12 * ms),
            _span("c", "q", "canonicalize", 12 * ms, 13 * ms),
            _span("e", "q", "execute_plan", 20 * ms, 120 * ms),
            _span("out", "e", "op:Output", 20 * ms, 120 * ms),
            _span("agg", "out", "op:Aggregate", 21 * ms, 119 * ms,
                  xlaCompiles=2, xlaCompileMs=30.0, xlaCacheLoads=1),
            _span("s1", "agg", "op:TableScan", 21 * ms, 41 * ms),
            _span("d", "s1", "ingest.decode", 22 * ms, 40 * ms),
            _span("r", "q", "result.pull", 121 * ms, 130 * ms),
        ]
        got = query_phases(spans)
        assert got["phaseMs"] == {
            "parse": 2.0, "plan": 4.0, "optimize": 5.0, "canonicalize": 1.0,
            "execute": 100.0, "resultPull": 9.0, "build": 0.0, "slab": 0.0,
            "devicePull": 0.0,
        }
        assert got["operatorMs"] == {
            "Output": 2.0, "Aggregate": 78.0, "TableScan": 20.0,
        }
        assert sum(got["operatorMs"].values()) == got["phaseMs"]["execute"]
        assert (got["xlaCompiles"], got["xlaCompileMs"], got["xlaCacheLoads"]) \
            == (3, 32.5, 1)


class TestProfilerBridge:
    def test_no_sink_no_annotation(self, monkeypatch):
        """Dark: the shared no-op span, and nothing of the profiler's made."""
        from trino_tpu.obs import trace

        made = []
        monkeypatch.setattr(
            trace, "TraceAnnotation", lambda name: made.append(name)
        )
        t = trace.Tracer()
        assert t.start_span("query") is trace.NOOP_SPAN
        with t.span("plan") as s:
            s.add("attempts")
        with t.activate(t.start_span("query")):
            pass
        assert made == []

    @staticmethod
    def _one_session(directory):
        """One profiler session round two nested spans, one on a second
        thread and the adoption of a span started elsewhere: the ``trino:``
        events ``name -> (line, start_ns, duration_ns)`` and the spans."""
        import glob
        import threading
        import time

        import jax
        from jax.profiler import ProfileData, TraceAnnotation

        from trino_tpu.obs.trace import InMemorySpanSink, Tracer

        t = Tracer()
        sink = InMemorySpanSink()
        t.add_sink(sink)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(directory), profiler_options=options)
        try:
            root = t.start_span("query", trace_id="qp")  # not bridged itself
            with t.activate(root):  # ... its adoption is: trino:query
                with t.span("outer"):
                    time.sleep(0.002)
                    with t.span("inner"):
                        time.sleep(0.003)

                    def worker(ctx=t.context()):
                        with TraceAnnotation("warm"):
                            pass  # a thread's first event costs the profiler extra
                        with t.span("elsewhere", trace_id=ctx[0], parent_id=ctx[1]):
                            time.sleep(0.002)

                    th = threading.Thread(target=worker)
                    th.start()
                    th.join()
            root.finish()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(
            str(directory / "plugins" / "profile" / "*" / "*.xplane.pb")
        )
        events = {}
        for plane in ProfileData.from_file(path).planes:
            for i, line in enumerate(plane.lines):  # one line per thread
                for e in line.events:
                    if e.name.startswith("trino:"):
                        assert e.name not in events, e.name
                        events[e.name] = (i, e.start_ns, e.duration_ns)
        return events, {s["name"]: s for s in sink.spans_for("qp")}

    def test_spans_are_events_on_the_profilers_clock(self, tmp_path):
        """The spans appear as ``trino:*`` events of a real profiler
        session; every event sits at the same offset from its span's own
        ``startNs`` and lasts as long, within 100 us (the best of five
        sessions: on a loaded host a thread can lose the processor between
        the two stamps, and only ever to the span's cost)."""
        worst = []
        for attempt in range(5):
            events, spans = self._one_session(tmp_path / str(attempt))
            assert set(events) == {
                "trino:query", "trino:outer", "trino:inner", "trino:elsewhere",
            }
            # the second thread's span is on another line of the same file
            assert events["trino:elsewhere"][0] != events["trino:outer"][0]
            # nesting holds on the profiler's clock as it does on the spans'
            _, o_start, o_dur = events["trino:outer"]
            _, i_start, i_dur = events["trino:inner"]
            assert o_start <= i_start and i_start + i_dur <= o_start + o_dur
            offsets, gaps = [], []
            for name in ("outer", "inner", "elsewhere"):
                _, start, dur = events["trino:" + name]
                s = spans[name]
                assert s["durationMs"] == pytest.approx(
                    (s["endNs"] - s["startNs"]) / 1e6, abs=0.001
                )
                gaps.append(abs(s["endNs"] - s["startNs"] - dur))
                offsets.append(start - s["startNs"])
            worst.append(max(max(gaps), max(offsets) - min(offsets)))
            if worst[-1] < 100_000:
                break
        assert min(worst) < 100_000, worst


_SERVED_Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity), sum(l_extendedprice),
       sum(l_extendedprice * (1 - l_discount)), avg(l_discount), count(*)
from tpch.tiny.lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus
"""
_SERVED_Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from tpch.tiny.customer, tpch.tiny.orders, tpch.tiny.lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10
"""


@pytest.fixture(scope="module")
def served():
    from trino_tpu import client
    from trino_tpu.server.http import TrinoTpuServer

    server = TrinoTpuServer(port=0).start()
    try:
        yield server, client.Connection(server.base_uri, client.ClientSession())
    finally:
        server.stop()


def _served_query(served, sql):
    """Run ``sql`` in the default session; its ``/v1/query`` record and spans."""
    server, conn = served
    rows, _ = conn.execute(sql)
    assert rows
    import time

    deadline = time.monotonic() + 5.0
    while True:
        # the root span closes just after the client has its last page
        info = [q for q in conn.list_queries() if q["query"] == sql][-1]
        spans = _get_json(
            server.base_uri, f"/v1/query/{info['queryId']}/timeline"
        )["spans"]
        if any(s["name"] == "query" for s in spans) or time.monotonic() > deadline:
            return info, spans
        time.sleep(0.01)


class TestServedDefaultPathSpans:
    @pytest.mark.parametrize("sql", [_SERVED_Q1, _SERVED_Q3], ids=["q1", "q3"])
    def test_every_phase_and_one_span_per_plan_node(self, served, sql):
        from trino_tpu.planner import plan as P
        from trino_tpu.sql.parser import parse_statement

        server, _ = served
        info, spans = _served_query(served, sql)
        names = [s["name"] for s in spans]
        for phase in ("query", "execute", "parse", "plan", "optimize",
                      "canonicalize", "execute_plan", "result.pull"):
            assert names.count(phase) == 1, (phase, names)
        by_id = {s["spanId"]: s for s in spans}
        ops = [s for s in spans if s["name"].startswith("op:")]
        # one span per executed node: no node number twice
        numbers = [s["attrs"]["node"] for s in ops]
        assert len(set(numbers)) == len(numbers)
        # ... and per node of the plan: the same node types as often (the
        # probe side of a join may run as a copy that a dynamic filter made,
        # with one more Filter where it pushed a domain)
        from trino_tpu.config import Session

        plan = server.engine.plan(parse_statement(sql), Session())
        planned, stack = [], [plan]
        while stack:
            n = stack.pop()
            planned.append(type(n).__name__)
            stack.extend(n.sources)
        assert isinstance(plan, P.Output)
        kinds = [s["name"][3:] for s in ops]
        for kind in set(planned) | set(kinds):
            extra = kinds.count(kind) - planned.count(kind)
            assert extra == 0 or (kind == "Filter" and 0 < extra <= planned.count("Join")), \
                (kind, planned, kinds)
        # operators nest as the plan does, under execute_plan
        root_ops = [s for s in ops if by_id[s["parentId"]]["name"] == "execute_plan"]
        assert [s["name"] for s in root_ops] == ["op:Output"]
        for s in ops:
            parent = by_id[s["parentId"]]
            assert parent["name"].startswith("op:") or parent["name"] == "execute_plan"
            assert parent["startNs"] <= s["startNs"] and s["endNs"] <= parent["endNs"]
        # the scan's decode is a span below its operator, not a stamp after it
        decodes = [s for s in spans if s["name"] == "ingest.decode"]
        assert len(decodes) == kinds.count("TableScan")
        assert all(by_id[s["parentId"]]["name"] == "op:TableScan" for s in decodes)
        # the operators that chose something say so
        for s in ops:
            if s["name"] == "op:Aggregate":
                # Q1's keys are two dictionary-coded columns (3 and 2 strings,
                # each with a validity mask here: (3+2) * (2+2) slots); Q3's
                # are integers and a date, which have no dictionary
                want = {"groupBy": "domain", "slots": 20} if sql is _SERVED_Q1 \
                    else {"groupBy": "sort"}
                got = {k: s["attrs"].get(k) for k in ("groupBy", "slots")}
                assert got == {"slots": None, **want}, s["attrs"]
                assert s["attrs"]["attempts"] >= 1
                assert s["attrs"]["maxGroups"] >= (got["slots"] or 0)
            if s["name"] == "op:Join":
                assert s["attrs"]["joinKind"] == "INNER"
                assert s["attrs"]["attempts"] == len(s["attrs"]["capacities"])

        stats = info["queryStats"]
        phases, operators = stats["phaseMs"], stats["operatorMs"]
        assert set(phases) == {"parse", "plan", "optimize", "canonicalize",
                               "execute", "resultPull", "build", "slab",
                               "devicePull"}
        # the compiled tier's, inside execute
        assert phases["slab"] == 0 and phases["build"] == 0
        assert phases["devicePull"] == 0
        assert set(operators) == set(kinds)
        assert sum(operators.values()) == pytest.approx(phases["execute"], rel=0.01)
        assert stats["queuedMs"] + sum(phases.values()) \
            == pytest.approx(stats["elapsedMs"], rel=0.05, abs=2.0)
        execute_plan = next(s for s in spans if s["name"] == "execute_plan")
        assert phases["execute"] == pytest.approx(execute_plan["durationMs"], abs=0.01)

    def test_compiles_counted_on_a_shapes_first_execution_only(self, served):
        # 211 rows: a shape no other test of this process has compiled for
        values = ", ".join(f"({i}, {i % 7})" for i in range(211))
        sql = (f"select k, sum(v), count(*) from (values {values}) t(v, k) "
               "group by k order by k")
        first, spans = _served_query(served, sql)
        assert first["queryStats"]["xlaCompiles"] > 0
        assert first["queryStats"]["xlaCompileMs"] > 0
        # counted where they happened: under operators, not on the root
        counted = {s["name"] for s in spans if s["attrs"].get("xlaCompiles")}
        assert counted and all(n.startswith("op:") for n in counted), counted
        second, _ = _served_query(served, sql)
        assert second["queryId"] != first["queryId"]
        assert second["queryStats"]["xlaCompiles"] == 0
        assert second["queryStats"]["xlaCompileMs"] == 0
        # the fragment programs' own counters keep their meaning
        assert second["traceCount"] == 0 and second["compileMs"] == 0.0


class TestServedCompiledPathSpans:
    """``execution_mode=distributed`` on one device, lineitem streamed
    through the slab program: the same phases as the default session, the
    slab inside ``execute``, and one ``execute_plan`` whatever falls back."""

    @pytest.fixture(scope="class")
    def compiled(self):
        from trino_tpu import client
        from trino_tpu.engine import Engine
        from trino_tpu.parallel.mesh import make_mesh
        from trino_tpu.server.http import TrinoTpuServer

        engine = Engine()
        engine.mesh = make_mesh(1)  # the slab path is the one-device path
        server = TrinoTpuServer(engine=engine, port=0).start()
        session = client.ClientSession(properties={
            "execution_mode": "distributed", "stream_scan_threshold_rows": 1,
        })
        try:
            yield server, client.Connection(server.base_uri, session)
        finally:
            server.stop()

    def test_phases_and_the_slab_span(self, compiled):
        first, cold = _served_query(compiled, _SERVED_Q1)
        sql = _SERVED_Q1.replace("'90'", "'75'")  # a literal variant: a hit
        info, spans = _served_query(compiled, sql)
        names = [s["name"] for s in spans]
        for phase in ("query", "parse", "plan", "optimize", "canonicalize",
                      "execute_plan", "result.pull", "stream.slab"):
            assert names.count(phase) == 1, (phase, names)
        by_id = {s["spanId"]: s for s in spans}

        def ancestors(s):
            while s["parentId"] in by_id:
                s = by_id[s["parentId"]]
                yield s["name"]

        execute_plan = next(s for s in spans if s["name"] == "execute_plan")
        assert execute_plan["attrs"]["executor"] == "FragmentedExecutor"
        assert "fallback" not in execute_plan["attrs"]
        slab = next(s for s in spans if s["name"] == "stream.slab")
        assert "execute_plan" in ancestors(slab)
        assert slab["attrs"]["cacheHit"] is True
        assert slab["attrs"]["params"] >= 1 and slab["attrs"]["attempt"] == 1
        assert slab["attrs"]["steps"] >= 1 and slab["attrs"]["cap"] >= 1
        assert slab["attrs"]["groups"] >= 4
        # both group keys are dictionary-coded and the slab's columns carry
        # no validity mask: (3+1) * (2+1) slots, hit or miss
        cold_slab = next(s for s in cold if s["name"] == "stream.slab")
        for s in (slab, cold_slab):
            assert (s["attrs"]["groupBy"], s["attrs"]["slots"]) == ("domain", 12)
        assert cold_slab["attrs"]["cacheHit"] is False
        # spans round the work, not stamps after it: each lies inside its
        # parent on the same clock
        pulls = [s for s in spans if s["name"] == "device_pull"]
        compiles = [s for s in cold if s["name"] == "program_compile"]
        assert len(pulls) == 1 and pulls[0]["attrs"]["attempt"] == 1
        assert compiles and all(s["attrs"]["key"] for s in compiles)
        assert not [s for s in spans if s["name"] == "program_compile"]
        for s, among in [(p, spans) for p in pulls] + [(c, cold) for c in compiles]:
            parent = {x["spanId"]: x for x in among}[s["parentId"]]
            assert parent["startNs"] <= s["startNs"] < s["endNs"] <= parent["endNs"]
        assert "execute_plan" in ancestors(pulls[0])

        stats = info["queryStats"]
        phases = stats["phaseMs"]
        assert phases["execute"] > 0 and 0 < phases["slab"] <= phases["execute"]
        assert phases["execute"] == pytest.approx(execute_plan["durationMs"], abs=0.01)
        assert phases["slab"] == pytest.approx(slab["durationMs"], abs=0.01)
        sequential = sum(v for k, v in phases.items()
                         if k not in ("build", "slab", "devicePull"))
        assert stats["queuedMs"] + sequential \
            == pytest.approx(stats["elapsedMs"], rel=0.05, abs=2.0)
        assert info["traceCount"] == 0 and info["programCacheHits"] >= 1
        assert first["traceCount"] >= 1

    def test_one_execute_plan_when_the_fused_path_falls_back(self, compiled):
        # a window is not traced into a fragment program: interpreted
        sql = ("select o_orderkey, row_number() over (order by o_orderkey) "
               "from tpch.tiny.orders where o_orderkey < 40")
        info, spans = _served_query(compiled, sql)
        plans = [s for s in spans if s["name"] == "execute_plan"]
        assert len(plans) == 1
        assert plans[0]["attrs"] == {
            "executor": "FragmentedExecutor", "fallback": "interpreter"}
        ops = [s for s in spans if s["name"].startswith("op:")]
        assert ops and not [s for s in spans if s["name"] == "stream.slab"]
        phases = info["queryStats"]["phaseMs"]
        assert phases["slab"] == 0
        assert phases["execute"] == pytest.approx(plans[0]["durationMs"], abs=0.01)


# --- distributed span/metrics tests (one shared 2-node cluster) ----------


def _get_json(uri: str, path: str):
    from trino_tpu.server import auth

    req = urllib.request.Request(f"{uri}{path}", headers=auth.headers())
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read().decode())


def _get_text(uri: str, path: str) -> str:
    from trino_tpu.server import auth

    req = urllib.request.Request(f"{uri}{path}", headers=auth.headers())
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.read().decode()


def _query_id_for(coordinator_uri: str, sql_fragment: str) -> str:
    qs = [
        q
        for q in _get_json(coordinator_uri, "/v1/query")
        if sql_fragment in q["query"]
    ]
    assert qs, f"no query matching {sql_fragment!r} on the coordinator"
    return qs[-1]["queryId"]


def _cluster_timeline(cluster, qid: str) -> list:
    """Union of the coordinator's and every worker's span dump for one
    trace — the cross-process view a real backend would assemble."""
    spans = list(_get_json(
        cluster.coordinator_uri, f"/v1/query/{qid}/timeline"
    )["spans"])
    for uri in cluster.worker_uris:
        try:
            spans.extend(_get_json(uri, f"/v1/query/{qid}/timeline")["spans"])
        except urllib.error.HTTPError:
            pass  # worker saw no tasks for this query
    return spans


@pytest.fixture(scope="module")
def obs_cluster():
    with MultiProcessQueryRunner(n_workers=2) as runner:
        yield runner


Q5_MARKER = "revenue"


def _ensure_q5(cluster) -> None:
    """The tests below read what a distributed query left on the cluster
    (counters, stage histograms, retained worker tasks, Q5's record). The
    cluster is one per xdist worker, and ``--dist load`` may hand a worker
    these tests without ``test_q5_span_tree_connected``: then run Q5 here."""
    from trino_tpu.benchmarks.tpch import queries

    listed = _get_json(cluster.coordinator_uri, "/v1/query")
    if not any(Q5_MARKER in q["query"] for q in listed):
        cluster.execute(queries("tpch.tiny")[5])


class TestDistributedSpans:
    def test_q5_span_tree_connected(self, obs_cluster):
        """TPC-H Q5 on a 2-node cluster yields one connected span tree:
        worker task_execute spans parent (via X-Trino-Trace) to the
        coordinator's task_attempt spans, which parent to stage spans,
        which reach the query root."""
        from trino_tpu.benchmarks.tpch import queries

        rows, _ = obs_cluster.execute(queries("tpch.tiny")[5])
        assert rows
        qid = _query_id_for(obs_cluster.coordinator_uri, Q5_MARKER)
        import time

        deadline = time.monotonic() + 5.0
        while True:
            # the root span closes just after the client has its last page
            spans = _cluster_timeline(obs_cluster, qid)
            roots = [s for s in spans if s["parentId"] is None]
            if roots or time.monotonic() > deadline:
                break
            time.sleep(0.01)
        assert all(s["traceId"] == qid for s in spans)
        by_id = {s["spanId"]: s for s in spans}
        assert len(roots) == 1 and roots[0]["name"] == "query"

        def depth(s, seen=50):
            while s["parentId"] is not None and seen:
                s = by_id[s["parentId"]]  # KeyError == disconnected tree
                seen -= 1
            return s

        # every span chains up to the single root — no orphans anywhere
        for s in spans:
            assert depth(s)["spanId"] == roots[0]["spanId"]

        names = {s["name"] for s in spans}
        assert {"query", "execute", "plan", "optimize", "fragment",
                "stage", "task_attempt"} <= names
        # worker-side spans joined the same tree across the HTTP gap
        execs = [s for s in spans if s["name"] == "task_execute"]
        assert execs
        attempt_ids = {
            s["spanId"] for s in spans if s["name"] == "task_attempt"
        }
        assert all(s["parentId"] in attempt_ids for s in execs)
        # multi-stage query: a join tree fans out over both workers
        stages = [s for s in spans if s["name"] == "stage"]
        assert len(stages) >= 2
        workers = {
            s["attrs"].get("worker")
            for s in spans
            if s["name"] == "task_attempt"
        }
        assert len(workers) == 2

    def test_metrics_scrape_format(self, obs_cluster):
        _ensure_q5(obs_cluster)
        text = _get_text(obs_cluster.coordinator_uri, "/v1/metrics")
        assert "# TYPE trino_tpu_queries_total counter" in text
        assert "# TYPE trino_tpu_query_elapsed_ms histogram" in text
        assert 'trino_tpu_queries_total{state="FINISHED"}' in text
        # per-stage elapsed histograms from the coordinator rollup
        assert "# TYPE trino_tpu_stage_elapsed_ms histogram" in text
        assert 'trino_tpu_stage_elapsed_ms_bucket{' in text
        assert 'le="+Inf"' in text
        assert "trino_tpu_task_elapsed_ms_count" in text

    def test_task_histogram_counts_consistent(self, obs_cluster):
        """Every FINISHED attempt is observed exactly once: the per-stage
        task-elapsed histogram total equals the FINISHED task counter."""
        _ensure_q5(obs_cluster)
        snap = _get_json(
            obs_cluster.coordinator_uri, "/v1/metrics?format=json"
        )
        finished = sum(
            v
            for k, v in snap["counters"].items()
            if k.startswith("trino_tpu_tasks_total")
            and 'state="FINISHED"' in k
        )
        observed = sum(
            h["count"]
            for k, h in snap["histograms"].items()
            if k.startswith("trino_tpu_task_elapsed_ms")
        )
        assert finished > 0
        assert observed == finished

    def test_query_stats_stage_percentiles(self, obs_cluster):
        _ensure_q5(obs_cluster)
        qid = _query_id_for(obs_cluster.coordinator_uri, Q5_MARKER)
        info = _get_json(obs_cluster.coordinator_uri, f"/v1/query/{qid}")
        stats = info["queryStats"]
        assert stats["elapsedMs"] >= 0 and stats["queuedMs"] >= 0
        stages = stats["stages"]
        assert stages
        multi = [s for s in stages if s.get("tasks", 0) >= 2]
        assert multi, "expected a fan-out stage on a 2-worker cluster"
        for s in multi:
            te = s["taskElapsedMs"]
            assert te["count"] == s["tasks"]
            assert 0 <= te["p50"] <= te["p99"] <= te["max"]

    def test_timeline_404_for_unknown_query(self, obs_cluster):
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get_json(
                obs_cluster.coordinator_uri, "/v1/query/nope_xyz/timeline"
            )
        assert ei.value.code == 404

    @pytest.mark.faults
    def test_retry_spans_under_task_policy(self, obs_cluster):
        """Chaos: with 30% task-crash injection the timeline shows the
        retried dispatch attempts (attempt >= 2, retry flag) and the
        retries counter moves."""
        before = _get_json(
            obs_cluster.coordinator_uri, "/v1/metrics?format=json"
        )["counters"].get("trino_tpu_task_retries_total", 0)
        rows, _ = obs_cluster.execute(
            "select count(*) as chaos_probe from lineitem",
            session_properties={
                "retry_policy": "TASK",
                "task_retry_attempts": 8,
                "fault_injection_seed": 3,
                "fault_task_crash_p": 0.3,
                "retry_initial_delay_ms": 20,
                "retry_max_delay_ms": 200,
            },
        )
        assert rows
        qid = _query_id_for(obs_cluster.coordinator_uri, "chaos_probe")
        spans = _cluster_timeline(obs_cluster, qid)
        retries = [
            s
            for s in spans
            if s["name"] == "task_attempt"
            and s["attrs"].get("attempt", 1) >= 2
        ]
        assert retries, "seed 3 must produce at least one retried attempt"
        assert all(s["attrs"].get("retry") for s in retries)
        # first attempts closed as failed, retried attempts as OK
        info = _get_json(obs_cluster.coordinator_uri, f"/v1/query/{qid}")
        assert info["taskRetries"] >= 1
        after = _get_json(
            obs_cluster.coordinator_uri, "/v1/metrics?format=json"
        )["counters"].get("trino_tpu_task_retries_total", 0)
        assert after - before >= 1


class TestDistributedDeviceStats:
    """Coordinator-merged worker stats: distributed EXPLAIN ANALYZE and
    the per-query deviceStats rollup (device profiler tentpole; local
    coverage lives in tests/test_device_profiler.py)."""

    DEA_MARKER = "dea_probe"

    def test_distributed_explain_analyze(self, obs_cluster):
        rows, _ = obs_cluster.execute(
            "explain analyze select o_orderpriority as dea_probe, count(*)"
            " from orders group by o_orderpriority"
        )
        text = "\n".join(r[0] for r in rows)
        assert "Distributed plan:" in text
        assert "Stages (stats merged from worker tasks):" in text
        assert "Stage " in text and "[tasks: " in text
        # merged per-stage output rows and task-wall percentiles
        assert "output rows: " in text
        assert "task wall p50/p99/max:" in text
        assert "wall time:" in text

    def test_stage_stats_merged_from_both_workers(self, obs_cluster):
        rows, _ = obs_cluster.execute(
            f"select o_orderpriority as {self.DEA_MARKER}, count(*) as c"
            " from orders group by o_orderpriority",
            # this test is about merging one stage's stats across BOTH
            # workers' tasks; pipeline fusion would collapse the chain
            # into a single fused task with no fan-out
            session_properties={"pipeline_fusion": False},
        )
        assert rows
        qid = _query_id_for(obs_cluster.coordinator_uri, self.DEA_MARKER)
        info = _get_json(obs_cluster.coordinator_uri, f"/v1/query/{qid}")
        stages = info["queryStats"]["stages"]
        fanout = [s for s in stages if s.get("tasks", 0) >= 2]
        assert fanout, "expected a 2-task stage on a 2-worker cluster"
        # rows were summed across BOTH workers' FINISHED tasks; the scan
        # stage's merged input covers the whole table (15k orders split
        # between the workers — one task alone cannot reach it)
        assert any(s.get("rows") for s in stages)
        assert sum(s.get("inputRows") or 0 for s in stages) >= 15000
        # per-fragment XLA cost analysis shipped back in task stats
        flops_stages = [s for s in stages if s.get("flops")]
        assert flops_stages, "no stage carried device cost analysis"
        for s in flops_stages:
            assert s["flops"] > 0
            assert s.get("peakHbmBytes", 0) >= 0
        # query-level rollup rode the same merge
        ds = info["deviceStats"]
        assert ds and ds["programs_profiled"] >= 1
        assert ds.get("total_flops", 0) > 0
        assert any(
            label.startswith("frag:") for label in ds["programs"]
        )

    def test_worker_runtime_tasks_table(self, obs_cluster):
        """system.runtime.tasks on a worker lists its (retained) tasks —
        the SQL view of the registry /v1/task serves."""
        from trino_tpu.client import Connection

        _ensure_q5(obs_cluster)  # (a one-table count alone may run on the coordinator)
        obs_cluster.execute(
            "select count(*) as tasks_probe from orders"
        )
        found = []
        for uri in obs_cluster.worker_uris:
            rows, _ = Connection(uri).execute(
                "select task_id, state, fragment, elapsed_ms"
                " from system.runtime.tasks"
            )
            found.extend(rows)
        assert found, "workers retained no tasks"
        assert all(r[1] in ("FINISHED", "FAILED", "RUNNING",
                            "CANCELED", "CANCELED_SPECULATIVE")
                   for r in found)
        assert all(r[3] >= 0 for r in found)


class TestFusedExplainAnalyze:
    def test_fragment_stats_without_fallback(self):
        """EXPLAIN ANALYZE on a fused query reports per-fragment compile/
        run stats instead of switching to the interpreter (VERDICT r2)."""
        from trino_tpu.testing import DistributedQueryRunner

        r = DistributedQueryRunner()
        rows, _ = r.execute(
            "explain analyze select l_returnflag, sum(l_quantity)"
            " from lineitem group by l_returnflag"
        )
        text = "\n".join(row[0] for row in rows)
        assert "Fragments (fused single-program execution):" in text
        assert "mode=fused" in text or "mode=streamed" in text
        assert "compile_attempts=" in text or "wall=" in text
