"""Streaming scan execution (Driver-loop analog): scan→agg fragments run
as a bounded chunk loop with carried accumulators; results must match the
materializing interpreter bit-for-bit.

Reference: ``operator/Driver.java:355-392`` (bounded pages through the
pipeline); here the whole chunk pipeline is one compiled step program.
"""

import pytest

from trino_tpu.testing import DistributedQueryRunner, LocalQueryRunner


@pytest.fixture(scope="module")
def local():
    return LocalQueryRunner()


@pytest.fixture(scope="module")
def streaming():
    r = DistributedQueryRunner()
    # force tiny tables onto the streaming path with multiple small chunks;
    # the slab loop too, whose step is a device chunk of every shard (left at
    # the session's 2,097,152 rows a shard, a Q5 step over eight host devices
    # held 48 GB, and a worker of the parallel suite was killed for it)
    r.session.set("stream_scan_threshold_rows", 1000)
    r.session.set("stream_chunk_rows", 4096)
    r.session.set("stream_device_chunk_rows", 4096)
    return r


def check(streaming, local, sql):
    got, _ = streaming.execute(sql)
    want, _ = local.execute(sql)
    assert got == want, f"stream != local for {sql}\n{got[:4]}\n{want[:4]}"


class TestStreamingAggregation:
    def test_grouped_with_all_kinds(self, streaming, local):
        check(
            streaming,
            local,
            """select l_returnflag, l_linestatus, sum(l_quantity), count(*),
                      avg(l_extendedprice), min(l_discount), max(l_tax)
               from lineitem group by l_returnflag, l_linestatus
               order by l_returnflag, l_linestatus""",
        )

    def test_global_agg(self, streaming, local):
        check(
            streaming,
            local,
            "select count(*), sum(l_quantity), min(l_shipdate),"
            " max(l_shipdate) from lineitem",
        )

    def test_filtered_projection_q6(self, streaming, local):
        check(
            streaming,
            local,
            """select sum(l_extendedprice * l_discount) from lineitem
               where l_shipdate >= date '1994-01-01'
                 and l_shipdate < date '1995-01-01'
                 and l_discount between decimal '0.05' and decimal '0.07'
                 and l_quantity < 24""",
        )

    def test_partial_final_split_across_exchange(self, streaming, local):
        # grouped agg whose partial side streams, final side combines
        check(
            streaming,
            local,
            """select o_orderpriority, count(*) from orders
               where o_orderdate >= date '1993-07-01'
               group by o_orderpriority order by o_orderpriority""",
        )

    def test_string_minmax_across_chunks(self, streaming, local):
        check(
            streaming,
            local,
            """select l_shipmode, min(l_shipinstruct), max(l_shipinstruct)
               from lineitem group by l_shipmode order by l_shipmode""",
        )

    def test_capacity_overflow_retry(self, streaming, local):
        """Per-shard distinct keys (~60175/8 ≈ 7.5k) exceed a tiny initial
        group budget, so the overflow protocol (deferred flag check +
        budget growth + rerun) MUST fire and converge to correct results.
        The stream must run more than once, with growing budgets."""
        from trino_tpu.exec import streaming as S

        budgets: list[int] = []
        orig = S.StreamingAggregator.run

        def counting_run(self):
            budgets.append(self.G)
            return orig(self)

        S.StreamingAggregator.run = counting_run
        streaming.session.set("stream_group_budget", 64)
        try:
            check(
                streaming,
                local,
                "select o_custkey, count(*) from orders"
                " group by o_custkey order by o_custkey limit 13",
            )
        finally:
            streaming.session.set("stream_group_budget", 1 << 12)
            S.StreamingAggregator.run = orig
        assert len(budgets) >= 2, "overflow retry path never exercised"
        assert budgets[-1] > budgets[0], f"group budget never grew: {budgets}"

    def test_streaming_actually_engaged(self, streaming):
        """The plan shape must stream (not fall back): watch the step
        count via the chunk source."""
        from trino_tpu.exec import streaming as S
        from trino_tpu.planner import plan as P
        from trino_tpu.planner.fragmenter import fragment_plan

        plan = streaming.plan(
            "select l_returnflag, sum(l_quantity) from lineitem"
            " group by l_returnflag"
        )
        sub = fragment_plan(plan)
        chains = [
            S.streamable_chain(f.root) for f in sub.all_fragments()
        ]
        assert any(c is not None for c in chains)


class TestStreamingSplitDictionaries:
    """Per-split string dictionaries must not corrupt streamed group keys
    or min/max state (advisor round-3 high finding): every split gets its
    own Dictionary, so the stream remaps codes onto one running dictionary
    (or falls back when the trace embedded rank tables that growth would
    invalidate). Both paths must equal the interpreter."""

    @pytest.fixture(scope="class")
    def split_streaming(self):
        from trino_tpu.connectors.tpch import TpchConnector

        r = DistributedQueryRunner()
        r.engine.catalogs.register("tpchsplit", TpchConnector(split_rows=2048))
        r.session.set("stream_scan_threshold_rows", 1000)
        r.session.set("stream_chunk_rows", 4096)
        return r

    @pytest.fixture(scope="class")
    def split_local(self, split_streaming):
        # share the engine so both runners see the same generated data
        r = LocalQueryRunner(engine=split_streaming.engine)
        return r

    def test_group_by_string_across_splits(self, split_streaming, split_local):
        sql = """select o_clerk, count(*), sum(o_totalprice)
                 from tpchsplit.tiny.orders group by o_clerk
                 order by o_clerk limit 20"""
        got, _ = split_streaming.execute(sql)
        want, _ = split_local.execute(sql)
        assert got == want

    def test_minmax_string_across_splits(self, split_streaming, split_local):
        sql = """select o_orderpriority, min(o_comment), max(o_comment)
                 from tpchsplit.tiny.orders group by o_orderpriority
                 order by o_orderpriority"""
        got, _ = split_streaming.execute(sql)
        want, _ = split_local.execute(sql)
        assert got == want


class TestStreamingJoins:
    """Probe-side streaming through joins: build sides materialize once,
    probe chunks flow through join→agg inside the compiled step
    (reference: HashBuilderOperator/LookupJoinOperator build-once,
    probe-streamed). Results must equal the interpreter, and the
    streamed-join path must actually engage."""

    @pytest.fixture()
    def engaged(self, monkeypatch):
        from trino_tpu.exec import streaming as S

        counts = {"join_streams": 0}
        orig = S.StreamingAggregator.run

        def counting_run(self):
            if self.build_roots:
                counts["join_streams"] += 1
            return orig(self)

        monkeypatch.setattr(S.StreamingAggregator, "run", counting_run)
        return counts

    def check_join(self, streaming, local, engaged, sql):
        got, _ = streaming.execute(sql)
        want, _ = local.execute(sql)
        assert got == want, f"stream != local for {sql}\n{got[:4]}\n{want[:4]}"
        assert engaged["join_streams"] >= 1, "join stream never engaged"

    def test_q3_shape(self, streaming, local, engaged):
        self.check_join(
            streaming, local, engaged,
            """select l_orderkey, sum(l_extendedprice * (1 - l_discount)),
                      o_orderdate, o_shippriority
               from customer, orders, lineitem
               where c_mktsegment = 'BUILDING'
                 and c_custkey = o_custkey and l_orderkey = o_orderkey
                 and o_orderdate < date '1995-03-15'
                 and l_shipdate > date '1995-03-15'
               group by l_orderkey, o_orderdate, o_shippriority
               order by 2 desc, o_orderdate limit 10""",
        )

    def test_q10_shape(self, streaming, local, engaged):
        self.check_join(
            streaming, local, engaged,
            """select c_custkey, c_name,
                      sum(l_extendedprice * (1 - l_discount)) as revenue
               from customer, orders, lineitem, nation
               where c_custkey = o_custkey and l_orderkey = o_orderkey
                 and o_orderdate >= date '1993-10-01'
                 and o_orderdate < date '1994-01-01'
                 and l_returnflag = 'R' and c_nationkey = n_nationkey
               group by c_custkey, c_name
               order by revenue desc limit 20""",
        )

    def test_left_join_stream(self, streaming, local, engaged):
        # NOTE: no ON-filter — the fragmenter still gathers filtered
        # LEFT joins (census gap, tests/test_tpch_fused.py Q13/Q21)
        self.check_join(
            streaming, local, engaged,
            """select n_name, count(c_custkey), count(*)
               from customer left join nation on c_nationkey = n_nationkey
               group by n_name order by n_name""",
        )

    def test_q5_shape_multi_join_spine(self, streaming, local, engaged):
        # several joins stacked on the probe spine: every build side
        # materializes once, lineitem streams through all of them
        self.check_join(
            streaming, local, engaged,
            """select n_name, sum(l_extendedprice * (1 - l_discount))
               from customer, orders, lineitem, supplier, nation, region
               where c_custkey = o_custkey and l_orderkey = o_orderkey
                 and l_suppkey = s_suppkey and c_nationkey = s_nationkey
                 and s_nationkey = n_nationkey and n_regionkey = r_regionkey
                 and r_name = 'ASIA'
                 and o_orderdate >= date '1994-01-01'
                 and o_orderdate < date '1995-01-01'
               group by n_name order by 2 desc""",
        )


class TestDeviceSlabStreaming:
    """Single-device runners exercise the HBM-slab fast path (the whole
    chunk loop as one fori_loop program with in-program dynamic_slice).
    Multi-device meshes take the host chunk path, so this class pins the
    mesh to one device the way the real chip runs."""

    @pytest.fixture(scope="class")
    def slab_runner(self):
        r = DistributedQueryRunner(n_devices=1)
        r.session.set("stream_scan_threshold_rows", 1000)
        r.session.set("stream_device_chunk_rows", 4096)
        return r

    @pytest.fixture(scope="class")
    def slab_local(self, slab_runner):
        return LocalQueryRunner(engine=slab_runner.engine)

    def _assert_slab_engaged(self, monkeypatch):
        from trino_tpu.exec import streaming as S

        counts = {"slab": 0}
        orig = S.StreamingAggregator._make_slab_program

        def counting(self, meta, cap, chunk_cols=None):
            counts["slab"] += 1
            return orig(self, meta, cap, chunk_cols)

        monkeypatch.setattr(
            S.StreamingAggregator, "_make_slab_program", counting
        )
        return counts

    def test_tpch_slab_group_by(self, slab_runner, slab_local, monkeypatch):
        counts = self._assert_slab_engaged(monkeypatch)
        sql = """select l_returnflag, l_linestatus, sum(l_quantity),
                        count(*), min(l_discount)
                 from lineitem group by l_returnflag, l_linestatus
                 order by l_returnflag, l_linestatus"""
        got, _ = slab_runner.execute(sql)
        want, _ = slab_local.execute(sql)
        assert got == want
        assert counts["slab"] >= 1, "device slab path never engaged"

    def test_tpch_slab_join_stream(self, slab_runner, slab_local):
        sql = """select o_orderpriority, sum(l_quantity), count(*)
                 from lineitem, orders where l_orderkey = o_orderkey
                 group by o_orderpriority order by o_orderpriority"""
        got, _ = slab_runner.execute(sql)
        want, _ = slab_local.execute(sql)
        assert got == want

    def test_memory_slab_repeated_queries(self, slab_runner, slab_local):
        import numpy as np

        from trino_tpu import types as T
        from trino_tpu.columnar import Batch, Column
        from trino_tpu.connectors.api import ColumnSchema, TableSchema

        mem = slab_runner.catalogs.get("memory")
        rng = np.random.default_rng(3)
        n = 50_000
        mem.create_table(
            "default", "slabbed",
            TableSchema("slabbed", (ColumnSchema("k", T.BIGINT),
                                    ColumnSchema("v", T.BIGINT))),
        )
        mem.insert("default", "slabbed", Batch(
            [Column(T.BIGINT, rng.integers(0, 97, n).astype(np.int64)),
             Column(T.BIGINT, rng.integers(0, 1000, n).astype(np.int64))], n))
        sql = ("select k, sum(v), count(*) from memory.default.slabbed"
               " group by k order by k")
        first, _ = slab_runner.execute(sql)
        second, _ = slab_runner.execute(sql)  # cached program + slab
        want, _ = slab_local.execute(sql)
        assert first == second == want


# --- literals reach the stored slab program as arguments ---------------------

Q1 = """select l_returnflag, l_linestatus, sum(l_quantity),
               sum(l_extendedprice), sum(l_extendedprice * (1 - l_discount)),
               sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
               avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
        from lineitem
        where l_shipdate <= date '1998-12-01' - interval '{0}' day
        group by l_returnflag, l_linestatus
        order by l_returnflag, l_linestatus"""
Q6 = """select sum(l_extendedprice * l_discount) from lineitem
        where l_shipdate >= date '{0}-01-01' and l_shipdate < date '{1}-01-01'
          and l_discount between {2} - 0.01 and {2} + 0.01
          and l_quantity < {3}"""
# orders is the build side of the streamed lineitem probe: a broadcast
# fragment of its own, whose filter takes its literal through __params__
# as fragment programs always did; the probe's literal is the step's
JOIN = """select o_orderpriority, sum(l_quantity), count(*)
          from lineitem, orders
          where l_orderkey = o_orderkey and o_totalprice < {0}
            and l_quantity < {1}
          group by o_orderpriority order by o_orderpriority"""
VARIANTS = {
    "q1": (Q1, [(90,), (120,), (60,), (97,)]),
    "q6": (Q6, [(1994, 1995, "0.06", 24), (1995, 1996, "0.04", 25),
                (1993, 1994, "0.08", 24)]),
    # same digits, so one fingerprint; the second varies the build side alone
    "join": (JOIN, [("150000.00", 30), ("190000.00", 30), ("250000.00", 11)]),
}


class TestLiteralVariantsThroughTheSlab:
    """One engine, the program cache on, lineitem streamed through
    ``_run_device_slab`` (one device, as on the chip): every execution after
    a shape's first is a literal variant of a cached plan and has to answer
    for ITS literals out of the stored program, tracing nothing."""

    @pytest.fixture(scope="class")
    def compiled(self):
        from trino_tpu.exec import streaming as S

        r = DistributedQueryRunner(n_devices=1)
        r.session.set("stream_scan_threshold_rows", 1)
        baked = DistributedQueryRunner(n_devices=1)
        baked.engine = r.engine
        baked.session.set("stream_scan_threshold_rows", 1)
        baked.session.set("program_cache", False)
        calls = {"slab": 0, "joins": 0}
        orig = S.StreamingAggregator._slab_attempt

        def counting(self, *args):
            calls["slab"] += 1
            calls["joins"] += bool(self.build_roots)
            return orig(self, *args)

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(S.StreamingAggregator, "_slab_attempt", counting)
            yield {"cached": r, "baked": baked, "calls": calls, "seen": {},
                   "local": LocalQueryRunner(engine=r.engine)}

    @pytest.mark.parametrize(
        "shape,variant",
        [(s, i) for s, (_, vs) in VARIANTS.items() for i in range(len(vs))],
    )
    def test_answer_is_this_literals(self, compiled, shape, variant):
        template, variants = VARIANTS[shape]
        sql = template.format(*variants[variant])
        r = compiled["cached"]
        before = dict(compiled["calls"])
        res = r.engine.execute_statement(sql, r.session)
        assert compiled["calls"]["slab"] == before["slab"] + 1, "never streamed"
        want, _ = compiled["local"].execute(sql)
        baked, _ = compiled["baked"].execute(sql)
        assert want and res.rows == want, (shape, variants[variant])
        assert baked == want
        nth = compiled["seen"].get(shape, 0)
        compiled["seen"][shape] = nth + 1
        if shape == "join":
            # (the cached session's run and the baked one's)
            assert compiled["calls"]["joins"] == before["joins"] + 2
        if nth:
            # the join's step too: its build sides are arguments (C10), so
            # the second variant's other build rides the stored program
            assert res.trace_count == 0 and res.program_cache_misses == 0
            assert res.program_cache_hits >= 1

    def test_another_plan_gets_its_own_program(self, compiled):
        """Same fragment id, same ordinal, another aggregate: the store is
        the fingerprint's, so the content key cannot meet a stranger."""
        r = compiled["cached"]
        sql = ("select l_returnflag, l_linestatus, max(l_quantity), count(*)"
               " from lineitem where l_shipdate <= date '1998-09-02'"
               " group by l_returnflag, l_linestatus order by 1, 2")
        res = r.engine.execute_statement(sql, r.session)
        assert res.trace_count >= 1
        assert res.rows == compiled["local"].execute(sql)[0]


@pytest.mark.parametrize("qn", [6, 1])
def test_chip_smoke_variant_is_another_literal_of_the_same_plan(qn, slab_spans):
    """``chip_smoke.VARIANTS`` rewrites a query's text by ``str.replace`` and
    dies on the chip if the text has drifted; no test read it. Here, at
    ``tpch.tiny``: the rewritten text differs, has the same plan fingerprint,
    is answered out of the first literals' programs with nothing traced, and
    the default session gives the same rows for it."""
    # importing it needs no chip, and nothing called here does
    import chip_smoke as smoke
    from trino_tpu.benchmarks.tpch import queries
    from trino_tpu.planner.canonicalize import canonicalize_plan
    from trino_tpu.sql.parser import parse_statement

    assert set(smoke.VARIANTS) == {6, 1} == set(smoke.DISTRIBUTED_QUERIES)
    base = queries("tpch.tiny")[qn]
    other = smoke.variant(qn, base)
    assert other != base
    r = DistributedQueryRunner(n_devices=1)
    r.session.set("stream_scan_threshold_rows", 1)

    def fingerprint(sql):
        plan = r.engine.plan(parse_statement(sql), r.session)
        _, params, fp = canonicalize_plan(plan, r.session, 1)
        return fp, [v for v, _ in params]

    (fp, literals), (fp_other, literals_other) = fingerprint(base), fingerprint(other)
    assert fp is not None and fp == fp_other
    assert literals != literals_other
    first = r.engine.execute_statement(base, r.session)
    varied = r.engine.execute_statement(other, r.session)
    assert first.trace_count >= 1
    assert varied.trace_count == 0 and varied.program_cache_hits >= 1
    assert [a["cacheHit"] for a in slab_spans] == [False, True]
    assert varied.rows and varied.rows != first.rows
    assert varied.rows == LocalQueryRunner(engine=r.engine).execute(other)[0]


# --- the group-by that does not sort, where the slab's dictionaries say so ----


def _sorted_rows(jaxpr):
    """Leading dimension of the first operand of every ``sort`` in a jaxpr,
    sub-jaxprs (the loop body, nested jits, shard_map) included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            yield eqn.invars[0].aval.shape[0]
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _sorted_rows(sub)


class _SlabSpans:
    """A span sink that keeps the ``stream.slab`` spans."""

    def __init__(self):
        self.attrs = []

    def record(self, span):
        if span.name == "stream.slab":
            self.attrs.append(dict(span.attrs))


@pytest.fixture()
def slab_spans():
    from trino_tpu.obs.trace import get_tracer

    sink = _SlabSpans()
    get_tracer().add_sink(sink)
    try:
        yield sink.attrs
    finally:
        get_tracer().remove_sink(sink)


class TestDomainGroupByThroughTheSlab:
    @pytest.fixture(scope="class")
    def runner(self):
        r = DistributedQueryRunner(n_devices=1)
        r.session.set("stream_scan_threshold_rows", 1)
        r.session.set("stream_device_chunk_rows", 32768)  # two steps of tiny
        # ... which stay two on the sort path: 16 x 2,048 groups is no more
        # than the width (``slab_step_rows``)
        r.session.set("stream_group_budget", 2048)
        return r

    def test_q1_takes_the_domain_path_and_its_program_sorts_no_chunk(
        self, runner, slab_spans, monkeypatch
    ):
        """Q1 through the slab path: three DELTAs equal the interpreter, the
        span says which way the rows were grouped, and the stored program
        sorts neither a chunk's rows nor the merge's."""
        import jax
        import numpy as np

        from trino_tpu.exec import streaming as S

        stored = []
        orig = S.StreamingAggregator._slab_attempt

        def keeping(self, programs, slab, chunk_cols, num_rows, cap, span,
                    meta=None):
            res = orig(self, programs, slab, chunk_cols, num_rows, cap, span,
                       meta)
            program, meta, _ = programs[("slab", self.site, self.G, cap, False, 1)]
            rows = np.int64(num_rows[0])
            steps = np.int32((rows + cap - 1) // cap)
            stored.append((cap, self.G, jax.make_jaxpr(program)(
                self._init_state(meta), slab, steps, rows, self.params, ())))
            return res

        monkeypatch.setattr(S.StreamingAggregator, "_slab_attempt", keeping)
        local = LocalQueryRunner(engine=runner.engine)
        for delta in (90, 60, 120):
            sql = Q1.format(delta)
            res = runner.engine.execute_statement(sql, runner.session)
            assert res.rows and res.rows == local.execute(sql)[0], delta
        assert [a["cacheHit"] for a in slab_spans] == [False, True, True]
        for a in slab_spans:
            # l_returnflag has 3 strings, l_linestatus 2; no validity masks
            assert (a["groupBy"], a["slots"]) == ("domain", 12), a
            assert a["steps"] == 2 and a["cap"] == 32768
        for cap, groups, jaxpr in stored:
            rows = set(_sorted_rows(jaxpr.jaxpr))
            assert cap not in rows and 2 * groups not in rows, rows

    def test_a_table_written_to_gets_a_program_with_its_new_dictionary(
        self, runner, slab_spans
    ):
        """The domain is a static of the stored program and the program's
        key does not carry it: the store it lives in is the one of the
        table's data version (``Engine._query_cache_entry``), so a write
        that grows the dictionary reaches a store with no program in it."""
        from trino_tpu import types as T
        from trino_tpu.columnar import Batch, Column
        from trino_tpu.connectors.api import ColumnSchema, TableSchema

        mem = runner.catalogs.get("memory")
        mem.create_table(
            "default", "flags",
            TableSchema("flags", (ColumnSchema("f", T.VARCHAR),
                                  ColumnSchema("v", T.BIGINT))),
        )

        def insert(flags, values):
            mem.insert("default", "flags", Batch(
                [Column.from_values(T.VARCHAR, flags),
                 Column.from_values(T.BIGINT, values)], len(flags)))

        sql = ("select f, sum(v), count(*), min(v) from memory.default.flags"
               " where v < 100 group by f order by f")
        insert(["a", "b", "a", None, "b"], [1, 2, 3, 4, 5])
        first = runner.engine.execute_statement(sql, runner.session)
        again = runner.engine.execute_statement(sql, runner.session)
        assert first.rows == again.rows == [
            ("a", 4, 2, 1), ("b", 7, 2, 2), (None, 4, 1, 4)]
        assert again.trace_count == 0
        insert(["c", "a", "d"], [6, 7, 8])
        grown = runner.engine.execute_statement(sql, runner.session)
        assert grown.trace_count >= 1
        assert grown.rows == [("a", 11, 3, 1), ("b", 7, 2, 2), ("c", 6, 1, 6),
                              ("d", 8, 1, 8), (None, 4, 1, 4)]
        # the dictionary's strings ("a", "b" and the "" that from_values
        # keeps for a null), the -1 code and null; then two strings more
        assert [(a["groupBy"], a["slots"], a["cacheHit"]) for a in slab_spans] \
            == [("domain", 5, False), ("domain", 5, True), ("domain", 7, False)]

    @pytest.fixture(scope="class")
    def shapes(self, runner):
        """One 65,536-row memory table holding every key shape the cases
        below group by, and the columns as NumPy arrays for the oracle."""
        import numpy as np

        from trino_tpu import types as T
        from trino_tpu.columnar import Batch, Column
        from trino_tpu.connectors.api import ColumnSchema, TableSchema

        n = 1 << 16
        rng = np.random.default_rng(7)
        data = {
            "k": rng.integers(0, 97, n).astype(np.int64),
            "k2": rng.integers(0, 5, n).astype(np.int64),
            "ki": rng.integers(0, 11, n).astype(np.int32),
            "kn": rng.integers(0, 7, n).astype(np.int64),
            "kneg": rng.integers(-50, 51, n).astype(np.int64),
            # 20 values 1,000 apart: a range of 19,001
            "kwide": rng.integers(0, 20, n).astype(np.int64) * 1000,
            "v": rng.integers(-(1 << 30), 1 << 30, n).astype(np.int64),
            "b": rng.integers(0, 2, n).astype(np.bool_),
        }
        kn_valid = rng.integers(0, 4, n) > 0
        flags = np.asarray(["x", "y", "z"])[rng.integers(0, 3, n)]
        types = {"ki": T.INTEGER, "b": T.BOOLEAN}
        cols = [
            Column(types.get(name, T.BIGINT), arr,
                   kn_valid if name == "kn" else None)
            for name, arr in data.items()
        ] + [Column.from_values(T.VARCHAR, flags.tolist())]
        names = list(data) + ["s"]
        mem = runner.catalogs.get("memory")
        mem.create_table(
            "default", "shapes",
            TableSchema("shapes", tuple(
                ColumnSchema(name, c.type) for name, c in zip(names, cols))),
        )
        mem.insert("default", "shapes", Batch(cols, n))
        return {**data, "kn_valid": kn_valid, "s": flags}

    # What ``group by`` and what beside it; which way the slab step groups.
    # The sort-path rows are the shapes the Pallas group-by's gate admitted
    # (integer keys with no mask, integer sums, up to 8,192 bins by the data's
    # min and max) or refused at its edge (a mask, min/max, a wider range, no
    # row selected), which ran end to end on a chip only while it lived; the
    # domain rows are what took its place.
    _SHAPES = {
        "bigint-97-values-signed-sums": ("k", "sum(v), count(*)", "", "sort"),
        "two-integer-keys": ("k, k2", "sum(v), count(*)", "", "sort"),
        "int32-key": ("ki", "sum(v), count(*)", "", "sort"),
        "key-with-nulls": ("kn", "sum(v), count(*), count(kn)", "", "sort"),
        "negative-keys": ("kneg", "sum(v), count(*)", "", "sort"),
        "range-past-8192": ("kwide", "sum(v), count(*)", "", "sort"),
        "avg-over-integers": ("k2", "avg(v), avg(ki)", "", "sort"),
        "min-max-beside-sums": ("k2", "min(v), max(v), sum(v)", "", "sort"),
        "no-row-selected": (
            "k", "sum(v), count(*)", "where v > 4611686018427387904", "sort"),
        "boolean-key": ("b", "sum(v), count(*), min(v)", "", "domain"),
        "dictionary-key": ("s", "sum(v), count(*), max(v)", "", "domain"),
        "boolean-and-dictionary-keys": (
            "b, s", "sum(v), avg(v), count(*)", "", "domain"),
    }

    @staticmethod
    def _numpy_rows(shape, t):
        """The rows NumPy gives for two of the shapes; None for the others,
        which the default session answers."""
        import numpy as np

        if shape == "bigint-97-values-signed-sums":
            sums = np.zeros(97, np.int64)
            np.add.at(sums, t["k"], t["v"])
            counts = np.bincount(t["k"], minlength=97)
            return [(k, int(sums[k]), int(counts[k])) for k in range(97)]
        if shape == "min-max-beside-sums":
            by = [t["v"][t["k2"] == k] for k in range(5)]
            return [(k, int(v.min()), int(v.max()), int(v.sum()))
                    for k, v in enumerate(by)]
        return None

    @pytest.mark.parametrize("shape", list(_SHAPES))
    def test_group_by_shapes_through_the_slab(
        self, runner, shapes, slab_spans, shape
    ):
        """Each shape twice through the slab program (the second answer out
        of the stored one) equals NumPy's rows for it, or the default
        session's, and the span says which way the step grouped."""
        keys, aggs, where, path = self._SHAPES[shape]
        sql = (f"select {keys}, {aggs} from memory.default.shapes {where}"
               f" group by {keys} order by {keys}")
        first = runner.engine.execute_statement(sql, runner.session)
        again = runner.engine.execute_statement(sql, runner.session)
        want = self._numpy_rows(shape, shapes)
        if want is None:
            want = LocalQueryRunner(engine=runner.engine).execute(sql)[0]
        assert first.rows == again.rows == want, shape
        assert bool(want) == (shape != "no-row-selected")
        assert again.trace_count == 0
        assert [a["cacheHit"] for a in slab_spans] == [False, True]
        for a in slab_spans:
            assert a["groupBy"] == path and ("slots" in a) == (path == "domain"), a
            assert a["steps"] == 2 and a["cap"] == 32768

    def test_the_store_of_a_warm_streamed_query_holds_programs_and_budgets(self):
        """What a fingerprint's program store holds after a warm streamed
        query, by kind: the slab program, its remembered chunk size, the
        fragment programs, the capacity sites, the fragmented plan and the
        counters. No key statistics of the data and nothing named by a Python
        address: ``chip_smoke.py`` used to open the store by hand to look
        for a group-by kernel's programs; this pins what is there."""
        import jax
        import numpy as np

        runner = DistributedQueryRunner(n_devices=1)  # one query, one store
        runner.session.set("stream_scan_threshold_rows", 1)
        sql = Q1.format(90)
        runner.engine.execute_statement(sql, runner.session)
        warm = runner.engine.execute_statement(sql, runner.session)
        assert warm.trace_count == 0 and warm.program_cache_hits >= 1
        engine = runner.engine
        with engine._query_cache_lock:
            (store,) = [e["programs"] for e in engine._query_cache.values()]

        def kind(key):
            # a fragment program's key is (its name, the capacities it holds)
            while isinstance(key, tuple):
                key = key[0]
            return key

        assert {kind(k) for k in store} == {
            "slab",                         # exec/streaming.py
            # (no "slabcap": a width is remembered only where the compiler
            # refused a wider one, as ``("slabcap", site, budget)``)
            # (no "post": on one device the streamed fragment's output
            # exchange is the identity and gets no program of its own)
            "fused", "caps",                # fragment programs, capacity sites
            "__subplan__", "__fusedunits__", "__fragstats__", "__skewroles__",
            "__stats__",
        }
        slab = [k for k in store if kind(k) == "slab"]
        # Q1's key is what it was before the step could widen: the site,
        # the budget, the session's width, a staged slab; and the mesh's size
        assert slab == [("slab", "agg@2#0", 4096, 1 << 21, False, 1)]
        program, meta, dicts = store[next(k for k in slab if k[0] == "slab")]
        assert callable(program) and meta["slots"] == 12
        held = [
            (k, type(leaf).__name__)
            for k, v in store.items() for leaf in jax.tree_util.tree_leaves(v)
            if isinstance(leaf, (jax.Array, np.ndarray))
        ]
        assert not held, held


def test_equal_plans_at_other_addresses_share_the_slab_program():
    """D4: what ``StreamingAggregator`` stores is keyed by content (fragment
    id, the aggregate's ordinal), not by ``id(node)``: an equal plan planned
    again finds the program, and its literals still ride as arguments."""
    from trino_tpu.exec import streaming as S
    from trino_tpu.exec.fragments import FragmentedExecutor, _Caps
    from trino_tpu.planner.canonicalize import canonicalize_plan
    from trino_tpu.planner.fragmenter import fragment_plan
    from trino_tpu.sql.parser import parse_statement

    r = DistributedQueryRunner(n_devices=1)
    r.session.set("stream_scan_threshold_rows", 1)
    sql = ("select l_linestatus, sum(l_quantity), count(*) from lineitem"
           " where l_quantity < {} group by l_linestatus")
    programs: dict = {}

    def aggregator(literal):
        plan = r.engine.plan(parse_statement(sql.format(literal)), r.session)
        plan, params, fp = canonicalize_plan(plan, r.session, 1)
        assert fp is not None and [v for v, _ in params] == [literal]
        ex = FragmentedExecutor(
            r.engine.catalogs, r.session, r.engine.mesh,
            programs=programs, params=params,
        )
        frag = next(
            f for f in fragment_plan(plan).all_fragments()
            if S.streamable_chain(f.root) is not None
        )
        agg, scan, builds = S.streamable_chain(frag.root)
        caps = programs.setdefault(("caps", "stream", frag.id), _Caps())
        return S.StreamingAggregator(ex, frag, agg, scan, caps), ex

    def rows(result):
        return sorted(result.batch.compact().to_pylist())

    first, ex1 = aggregator(10)
    second, ex2 = aggregator(30)
    assert first.agg is not second.agg and first.site == second.site
    got1, got2 = rows(first.run()), rows(second.run())
    assert ex1.compile_stats["trace_count"] == 1
    assert ex2.compile_stats["trace_count"] == 0
    assert ex2.compile_stats["program_cache_hits"] == 1
    assert len([k for k in programs if k[0] == "slab"]) == 1
    assert not [k for k in programs if "id(" in repr(k)]
    local = LocalQueryRunner(engine=r.engine)
    for literal, got in ((10, got1), (30, got2)):
        want, _ = local.execute(
            "select l_linestatus, sum(l_quantity), count(*) from lineitem"
            f" where l_quantity < {literal} group by l_linestatus")
        # partial accumulators: key, sum, count of the sum, count(*)
        assert [(g[0], g[1], g[-1]) for g in got] == sorted(want)


# --- the width of a slab step, chosen from the group budget -------------------


@pytest.mark.parametrize("case, base, groups, sort_path, held, want", [
    # the programs of before keep the session's width, and with it their key
    ("q1: 12 slots on the domain path", 1 << 21, 4096, False, 1 << 23, 1 << 21),
    ("q2 of h2o: 16,384 groups, sort path", 1 << 21, 16384, True, 100_663_296, 1 << 21),
    ("q4 of h2o: 100 integer groups", 1 << 21, 4096, True, 100_663_296, 1 << 21),
    ("16 chunks of groups just fit", 1 << 21, 1 << 17, True, 100_663_296, 1 << 21),
    ("a domain path under a large budget", 1 << 21, 1 << 20, False, 100_663_296, 1 << 21),
    ("a global aggregate", 1 << 21, 1, False, 100_663_296, 1 << 21),
    # the sort path past 16 rows a group of the budget widens
    ("one group more than fits", 1 << 21, (1 << 17) + 1, True, 100_663_296, 1 << 22),
    ("q5 of h2o: a million groups, six steps", 1 << 21, 1 << 20, True, 100_663_296, 1 << 24),
    ("a table smaller than the wide step: one step", 1 << 21, 1 << 20, True, 3 << 22, 3 << 22),
    ("a slab no larger than the base width", 1 << 12, 1 << 20, True, 1 << 12, 1 << 12),
])
def test_slab_step_rows(case, base, groups, sort_path, held, want):
    from trino_tpu.exec.streaming import SLAB_ROWS_PER_GROUP, slab_step_rows

    assert SLAB_ROWS_PER_GROUP == 16
    assert slab_step_rows(base, groups, sort_path, held) == want, case
    if "six steps" in case:
        assert held == 6 * want


class TestWideSlabStep:
    """A step widened from the group budget reads the same rows once each
    and answers as the session's width and the default session do."""

    BASE, BUDGET, WIDE = 4096, 1024, 16384

    @pytest.fixture(scope="class")
    def runner(self):
        r = DistributedQueryRunner(n_devices=1)
        r.session.set("stream_scan_threshold_rows", 1)
        r.session.set("stream_device_chunk_rows", self.BASE)
        r.session.set("stream_group_budget", self.BUDGET)
        return r

    @pytest.fixture(scope="class")
    def tables(self, runner):
        """``wide50k``: 50,000 rows, padded (at the quantum the cases set)
        to 53,248, which 16,384 does not divide; ``wide10k``: 10,000 rows
        in 12,288, less than one wide step."""
        import numpy as np

        from trino_tpu import types as T
        from trino_tpu.columnar import Batch, Column
        from trino_tpu.connectors.api import ColumnSchema, TableSchema

        mem = runner.catalogs.get("memory")
        for name, n in (("wide50k", 50_000), ("wide10k", 10_000)):
            rng = np.random.default_rng(n)
            cols = [
                Column(T.BIGINT, rng.integers(0, 300, n).astype(np.int64)),
                Column(T.BIGINT, rng.integers(0, 3, n).astype(np.int64)),
                Column(T.BIGINT, rng.integers(0, 9, n).astype(np.int64),
                       rng.integers(0, 5, n) > 0),
                Column(T.BIGINT, rng.integers(-(1 << 40), 1 << 40, n)),
            ]
            mem.create_table("default", name, TableSchema(name, tuple(
                ColumnSchema(c, T.BIGINT) for c in ("k", "k2", "kn", "v"))))
            mem.insert("default", name, Batch(cols, n))

    _CASES = {
        # table, keys, aggregates, steps at the wide width
        "rows-not-a-multiple-of-the-width": ("wide50k", "k", "sum(v), count(*)", 4),
        "a-width-larger-than-the-table": ("wide10k", "k", "sum(v), count(*)", 1),
        "null-keys": ("wide50k", "kn", "sum(v), count(*), count(kn)", 4),
        "a-two-key-group-by": ("wide50k", "k, k2", "sum(v), avg(v)", 4),
        "min-max-beside-sums": ("wide50k", "k2", "min(v), max(v), sum(v)", 4),
    }

    @pytest.mark.parametrize("case", list(_CASES))
    def test_the_wide_step_answers_as_the_base_width_and_the_default_session(
        self, runner, tables, slab_spans, monkeypatch, case
    ):
        from trino_tpu.connectors import api
        from trino_tpu.exec import streaming as S

        table, keys, aggs, steps = self._CASES[case]
        # a quantum of the base width, so a wide step need not divide the
        # padded rows (on the chip: 4,194,304 under steps of 16,777,216)
        monkeypatch.setattr(api, "SLAB_PAD_QUANTUM", self.BASE)
        sql = (f"select {keys}, {aggs} from memory.default.{table}"
               f" group by {keys} order by {keys}")
        wide = runner.engine.execute_statement(sql, runner.session)
        again = runner.engine.execute_statement(sql, runner.session)
        assert again.trace_count == 0
        monkeypatch.setattr(S, "SLAB_ROWS_PER_GROUP", 0)  # the rule off
        narrow = runner.engine.execute_statement(sql, runner.session)
        want = LocalQueryRunner(engine=runner.engine).execute(sql)[0]
        assert wide.rows == again.rows == narrow.rows == want and len(want) > 2
        rows, held = (50_000, 53_248) if table == "wide50k" else (10_000, 12_288)
        cap = min(self.WIDE, held)
        assert [(a["cap"], a["baseCap"], a["steps"], a["cacheHit"]) for a in slab_spans] == [
            (cap, self.BASE, steps, False), (cap, self.BASE, steps, True),
            (self.BASE, self.BASE, -(-rows // self.BASE), False),
        ]
        assert all(a["groupBy"] == "sort" and a["groups"] == self.BUDGET for a in slab_spans)

    def test_a_refused_wide_compile_halves_answers_and_is_remembered(
        self, runner, tables, slab_spans, monkeypatch
    ):
        """The compiler refusing the wide step's program (scoped vmem) costs
        one failed compile, once: the ladder halves from the wide start, the
        query answers, and the store remembers the width for that budget."""
        import jax

        from trino_tpu.exec import streaming as S

        sql = ("select k, sum(v), count(*) from memory.default.wide50k"
               " where v > 0 group by k order by k")
        orig = S.StreamingAggregator._make_slab_program
        refused = []

        def refusing(self, meta, cap, chunk_cols=None):
            if cap > 8192:
                refused.append(cap)

                def program(*args):
                    raise jax.errors.JaxRuntimeError(
                        "RESOURCE_EXHAUSTED: Ran out of memory in memory space"
                        " vmem while allocating on stack for %reduce-window")

                return program
            return orig(self, meta, cap, chunk_cols)

        monkeypatch.setattr(S.StreamingAggregator, "_make_slab_program", refusing)
        monkeypatch.setattr(S, "SLAB_MIN_ROWS", 1024)
        first = runner.engine.execute_statement(sql, runner.session)
        again = runner.engine.execute_statement(sql, runner.session)
        assert first.rows == again.rows == LocalQueryRunner(
            engine=runner.engine).execute(sql)[0]
        assert refused == [16384]
        assert [(a["attempt"], a["cap"], a["cacheHit"]) for a in slab_spans] == [
            (1, 16384, False), (2, 8192, False), (1, 8192, True)]
        assert again.trace_count == 0
        with runner.engine._query_cache_lock:
            stores = [e["programs"] for e in runner.engine._query_cache.values()]
        held = [s for s in stores if ("slabcap", "agg@2#0", self.BUDGET) in s]
        assert len(held) == 1 and held[0]["slabcap", "agg@2#0", self.BUDGET] == 8192


def test_a_program_the_rule_leaves_alone_lowers_to_the_text_it_had(slab_spans):
    """Q1's slab program (domain path, base width) against the loop body as
    it stood before the step could widen, written out here: the same
    StableHLO text, so the clamp of a wide last step costs it nothing."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from trino_tpu.columnar import Batch, Column
    from trino_tpu.exec import streaming as S

    def parent_program(sagg, meta, cap):
        inner = sagg._make_step(meta)

        def program(state, slab, n_steps, num_rows, params, builds):
            def body(i, state):
                off = i.astype(jnp.int64) * cap
                cnt = jnp.minimum(cap, (num_rows - off).astype(jnp.int32))
                cols = []
                for c in slab.columns:
                    data = jax.lax.dynamic_slice_in_dim(c.data, off, cap, axis=0)
                    valid = (
                        None if c.valid is None
                        else jax.lax.dynamic_slice_in_dim(c.valid, off, cap, axis=0))
                    cols.append(Column(c.type, data, valid, c.dictionary))
                live = jnp.arange(cap, dtype=jnp.int32) < cnt
                return inner(state, Batch(cols, cap, live), None, params, builds)

            return jax.lax.fori_loop(0, n_steps, body, state)

        return program

    texts = []
    orig = S.StreamingAggregator._slab_attempt

    def lowering(self, programs, slab, chunk_cols, num_rows, cap, span, meta=None):
        res = orig(self, programs, slab, chunk_cols, num_rows, cap, span, meta)
        _, meta, _ = programs[("slab", self.site, self.G, cap, False, 1)]
        # (no join on the probe spine: no build side among the arguments)
        args = (self._init_state(meta), slab, np.int32(1), np.int64(num_rows[0]),
                self.params, ())
        texts.append((cap, [
            jax.jit(make(meta, cap)).lower(*args).as_text()
            for make in (lambda m, c: self._make_slab_program(m, c),
                         lambda m, c: parent_program(self, m, c))]))
        return res

    runner = DistributedQueryRunner(n_devices=1)
    runner.session.set("stream_scan_threshold_rows", 1)
    runner.session.set("stream_device_chunk_rows", 32768)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(S.StreamingAggregator, "_slab_attempt", lowering)
        runner.engine.execute_statement(Q1.format(90), runner.session)
    ((cap, (ours, parents)),) = texts
    assert [(a["cap"], a["baseCap"]) for a in slab_spans] == [(cap, cap)] == [(32768, 32768)]
    assert "dynamic_slice" in ours and ours == parents


def _slab_span(at, site, steps, **attrs):
    return {"name": "stream.slab", "spanId": f"s{at}", "startNs": at, "endNs": at + 1,
            "durationMs": 1e-6, "attrs": {"site": site, "steps": steps, **attrs}}


@pytest.mark.parametrize("case, spans, want", [
    ("a warm query: one loop", [_slab_span(1, "agg@2#0", 6)], 6),
    ("the cold ladder: the pass that outgrew its budget does not count",
     [_slab_span(1, "agg@2#0", 48), _slab_span(5, "agg@2#0", 6), _slab_span(9, "agg@2#0", 6)], 6),
    ("a refused width: the attempt that answered",
     [_slab_span(1, "agg@2#0", 6, attempt=1), _slab_span(2, "agg@2#0", 12, attempt=2)], 12),
    ("two streamed aggregates add up, whatever order the spans come in",
     [_slab_span(7, "agg@4#0", 3), _slab_span(1, "agg@2#0", 48), _slab_span(3, "agg@2#0", 6)], 9),
    ("nothing streamed through a slab program",
     [{"name": "execute_plan", "spanId": "e", "startNs": 0, "endNs": 9, "attrs": {}}], None),
])
def test_slab_steps_are_those_of_each_aggregates_last_loop(case, spans, want):
    """``queryStats.slabSteps`` and the benchmark's reader of it
    (``benchmark/metrics/slab_steps.py``), which reads nothing from a
    program that has no such counter and does not raise."""
    import os

    from benchmark import harness
    from trino_tpu.obs.trace import aggregate_counts, query_phases

    assert aggregate_counts(spans).get("slabSteps") == want, case
    stats = query_phases(spans)
    assert stats.get("slabSteps") == want and stats["resultRows"] == 0
    read = harness.load_reader(
        os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmark"), "slab_steps")
    infos = [{"state": "FINISHED", "queryStats": stats}] * 2
    assert read({"infos": infos}) == want
    assert read({"infos": []}) is None
    assert read({"infos": [{"state": "FINISHED", "queryStats": {"aggAttempts": 1}}]}) is None


# --- a streamed aggregate over a join: the build sides are arguments (C10) ----

Q3 = """select l.l_orderkey, sum(l.l_extendedprice * (1 - l.l_discount)) as revenue,
               o.o_orderdate, o.o_shippriority
        from {customer} c, {orders} o, tpch.tiny.lineitem l
        where c.c_mktsegment = 'BUILDING' and c.c_custkey = o.o_custkey
          and l.l_orderkey = o.o_orderkey and o.o_orderdate < date '1995-03-15'
          and l.l_shipdate > date '1995-03-15'
        group by l.l_orderkey, o.o_orderdate, o.o_shippriority
        order by revenue desc, o.o_orderdate limit 10"""


def _mesh_runner(devices, **session):
    r = DistributedQueryRunner(n_devices=devices)
    r.session.set("stream_scan_threshold_rows", 1)
    r.session.set("stream_device_chunk_rows", 8192)  # a shard's step
    for name, value in session.items():
        r.session.set(name, value)
    return r


@pytest.mark.parametrize("devices", [1, 4])
def test_a_streamed_join_is_stored_and_answers_from_this_querys_build(devices, slab_spans):
    """Q3 twice in the compiled session: the second traces nothing and both
    equal the default session. Then, one program store held across writes (the
    engine's own is the data version's): a row appended to the build side's
    table gives the new answer out of the STORED slab program, its build sides
    being arguments; a build side grown past its capacity is another key, a
    new program and the right answer."""
    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.connectors.api import ColumnSchema, TableSchema
    from trino_tpu.planner.canonicalize import canonicalize_plan
    from trino_tpu.sql.parser import parse_statement

    r = _mesh_runner(devices)
    local = LocalQueryRunner(engine=r.engine)
    sql = Q3.format(customer="tpch.tiny.customer", orders="tpch.tiny.orders")
    cold = r.engine.execute_statement(sql, r.session)
    warm = r.engine.execute_statement(sql, r.session)
    want = local.execute(sql)[0]
    assert cold.rows == warm.rows == want and len(want) == 10
    assert cold.trace_count >= 1
    assert warm.trace_count == 0 and warm.program_cache_hits >= 1
    assert [(a["cacheHit"], a["shards"]) for a in slab_spans][-1] == (True, devices)

    # the build side's table in the memory catalog, where it can be written to
    mem = r.catalogs.get("memory")
    mem.create_table("default", "orders_m", TableSchema("orders_m", (
        ColumnSchema("o_orderkey", T.BIGINT), ColumnSchema("o_custkey", T.BIGINT),
        ColumnSchema("o_orderdate", T.DATE), ColumnSchema("o_shippriority", T.INTEGER))))

    def insert(rows):
        cols = [Column.from_values(t, [row[i] for row in rows])
                for i, t in enumerate((T.BIGINT, T.BIGINT, T.DATE, T.INTEGER))]
        mem.insert("default", "orders_m", Batch(cols, len(rows)))

    orders = local.execute(
        "select o_orderkey, o_custkey, o_orderdate, o_shippriority from tpch.tiny.orders")[0]
    insert([(k, c, str(d), p) for k, c, d, p in orders])
    sql = Q3.format(customer="tpch.tiny.customer", orders="memory.default.orders_m")
    plan = r.engine.plan(parse_statement(sql), r.session)
    plan, params, fingerprint = canonicalize_plan(plan, r.session, devices)
    assert fingerprint is not None
    programs: dict = {}

    def compiled():
        del slab_spans[:]
        res = r.engine._execute_query_plan(plan, r.session, programs=programs, params=params)
        (slab,) = [a for a in slab_spans if a["attempt"] == 1][-1:]
        return res.rows, slab["cacheHit"]

    first, hit = compiled()
    assert first == want and not hit
    again, hit = compiled()
    assert again == want and hit
    # the order whose lines shipped after the date are worth most, given a
    # second row that qualifies: a BUILDING customer, a date before, priority 7
    (key, _), = local.execute(
        "select l_orderkey, sum(l_extendedprice * (1 - l_discount)) from tpch.tiny.lineitem"
        " where l_shipdate > date '1995-03-15' group by l_orderkey order by 2 desc limit 1")[0]
    (customer,), = local.execute(
        "select min(c_custkey) from tpch.tiny.customer where c_mktsegment = 'BUILDING'")[0]
    insert([(key, customer, "1995-01-01", 7)])
    written, hit = compiled()
    assert hit, "a written build side has to ride the stored program as its argument"
    assert written == local.execute(sql)[0] and written != want
    assert written[0][0] == key and written[0][3] == 7
    # 70,000 orders more that qualify (and have no lines): the join below
    # outgrows its capacity, and the build side comes at a larger one
    insert([(10_000_000 + i, customer, "1995-01-01", 0) for i in range(70_000)])
    grown, hit = compiled()
    assert not hit, "a build side at another capacity is another program"
    assert grown == written == local.execute(sql)[0]
    stored = [k for k in programs if isinstance(k, tuple) and k[0] == "slab"]
    assert len(stored) >= 2 and not [k for k in stored if "id(" in repr(k)]
    for k in stored:  # site, budget, rows a step, staged, mesh; capacities, builds
        assert k[1] == "agg@2#0" and k[5] == devices and len(k) == 8
        assert all(site.split("@")[0] in ("agg", "join", "ujoin", "densejoin", "semi")
                   for site, _ in k[6] if isinstance(site, str) and "@" in site)


def test_the_host_chunk_step_is_stored_on_a_mesh():
    """C7: where the table is not staged (no room for a slab), the chunks
    come from the host and the jitted step is stored under a key that holds
    the mesh's size: a warm query on four devices traces nothing."""
    r = _mesh_runner(4, stream_device_cache_bytes=0, stream_chunk_rows=4096)
    sql = Q3.format(customer="tpch.tiny.customer", orders="tpch.tiny.orders")
    cold = r.engine.execute_statement(sql, r.session)
    warm = r.engine.execute_statement(sql, r.session)
    assert cold.rows == warm.rows == LocalQueryRunner(engine=r.engine).execute(sql)[0]
    assert cold.trace_count >= 1 and warm.trace_count == 0
    with r.engine._query_cache_lock:
        keys = [k for e in r.engine._query_cache.values() for k in e["programs"]]
    (step,) = [k for k in keys if isinstance(k, tuple) and k[0] == "step"]
    assert step[1] == "agg@2#0" and step[3] == 4
    assert not [k for k in keys if isinstance(k, tuple) and k[0] == "slab"]


def test_the_slab_is_row_sharded_and_a_warm_query_ships_nothing():
    """Four devices at tpch.tiny: the staged slab's first column lies on all
    four, none holding over 40% of it (what ``chip_smoke.py::four_chips``
    asserts of a scanned column); a warm query puts no byte on the devices,
    sends rows through the exchange, and answers as one device does."""
    from trino_tpu.connectors.api import slab_shard_rows

    sql = Q3.format(customer="tpch.tiny.customer", orders="tpch.tiny.orders")
    four, one = _mesh_runner(4), _mesh_runner(1)
    cold = four.engine.execute_statement(sql, four.session)
    warm = four.engine.execute_statement(sql, four.session)
    alone = one.engine.execute_statement(sql, one.session)
    assert cold.rows == warm.rows == alone.rows and len(warm.rows) == 10
    assert cold.ingest_stats["h2d_bytes"] > 0 and warm.ingest_stats["h2d_bytes"] == 0
    assert warm.exchange_stats["shuffle_rows"] > 0 and warm.exchange_stats["exchanges"] == 1
    assert alone.exchange_stats["shuffle_rows"] == 0
    ((slab, rows),) = four.catalogs.get("tpch")._device_slabs.values()
    first = slab.columns[0].data
    shards = first.addressable_shards
    assert len({s.device for s in shards}) == 4
    assert max(s.data.nbytes for s in shards) <= 0.4 * first.nbytes
    assert rows == 60175 and list(slab_shard_rows(rows, 4)) == [15044, 15044, 15044, 15043]
    # each shard holds its run of the rows from its first row on
    held = slab.capacity // 4
    import numpy as np

    keys = np.asarray(first).reshape(4, held)
    ((whole, _),) = one.catalogs.get("tpch")._device_slabs.values()
    assert (np.concatenate([keys[s, :n] for s, n in enumerate(slab_shard_rows(rows, 4))])
            == np.asarray(whole.columns[0].data)[:rows]).all()


def test_the_shards_partial_states_merged_are_one_devices_state():
    """The share tied to the whole: the group states the four shards of a
    mesh carry out of the slab loop, merged by key, are the state one device
    carries out of it over the same rows."""
    from trino_tpu.exec import streaming as S
    from trino_tpu.exec.fragments import FragmentedExecutor, _Caps
    from trino_tpu.planner.canonicalize import canonicalize_plan
    from trino_tpu.planner.fragmenter import fragment_plan
    from trino_tpu.sql.parser import parse_statement

    sql = ("select l_suppkey, sum(l_quantity), count(*), min(l_shipdate) from lineitem"
           " where l_quantity < 30 group by l_suppkey")

    def partial_state(devices):
        r = _mesh_runner(devices)
        plan = r.engine.plan(parse_statement(sql), r.session)
        plan, params, _ = canonicalize_plan(plan, r.session, devices)
        ex = FragmentedExecutor(r.engine.catalogs, r.session, r.engine.mesh,
                                programs={}, params=params)
        frag = next(f for f in fragment_plan(plan).all_fragments()
                    if S.streamable_chain(f.root) is not None)
        agg, scan, _ = S.streamable_chain(frag.root)
        assert agg.step == "partial"
        sagg = S.StreamingAggregator(ex, frag, agg, scan, _Caps())
        return sagg.run().batch.compact().to_pylist()

    # key, sum, its count, count(*), min, its count
    merged: dict = {}
    shards = partial_state(4)
    for key, total, n, rows, least, m in shards:
        have = merged.get(key)
        merged[key] = (total, n, rows, least, m) if have is None else (
            have[0] + total, have[1] + n, have[2] + rows, min(have[3], least), have[4] + m)
    one = {row[0]: tuple(row[1:]) for row in partial_state(1)}
    assert len(shards) > len(one) == 100, "each shard has to hold a part of a group"
    assert merged == one
