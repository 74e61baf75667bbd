"""A streamed join's output capacity (``exec/fragments.py::_exec_join``,
``exec/streaming.py::_prebuild``): where the build key is unique among the
build side's live rows, the slab step gives the join its probe's capacity,
else twice that; the overflow flag and the growth ladder stay the guard.
TPC-H Q5 through the one-device slab runner against the default session, the
``joins`` of ``stream.slab`` and the counters ``queryStats.buildRows`` and
``joinOutSlots`` against the spans. At the probe's width a unique build is a
lookup (``lookup`` on each join, ``queryStats.lookupJoins``): no probe column
gathered; a duplicate build key, or a build said unique that is not, takes
the expansion. The same rule in a fragment program whose build side is made
before it (``FragmentedExecutor._unique_builds``): Q3's orders ⨝ customer,
and orders joined to a memory table below lineitem's streamed join."""

import pytest

from trino_tpu.obs.trace import aggregate_counts, get_tracer
from trino_tpu.testing import DistributedQueryRunner, LocalQueryRunner

Q5 = """select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
from customer, orders, lineitem, supplier, nation, region
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and s_nationkey = n_nationkey and n_regionkey = r_regionkey
  and r_name = 'ASIA' and o_orderdate >= date '1994-01-01'
  and o_orderdate < date '1994-01-01' + interval '1' year
group by n_name order by revenue desc"""

#: lineitem streamed a supplier's rows at a time through a memory table
BY_SUPPLIER = """select d.label, count(*), sum(l.l_quantity)
from tpch.tiny.lineitem l join memory.default.{table} d on l.l_suppkey = d.k
group by d.label order by d.label"""

#: lineitem streamed through orders, which a fragment program joins to a memory
#: table of customer keys made by a fragment of its own (the filter keeps it so)
BY_CUSTOMER = """select d.label, count(*), sum(l.l_quantity)
from tpch.tiny.lineitem l join tpch.tiny.orders o on l.l_orderkey = o.o_orderkey
join memory.default.{table} d on o.o_custkey = d.k
where d.label like 'g%'
group by d.label order by d.label"""


class _Streamed:
    """A sink that keeps the compiled tier's ``stream.*`` spans and the spans
    over its fragment programs."""

    def __init__(self):
        self.spans = []

    def record(self, span):
        if span.name.startswith("stream.") or span.name in (
            "fragment_execute", "fused_execute"
        ):
            self.spans.append(span.to_json())

    def named(self, name):
        return [s["attrs"] for s in self.spans if s["name"] == name]

    def joins(self, name):
        return [j for attrs in self.named(name) for j in attrs.get("joins", ())]


@pytest.fixture()
def streamed():
    sink = _Streamed()
    get_tracer().add_sink(sink)
    try:
        yield sink
    finally:
        get_tracer().remove_sink(sink)


def _slab_runner(devices=1):
    r = DistributedQueryRunner(n_devices=devices)
    r.session.set("stream_scan_threshold_rows", 1000)
    r.session.set("stream_device_chunk_rows", 4096)
    return r


@pytest.fixture(scope="module")
def runner():
    return _slab_runner()


def test_q5_through_the_slab_equals_the_default_session(runner, streamed):
    got = runner.engine.execute_statement(Q5, runner.session)
    assert got.rows == LocalQueryRunner(engine=runner.engine).execute(Q5)[0]
    assert [r[0] for r in got.rows][:2] == ["VIETNAM", "CHINA"]
    (slab,) = streamed.named("stream.slab")
    assert slab["groupBy"] == "domain"
    (build,) = streamed.named("stream.build")
    # one entry a join of the step, each with its key columns; the build
    # sides' joins ran in fragments of their own
    assert len(slab["joins"]) == build["builds"] == len(build["rowsBySite"])
    assert max(j["keys"] for j in slab["joins"]) == 2
    for j in slab["joins"]:
        assert j["unique"] and j["lookup"] and j["outCap"] == j["probeCap"] == 4096
    # a warm query answers from the stored program, at the same widths
    del streamed.spans[:]
    warm = runner.engine.execute_statement(Q5, runner.session)
    assert warm.rows == got.rows and warm.trace_count == 0
    assert streamed.named("stream.slab")[0]["joins"] == slab["joins"]


def test_the_counters_are_those_of_the_spans(runner, streamed):
    res = runner.engine.execute_statement(Q5, runner.session)
    assert res.rows
    counts = aggregate_counts(streamed.spans)
    (slab,) = streamed.named("stream.slab")
    (build,) = streamed.named("stream.build")
    assert counts["joinOutSlots"] == slab["steps"] * sum(j["outCap"] for j in slab["joins"])
    assert counts["lookupJoins"] == sum(j["lookup"] for j in slab["joins"]) == len(slab["joins"])
    assert counts["buildRows"] == build["rows"] == sum(build["rowsBySite"].values())
    assert 0 < build["rows"] <= sum(build["capacities"])


def test_the_served_query_carries_the_counters():
    from trino_tpu import client
    from trino_tpu.server.http import TrinoTpuServer

    server = TrinoTpuServer(port=0).start()
    try:
        conn = client.Connection(server.base_uri, client.ClientSession(
            catalog="tpch", schema="tiny", properties={
                "execution_mode": "distributed", "stream_scan_threshold_rows": 1000,
                "stream_device_chunk_rows": 4096}))
        rows, _ = conn.execute(Q5)
        assert len(rows) == 5
        (info,) = [q for q in conn.list_queries() if q["state"] == "FINISHED"]
        stats = info["queryStats"]
        assert stats["slabSteps"] >= 1
        assert stats["joinOutSlots"] >= stats["slabSteps"] * 4096
        assert stats["buildRows"] > 0
        assert stats["lookupJoins"] >= 1
    finally:
        server.stop()


def _key_table(runner, name, keys):
    runner.execute(f"create table memory.default.{name} (k bigint, label varchar)")
    values = ", ".join(f"({k}, 'group{k % 3}')" for k in keys)
    runner.execute(f"insert into memory.default.{name} values {values}")


CASES = ("unique", "duplicate appended", "forced overflow")


@pytest.mark.parametrize(
    "where,devices,case",
    [pytest.param("slab", 1, case, id=case) for case in CASES]
    + [
        pytest.param("fragment", n, case, id=f"fragment-{n}-{case}")
        for n in (1, 4) for case in CASES
    ],
)
def test_the_output_capacity_follows_the_build_key(
    where, devices, case, streamed, monkeypatch
):
    """A memory table of keys as a join's build side: of lineitem's streamed
    join in the slab step (supplier keys), or of orders' join in the
    fragment program below it (customer keys), on one device and on four.
    Unique keys: the join's output is its probe's width, and it runs as a
    lookup. A duplicate key appended: twice that, from a program of its own,
    and the answer stays the default session's. A build said to be unique
    that is not (the flag forced): the lookup's flag fires, the ladder grows
    the capacity, and the answer is still right."""
    from trino_tpu.exec import fragments as F
    from trino_tpu.ops import join as J

    runner = _slab_runner(devices)
    if where == "slab":
        table = {"unique": "sup_u", "duplicate appended": "sup_d", "forced overflow": "sup_f"}[case]
        _key_table(runner, table, range(1, 101))
        sql, span = BY_SUPPLIER.format(table=table), "stream.slab"
    else:
        table = f"cus{devices}_{case[0]}"
        _key_table(runner, table, range(1, 301))
        sql, span = BY_CUSTOMER.format(table=table), "fragment_execute"
    first = runner.engine.execute_statement(sql, runner.session)
    (join,) = streamed.joins(span)
    probe = join["probeCap"]
    # twice the probe's width a shard, in the bucket the expansion takes
    wide = devices * F.bucket_capacity(max(1024, 2 * probe // devices))
    assert join["unique"] and join["lookup"] and join["outCap"] == probe
    assert where == "fragment" or probe == 4096
    if case != "unique":
        runner.execute(f"insert into memory.default.{table} values (7, 'gagain')")
        if case == "forced overflow" and where == "slab":
            monkeypatch.setattr(J, "unique_keys", lambda keys, sel: ~sel[:0].any())
        elif case == "forced overflow":
            monkeypatch.setattr(
                F, "_unique_flags", lambda sides: F.jnp.ones(len(sides), bool)
            )
    del streamed.spans[:]
    got = runner.engine.execute_statement(sql, runner.session)
    assert got.rows == LocalQueryRunner(engine=runner.engine).execute(sql)[0]
    joins = streamed.joins(span)
    if case == "unique":
        assert got.rows == first.rows and got.trace_count == 0
        assert joins == [join]
    elif case == "duplicate appended":
        assert got.rows != first.rows and got.trace_count > 0
        assert [(j["unique"], j["outCap"], j["lookup"]) for j in joins] == [(False, wide, False)]
    else:
        # the first attempt, a lookup at the probe's width, met a probe row
        # matching twice; the capacity grown past the probe's width expands
        assert [j["unique"] for j in joins] == [True] * len(joins) and len(joins) >= 2
        assert joins[0]["outCap"] == probe and joins[-1]["outCap"] == wide
        assert joins[0]["lookup"] and not joins[-1]["lookup"]


@pytest.mark.parametrize("query,devices", [("q5", 1), ("q3", 1), ("q3", 4)])
def test_every_spine_join_is_a_lookup(runner, streamed, query, devices):
    """Every build key of Q5's and Q3's probe spines is unique, so each join
    of the step runs as a lookup, on one device and on each shard of four,
    and the answer is the default session's. (At tpch.tiny Q5's step holds
    one join, its two-column one. Q3's sort-path group-by widens a 4,096-row
    step to 65,536 rows after its join took 4,096 slots, which is no lookup:
    Q3 runs at SF1's shape, a step the width of the session's chunk.) Q3's
    orders ⨝ customer, in the fragment program below the step, is a lookup
    too: the step's build side is the probe's width, orders', and a warm
    query traces nothing."""
    from trino_tpu.benchmarks.tpch import queries

    if query == "q3":
        runner = DistributedQueryRunner(n_devices=devices)
        runner.session.set("stream_scan_threshold_rows", 1)
        runner.session.set("stream_device_chunk_rows", 65536)  # a shard's step
    sql = Q5 if query == "q5" else queries("tpch.tiny")[3]
    got = runner.engine.execute_statement(sql, runner.session)
    assert got.rows and got.rows == LocalQueryRunner(engine=runner.engine).execute(sql)[0]
    (slab,) = streamed.named("stream.slab")
    assert slab["joins"] and all(j["lookup"] and j["unique"] for j in slab["joins"])
    assert all(j["outCap"] == j["probeCap"] == slab["cap"] * devices for j in slab["joins"])
    assert aggregate_counts(streamed.spans)["lookupJoins"] == len(slab["joins"]) == 1
    if query == "q3":
        (join,) = streamed.joins("fragment_execute")
        assert join["unique"] and join["lookup"] and join["outCap"] == join["probeCap"]
        (build,) = streamed.named("stream.build")
        assert build["capacities"] == [join["probeCap"]]
        del streamed.spans[:]
        warm = runner.engine.execute_statement(sql, runner.session)
        assert warm.rows == got.rows and warm.trace_count == 0
        assert aggregate_counts(streamed.spans)["fragmentLookupJoins"] == 1


def test_a_literal_that_lets_a_duplicate_through_runs_a_program_of_its_own(streamed):
    """Literal variants of one cached plan whose fragment join's build side is
    unique for one literal and not for the other: the flag keys the stored
    program, so each variant runs its own (a lookup, or the expansion), warm
    ones trace nothing, the span's ``joins`` are those of the program that
    ran, and every answer is the default session's."""
    runner = _slab_runner()
    _key_table(runner, "cus_lit", range(1, 301))
    runner.execute("insert into memory.default.cus_lit values (7, 'gagain')")
    sql = BY_CUSTOMER.format(table="cus_lit").replace(
        "d.label like 'g%'", "d.k <> {k}")
    seen = []
    for k in (7, 8, 7, 8):
        del streamed.spans[:]
        got = runner.engine.execute_statement(sql.format(k=k), runner.session)
        assert got.rows == LocalQueryRunner(engine=runner.engine).execute(sql.format(k=k))[0]
        (join,) = streamed.joins("fragment_execute")
        seen.append((join["unique"], join["lookup"], got.trace_count > 0))
        assert aggregate_counts(streamed.spans)["fragmentLookupJoins"] == join["lookup"]
    assert seen[:2] == [(True, True, True), (False, False, True)]
    assert seen[3] == (False, False, False) and seen[2][:2] == (True, True)
