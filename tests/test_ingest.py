"""Columnar ingest tier (trino_tpu/ingest.py): coalesced H2D staging
arenas, double-buffered split decode, and the device-resident table
cache — plus the native/fallback decode parity contract."""

import numpy as np
import pytest

from trino_tpu import native
from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column, Dictionary
from trino_tpu.config import Session
from trino_tpu.ingest import (
    DeviceTableCache,
    SplitPrefetcher,
    shard_batch_coalesced,
    splits_fingerprint,
)
from trino_tpu.parallel.mesh import make_mesh, shard_batch


# === fast native smoke test (gates the native-specific cases) ==========


def test_native_smoke():
    """The one-liner that proves the shared library round-trips: if this
    fails, every native-path test below is suspect; if the library is
    absent, the suite still runs (fallbacks are the contract), but the
    conftest report header makes the degraded mode visible."""
    arrays = [np.arange(5, dtype=np.int64), np.ones(3, dtype=np.float32)]
    out = native.pack_arena(arrays, use_native=native.NATIVE_AVAILABLE)
    assert out.dtype == np.uint32
    assert out.size == native.arena_words([a.nbytes for a in arrays])


needs_native = pytest.mark.skipif(
    not native.NATIVE_AVAILABLE, reason="native columnar library not built"
)


# === arena pack parity ==================================================


@needs_native
def test_pack_arena_native_python_parity():
    rng = np.random.default_rng(0)
    arrays = [
        rng.integers(-(2**62), 2**62, 17, dtype=np.int64),
        rng.integers(0, 2**32, 33, dtype=np.uint32),
        rng.random(9).astype(np.float32),
        rng.integers(0, 2, 13).astype(np.bool_),
        rng.integers(-128, 127, 7, dtype=np.int8),
        rng.integers(-(2**15), 2**15, 5, dtype=np.int16),
        np.zeros(0, dtype=np.int32),  # empty buffer mid-arena
    ]
    a_native = native.pack_arena(arrays, use_native=True)
    a_python = native.pack_arena(arrays, use_native=False)
    assert np.array_equal(a_native, a_python)


def test_pack_arena_empty():
    assert native.pack_arena([]).size == 0
    assert native.pack_arena([np.zeros(0, dtype=np.int64)]).size == 0


# === coalesced shard placement is bit-identical to per-column ==========


def _parts_with_everything(mesh, rng):
    """Per-device parts covering every segment kind: int64, nullable
    int32, float64 (arena fallback), float32, bool, dictionary varchar,
    wide-decimal (N, 2) lanes — with ragged row counts so selection
    masks and padding engage."""
    n = mesh.devices.size
    parts = []
    for i in range(n):
        rows = 5 + i
        d = Dictionary([f"s{i}a", f"s{i}b"])
        cols = [
            Column(T.BIGINT, rng.integers(-(2**60), 2**60, rows, dtype=np.int64)),
            Column(
                T.INTEGER,
                rng.integers(-100, 100, rows).astype(np.int32),
                np.asarray([k % 3 != 0 for k in range(rows)], dtype=np.bool_),
            ),
            Column(T.DOUBLE, rng.random(rows)),
            Column(T.REAL, rng.random(rows).astype(np.float32)),
            Column(T.BOOLEAN, rng.integers(0, 2, rows).astype(np.bool_)),
            Column(
                T.VARCHAR, rng.integers(0, 2, rows).astype(np.int32), None, d
            ),
            Column(
                T.DecimalType(38, 2),
                rng.integers(0, 1 << 40, (rows, 2), dtype=np.int64),
            ),
        ]
        parts.append(Batch(cols, rows))
    return parts


def _assert_batches_equal(b1: Batch, b2: Batch):
    assert b1.capacity == b2.capacity
    s1 = None if b1.sel is None else np.asarray(b1.sel)
    s2 = None if b2.sel is None else np.asarray(b2.sel)
    assert (s1 is None) == (s2 is None)
    if s1 is not None:
        assert np.array_equal(s1, s2)
    for c1, c2 in zip(b1.columns, b2.columns):
        assert c1.data.dtype == c2.data.dtype
        assert np.array_equal(np.asarray(c1.data), np.asarray(c2.data))
        v1 = None if c1.valid is None else np.asarray(c1.valid)
        v2 = None if c2.valid is None else np.asarray(c2.valid)
        assert (v1 is None) == (v2 is None)
        if v1 is not None:
            assert np.array_equal(v1, v2)


@pytest.mark.parametrize("use_native", [True, False])
def test_shard_batch_coalesced_bit_identical(use_native):
    mesh = make_mesh()
    rng = np.random.default_rng(3)
    parts = _parts_with_everything(mesh, rng)
    stats: dict = {}
    plain = shard_batch(mesh, parts)
    coalesced = shard_batch_coalesced(
        mesh, parts, use_native=use_native, stats=stats, min_bytes=0
    )
    _assert_batches_equal(plain, coalesced)
    assert stats["h2d_bytes"] > 0
    # one arena transfer per device, plus the float64 per-column fallback
    n = mesh.devices.size
    assert stats["h2d_transfers"] == n + n
    assert stats["fallback_columns"] == 1  # the DOUBLE column


def test_shard_batch_coalesced_full_parts_no_sel():
    """Equal-capacity all-valid parts skip the selection mask in both
    paths (the no-mask fast path must survive coalescing)."""
    mesh = make_mesh()
    n = mesh.devices.size
    parts = [
        Batch([Column(T.BIGINT, np.arange(8, dtype=np.int64) + i)], 8)
        for i in range(n)
    ]
    plain = shard_batch(mesh, parts)
    coalesced = shard_batch_coalesced(mesh, parts, min_bytes=0)
    assert plain.sel is None and coalesced.sel is None
    _assert_batches_equal(plain, coalesced)


def test_shard_batch_coalesced_small_scan_delegates():
    """Under the byte threshold the arena can't amortize its unpack
    compile: the per-column path runs instead, with H2D still counted."""
    mesh = make_mesh()
    n = mesh.devices.size
    parts = [
        Batch([Column(T.BIGINT, np.arange(4, dtype=np.int64))], 4)
        for _ in range(n)
    ]
    stats: dict = {}
    plain = shard_batch(mesh, parts)
    coalesced = shard_batch_coalesced(mesh, parts, stats=stats)
    _assert_batches_equal(plain, coalesced)
    assert stats["h2d_bytes"] == n * 4 * 8
    assert "coalesced_columns" not in stats


# === split prefetcher ===================================================


def test_prefetcher_order_and_stats():
    stats: dict = {}
    out = list(
        SplitPrefetcher(lambda x: x * 2, range(20), enabled=True, stats=stats)
    )
    assert out == [x * 2 for x in range(20)]
    assert stats["splits_decoded"] == 20
    assert out == list(SplitPrefetcher(lambda x: x * 2, range(20), enabled=False))


def test_prefetcher_propagates_decode_error():
    def boom(x):
        if x == 3:
            raise ValueError("bad split")
        return x

    with pytest.raises(ValueError, match="bad split"):
        list(SplitPrefetcher(boom, range(6), enabled=True))


def test_prefetcher_early_stop():
    """Consumer break (connector limit hint) must not deadlock the
    producer thread blocked on the full slot."""
    seen = []

    def decode(x):
        seen.append(x)
        return x

    it = iter(SplitPrefetcher(decode, range(100), enabled=True))
    assert next(it) == 0
    it.close()  # generator close -> producer unblocked and joined
    assert len(seen) < 100


# === device table cache unit behavior ===================================


def _dummy_batch():
    return Batch([Column(T.BIGINT, np.arange(4, dtype=np.int64))], 4)


def test_table_cache_lru_eviction_under_byte_budget():
    tc = DeviceTableCache()
    b = _dummy_batch()
    assert tc.admit(("k1",), b, 100, max_bytes=250)
    assert tc.admit(("k2",), b, 100, max_bytes=250)
    assert tc.lookup(("k1",)) is not None  # touch: k2 becomes LRU
    assert tc.admit(("k3",), b, 100, max_bytes=250)
    assert tc.lookup(("k2",)) is None  # evicted
    assert tc.lookup(("k1",)) is not None
    assert tc.lookup(("k3",)) is not None
    assert tc.evictions == 1
    assert tc.total_bytes == 200


def test_table_cache_rejects_over_budget_and_low_headroom(monkeypatch):
    tc = DeviceTableCache()
    b = _dummy_batch()
    assert not tc.admit(("big",), b, 999, max_bytes=250)
    assert tc.rejections == 1
    # HBM admission: the profiler-informed headroom check says no
    import trino_tpu.ingest as ingest_mod

    monkeypatch.setattr(
        ingest_mod, "hbm_headroom_ok", lambda *a, **k: False
    )
    assert not tc.admit(("k1",), b, 10, max_bytes=250)
    assert tc.rejections == 2
    assert tc.lookup(("k1",)) is None


def test_table_cache_invalidate_by_catalog():
    tc = DeviceTableCache()
    b = _dummy_batch()
    tc.admit(("cat_a", "t1"), b, 10, max_bytes=100)
    tc.admit(("cat_b", "t2"), b, 10, max_bytes=100)
    assert tc.invalidate("cat_a") == 1
    assert tc.lookup(("cat_a", "t1")) is None
    assert tc.lookup(("cat_b", "t2")) is not None
    assert tc.invalidate() == 1
    assert tc.total_bytes == 0


def test_splits_fingerprint_changes_with_splits():
    from trino_tpu.connectors.api import Split

    a = [Split("t", 0, 2, info=("f1", 0)), Split("t", 1, 2, info=("f1", 1))]
    b = a + [Split("t", 2, 3, info=("f2", 0))]
    assert splits_fingerprint(a) != splits_fingerprint(b)
    assert splits_fingerprint(a) == splits_fingerprint(list(a))


# === engine-level behavior ==============================================


@pytest.fixture()
def drunner():
    from trino_tpu.testing import DistributedQueryRunner

    return DistributedQueryRunner(
        Session(
            user="test",
            catalog="memory",
            schema="default",
            # tiny test tables must still exercise the arena path
            properties={"coalesce_min_bytes": 0},
        )
    )


def test_warm_repeat_scan_h2d_zero(drunner):
    sql = (
        "select l_returnflag, sum(l_quantity), count(*) from"
        " tpch.tiny.lineitem group by l_returnflag order by l_returnflag"
    )
    cold = drunner.engine.execute_statement(sql, drunner.session)
    assert cold.ingest_stats is not None
    assert cold.ingest_stats["h2d_bytes"] > 0
    warm = drunner.engine.execute_statement(sql, drunner.session)
    assert warm.rows == cold.rows
    assert warm.ingest_stats["h2d_bytes"] == 0
    assert warm.ingest_stats.get("table_cache_hits", 0) >= 1
    assert warm.ingest_stats["tableCache"]["entries"] >= 1


def test_results_identical_across_ingest_modes(drunner):
    sql = (
        "select l_linestatus, l_returnflag, sum(l_extendedprice),"
        " avg(l_discount), count(*) from tpch.tiny.lineitem"
        " where l_quantity < 30 group by 1, 2 order by 1, 2"
    )
    base = drunner.engine.execute_statement(sql, drunner.session).rows
    for props in (
        {"native_decode": False},
        {"table_cache": False},
        {"coalesced_h2d": False},
        {"ingest_prefetch": False},
        {
            "native_decode": False,
            "table_cache": False,
            "coalesced_h2d": False,
            "ingest_prefetch": False,
        },
    ):
        ses = Session(
            user="test",
            properties={
                "execution_mode": "distributed",
                "coalesce_min_bytes": 0,
                **props,
            },
        )
        got = drunner.engine.execute_statement(sql, ses).rows
        assert got == base, f"rows diverged under {props}"


def test_memory_insert_invalidates_cached_scan(drunner):
    drunner.execute("create table memory.default.inv (k bigint)")
    drunner.execute("insert into memory.default.inv values (1), (2)")
    sql = "select count(*), sum(k) from memory.default.inv"
    r1 = drunner.engine.execute_statement(sql, drunner.session)
    assert r1.rows == [(2, 1 + 2)]
    # warm: cache hit on the unchanged table
    r2 = drunner.engine.execute_statement(sql, drunner.session)
    assert r2.ingest_stats.get("table_cache_hits", 0) >= 1
    # INSERT bumps the memory connector's _version: the key changes, the
    # next scan MUST miss and see the new row
    drunner.execute("insert into memory.default.inv values (10)")
    r3 = drunner.engine.execute_statement(sql, drunner.session)
    assert r3.rows == [(3, 13)]


def test_parquet_append_invalidates_cached_scan(tmp_path, drunner):
    from trino_tpu.connectors.api import ColumnSchema, TableSchema
    from trino_tpu.connectors.parquet import ParquetConnector

    pq = ParquetConnector(str(tmp_path))
    drunner.engine.catalogs.register("pqc", pq)
    pq.create_table(
        "default",
        "t",
        TableSchema("t", (ColumnSchema("x", T.BIGINT),)),
    )
    pq.insert(
        "default",
        "t",
        Batch([Column(T.BIGINT, np.arange(10, dtype=np.int64))], 10),
    )
    sql = "select count(*), sum(x) from pqc.default.t"
    r1 = drunner.engine.execute_statement(sql, drunner.session)
    assert r1.rows == [(10, 45)]
    r2 = drunner.engine.execute_statement(sql, drunner.session)
    assert r2.ingest_stats.get("table_cache_hits", 0) >= 1
    # appending a part file changes the file-list data_version
    pq.insert(
        "default",
        "t",
        Batch([Column(T.BIGINT, np.asarray([100], dtype=np.int64))], 1),
    )
    r3 = drunner.engine.execute_statement(sql, drunner.session)
    assert r3.rows == [(11, 145)]


def test_parquet_decode_native_fallback_parity(tmp_path):
    """read_split through the C hot loops vs the pure-Python fallback
    must produce bit-identical host batches."""
    from trino_tpu.connectors.api import ColumnSchema, TableSchema
    from trino_tpu.connectors.parquet import ParquetConnector

    rng = np.random.default_rng(11)
    n = 500
    valid = rng.integers(0, 4, n) > 0
    d, codes = Dictionary.from_strings(
        [f"name_{int(i) % 7}" for i in rng.integers(0, 100, n)]
    )
    batch = Batch(
        [
            Column(T.BIGINT, rng.integers(0, 1 << 40, n, dtype=np.int64)),
            Column(
                T.INTEGER,
                rng.integers(-50, 50, n).astype(np.int32),
                valid,
            ),
            Column(T.DOUBLE, rng.random(n)),
            Column(T.VARCHAR, codes.astype(np.int32), None, d),
        ],
        n,
    )
    pq = ParquetConnector(str(tmp_path))
    pq.create_table(
        "default",
        "p",
        TableSchema(
            "p",
            (
                ColumnSchema("a", T.BIGINT),
                ColumnSchema("b", T.INTEGER),
                ColumnSchema("c", T.DOUBLE),
                ColumnSchema("s", T.VARCHAR),
            ),
        ),
    )
    pq.insert("default", "p", batch)
    cols = ["a", "b", "c", "s"]
    splits = pq.get_splits("default", "p", 4)
    assert splits
    for s in splits:
        b_native = pq.read_split("default", "p", cols, s)
        with native.python_fallback():
            b_python = pq.read_split("default", "p", cols, s)
        assert b_native.num_rows == b_python.num_rows
        for c1, c2 in zip(b_native.columns, b_python.columns):
            assert np.array_equal(np.asarray(c1.data), np.asarray(c2.data))
            if c1.dictionary is not None:
                assert list(c1.dictionary.values) == list(
                    c2.dictionary.values
                )


def test_orc_decode_native_fallback_parity(tmp_path):
    from trino_tpu.connectors.api import ColumnSchema, TableSchema
    from trino_tpu.connectors.orc import OrcConnector

    rng = np.random.default_rng(13)
    n = 400
    batch = Batch(
        [
            Column(T.BIGINT, rng.integers(0, 1 << 30, n, dtype=np.int64)),
            Column(T.DOUBLE, rng.random(n)),
        ],
        n,
    )
    oc = OrcConnector(str(tmp_path))
    oc.create_table(
        "default",
        "o",
        TableSchema(
            "o", (ColumnSchema("a", T.BIGINT), ColumnSchema("c", T.DOUBLE))
        ),
    )
    oc.insert("default", "o", batch)
    for s in oc.get_splits("default", "o", 4):
        b_native = oc.read_split("default", "o", ["a", "c"], s)
        with native.python_fallback():
            b_python = oc.read_split("default", "o", ["a", "c"], s)
        for c1, c2 in zip(b_native.columns, b_python.columns):
            assert np.array_equal(np.asarray(c1.data), np.asarray(c2.data))


def test_ingest_metrics_and_stats_surface(drunner):
    from trino_tpu.obs.metrics import get_registry

    drunner.execute("select count(*) from tpch.tiny.region")
    snap = get_registry().snapshot()
    flat = str(snap)
    assert "trino_tpu_ingest_h2d_bytes_total" in flat
    assert "trino_tpu_ingest_decode_ms" in flat
    res = drunner.engine.execute_statement(
        "select count(*), sum(n_nationkey) from tpch.tiny.nation",
        drunner.session,
    )
    ing = res.ingest_stats
    assert ing is not None and "h2d_bytes" in ing


# === the default session: tables live on the device =====================
# LocalExecutor's scan hands on a device-resident batch and keeps it
# across queries in the engine's DeviceTableCache (table_cache=false is
# the scan as it was: host batches, the cache untouched).

_LOCAL_Q1 = (
    "select l_returnflag, l_linestatus, sum(l_quantity),"
    " sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)), count(*)"
    " from tpch.tiny.lineitem where l_shipdate <= date '1998-09-02'"
    " group by l_returnflag, l_linestatus order by 1, 2"
)
_LOCAL_Q3 = (
    "select l.l_orderkey, sum(l.l_extendedprice * (1 - l.l_discount)) as"
    " revenue, o.o_orderdate, o.o_shippriority from tpch.tiny.customer c,"
    " tpch.tiny.orders o, tpch.tiny.lineitem l where c.c_mktsegment ="
    " 'BUILDING' and c.c_custkey = o.o_custkey"
    " and l.l_orderkey = o.o_orderkey and o.o_orderdate < date '1995-03-15'"
    " and l.l_shipdate > date '1995-03-15' group by l.l_orderkey,"
    " o.o_orderdate, o.o_shippriority order by revenue desc, o.o_orderdate"
    " limit 10"
)
_HOST = Session(properties={"table_cache": False})


@pytest.fixture()
def lrunner():
    from trino_tpu.testing import LocalQueryRunner

    return LocalQueryRunner()


def _scan_nodes(plan):
    from trino_tpu.planner import plan as P

    out, stack = [], [plan]
    while stack:
        n = stack.pop()
        if isinstance(n, P.TableScan):
            out.append(n)
        stack.extend(n.sources)
    return out


def _traced(runner, sql, session=None):
    """(StatementResult, the query's op:TableScan spans)."""
    from trino_tpu.obs.trace import InMemorySpanSink, get_tracer

    sink = InMemorySpanSink()
    get_tracer().add_sink(sink)
    try:
        res = runner.engine.execute_statement(sql, session or runner.session)
    finally:
        get_tracer().remove_sink(sink)
    spans = [s for t in sink.trace_ids() for s in sink.spans_for(t)]
    return res, [s for s in spans if s["name"] == "op:TableScan"]


@pytest.mark.parametrize(
    "sql,scans", [(_LOCAL_Q1, 1), (_LOCAL_Q3, 3)], ids=["q1", "q3"]
)
def test_local_scan_resident_second_run_hits(lrunner, sql, scans):
    host = lrunner.engine.execute_statement(sql, _HOST)
    want_bytes = 0
    for node in _scan_nodes(lrunner.plan(sql)):
        ex = lrunner.engine._executor(_HOST, None)
        for c in ex._exec(node).batch.columns:
            want_bytes += np.asarray(c.data).nbytes
            want_bytes += 0 if c.valid is None else np.asarray(c.valid).nbytes
    assert lrunner.engine.table_cache.snapshot()["misses"] == 0

    cold, cold_spans = _traced(lrunner, sql)
    assert cold.rows == host.rows
    assert cold.ingest_stats["table_cache_misses"] == scans
    assert "table_cache_hits" not in cold.ingest_stats
    assert cold.ingest_stats["h2d_bytes"] == want_bytes > 0
    assert [s["attrs"]["tableCacheHit"] for s in cold_spans] == [False] * scans

    warm, warm_spans = _traced(lrunner, sql)
    assert warm.rows == host.rows
    assert warm.ingest_stats == {"h2d_bytes": 0, "table_cache_hits": scans}
    assert [s["attrs"]["tableCacheHit"] for s in warm_spans] == [True] * scans
    snap = lrunner.engine.table_cache.snapshot()
    assert (snap["entries"], snap["hits"], snap["misses"]) == (scans,) * 3
    assert snap["bytes"] == want_bytes


def test_local_scan_columns_are_device_arrays_with_the_hosts_rows(lrunner):
    import jax

    (node,) = _scan_nodes(lrunner.plan(_LOCAL_Q1))
    host = lrunner.engine._executor(_HOST, None)._exec(node).batch
    for _ in range(2):  # a miss, then the resident batch
        dev = lrunner.engine._executor(lrunner.session, None)._exec(node).batch
        assert dev.num_rows == host.num_rows and dev.sel is None
        for h, d in zip(host.columns, dev.columns):
            assert isinstance(h.data, np.ndarray)
            assert isinstance(d.data, jax.Array)
            assert d.data.dtype == h.data.dtype
            assert np.array_equal(np.asarray(d.data), h.data)
            assert (d.valid is None) == (h.valid is None)
            assert d.valid is None or isinstance(d.valid, jax.Array)
            assert d.dictionary is h.dictionary  # host objects, shared
            # a host consumer that writes in place has to copy first
            assert not np.asarray(d.data).flags.writeable


def test_local_scan_write_between_runs_misses_and_reads_new_rows(lrunner):
    lrunner.execute("create table memory.default.res (k bigint, v bigint)")
    lrunner.execute("insert into memory.default.res values (1, 10), (2, 20)")
    sql = (
        "select count(*), sum(a.v), sum(b.v) from memory.default.res a,"
        " memory.default.res b where a.k = b.k"
    )
    r1 = lrunner.engine.execute_statement(sql, lrunner.session)
    assert r1.rows == [(2, 30, 30)]
    r2 = lrunner.engine.execute_statement(sql, lrunner.session)
    assert r2.rows == r1.rows
    assert r2.ingest_stats == {"h2d_bytes": 0, "table_cache_hits": 2}
    # INSERT bumps the connector's version: the first scan misses and reads
    # the new row, the second is served the batch the first one put
    lrunner.execute("insert into memory.default.res values (3, 30)")
    r3 = lrunner.engine.execute_statement(sql, lrunner.session)
    assert r3.rows == [(3, 60, 60)]
    assert r3.ingest_stats["table_cache_misses"] == 1
    assert r3.ingest_stats["table_cache_hits"] == 1
    assert r3.ingest_stats["h2d_bytes"] > 0


def test_local_scan_with_pushed_limit_is_not_admitted(lrunner):
    sql = "select o_orderkey from tpch.tiny.orders limit 5"
    (node,) = _scan_nodes(lrunner.plan(sql))
    assert node.limit == 5
    for _ in range(2):
        res, spans = _traced(lrunner, sql)
        assert len(res.rows) == 5
        # cut short, so neither looked up nor kept; still one upload a column
        assert "tableCacheHit" not in spans[0]["attrs"]
        assert res.ingest_stats["h2d_bytes"] > 0
        assert "table_cache_misses" not in res.ingest_stats
    snap = lrunner.engine.table_cache.snapshot()
    assert (snap["entries"], snap["hits"], snap["misses"]) == (0, 0, 0)


def test_local_scan_over_the_byte_budget_is_rejected_and_still_right(lrunner):
    host = lrunner.engine.execute_statement(_LOCAL_Q1, _HOST)
    small = Session(properties={"table_cache_max_bytes": 1 << 10})
    for n in (1, 2):
        res = lrunner.engine.execute_statement(_LOCAL_Q1, small)
        assert res.rows == host.rows
        # not resident, so every query pays the upload again: once a column
        assert res.ingest_stats["table_cache_misses"] == 1
        assert res.ingest_stats["h2d_bytes"] > 1 << 10
        snap = lrunner.engine.table_cache.snapshot()
        assert (snap["entries"], snap["rejections"]) == (0, n)


def test_local_scan_table_cache_off_leaves_the_cache_untouched(lrunner):
    (node,) = _scan_nodes(lrunner.plan(_LOCAL_Q1))
    ex = lrunner.engine._executor(_HOST, None)
    batch = ex._exec(node).batch
    assert all(isinstance(c.data, np.ndarray) for c in batch.columns)
    res = lrunner.engine.execute_statement(_LOCAL_Q1, _HOST)
    assert "h2d_bytes" not in (res.ingest_stats or {})
    assert lrunner.engine.table_cache.snapshot() == {
        "entries": 0, "bytes": 0, "hits": 0, "misses": 0,
        "evictions": 0, "rejections": 0,
    }


def test_local_scan_of_live_state_is_read_anew_every_query(lrunner):
    # the system tables materialize process state at scan time and have no
    # snapshot token (supports_result_caching false): never looked up or
    # kept, so the second read lists the first
    sql = "select query from system.runtime.queries"
    r1, s1 = _traced(lrunner, sql)
    r2, s2 = _traced(lrunner, sql)
    assert len(r2.rows) == len(r1.rows) + 1
    for res, spans in ((r1, s1), (r2, s2)):
        assert "tableCacheHit" not in spans[0]["attrs"]
        assert "table_cache_misses" not in res.ingest_stats
        assert res.ingest_stats["h2d_bytes"] > 0  # resident all the same
    snap = lrunner.engine.table_cache.snapshot()
    assert (snap["entries"], snap["hits"], snap["misses"]) == (0, 0, 0)


@pytest.mark.parametrize("first", ["local", "mesh"])
def test_local_and_one_device_mesh_scans_do_not_serve_each_other(first):
    # one engine, one cache, one device: a mesh scan's batch is padded to
    # its shards' capacity under a selection wherever they are uneven, the
    # local one is the table's rows as they are; the placement kind in the
    # key keeps them apart
    from trino_tpu.testing import DistributedQueryRunner

    runner = DistributedQueryRunner(n_devices=1)
    sessions = {"mesh": runner.session, "local": Session()}
    order = [first, "mesh" if first == "local" else "local"]
    (node,) = _scan_nodes(runner.plan(_LOCAL_Q1))
    rows = []
    for n, kind in enumerate(order, start=1):
        res = runner.engine.execute_statement(_LOCAL_Q1, sessions[kind])
        rows.append(res.rows)
        assert res.ingest_stats["table_cache_misses"] == 1
        assert "table_cache_hits" not in res.ingest_stats
        assert runner.engine.table_cache.snapshot()["entries"] == n
    assert rows[0] == rows[1]
    host = runner.engine._executor(_HOST, None)._exec(node).batch
    served = {}
    for kind in order:  # each is served the batch its own scan put
        ex = runner.engine._executor(sessions[kind], None)
        served[kind] = batch = ex._exec(node).batch
        assert ex.ingest_stats["table_cache_hits"] == 1
        live = np.asarray(batch.selection_mask())
        assert int(live.sum()) == host.num_rows
        for h, d in zip(host.columns, batch.columns):
            assert np.array_equal(np.asarray(d.data)[live], h.data)
    assert served["local"].sel is None
    assert served["local"].capacity == host.num_rows
    assert served["local"].columns[0].data is not served["mesh"].columns[0].data
