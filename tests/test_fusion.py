"""Whole-pipeline pjit fusion.

Covers the fusion PR end to end: the grouping pass's eligibility matrix
(fusable chains, blocked spill-sized fragments, spooling boundaries,
skew-salted pair atomicity), fused-vs-unfused bit-identical results over
the TPC-H corpus with partitioned joins, the acceptance bound (a >=3
fragment query in <=2 dispatch round-trips), cross-query program-cache
reuse of fused programs, the RESOURCE_EXHAUSTED capacity-halving ladder,
the shared dbgen disk cache, and a cluster chaos run with fusion on.
"""

import numpy as np
import pytest

from test_tpch_suite import QUERIES
from trino_tpu.planner import plan as P
from trino_tpu.planner.fragmenter import (
    FusedFragment,
    fragment_plan,
    fuse_groups,
    partitioned_join_pairs,
)
from trino_tpu.testing import DistributedQueryRunner, LocalQueryRunner

# forces real HASH exchanges at tiny scale (everything fits under the
# broadcast threshold otherwise, and broadcast links never fuse)
PARTITIONED = {"join_distribution_type": "PARTITIONED"}

# orders |><| lineitem with a partitioned distribution plans as a >=4
# fragment chain (two scans, the join, partial+final aggregation) whose
# interior links are all HASH/single — the canonical fusable pipeline
JOIN_SQL = """
    select o_orderpriority, count(*) as c, sum(l_extendedprice) as s
    from tpch.tiny.orders o
    join tpch.tiny.lineitem l on o.o_orderkey = l.l_orderkey
    group by o_orderpriority
    order by o_orderpriority
"""


def _subplan(sql, **props):
    r = LocalQueryRunner()
    r.session.set("execution_mode", "distributed")
    r.session.set("join_distribution_type", "PARTITIONED")
    for k, v in props.items():
        r.session.set(k, v)
    return fragment_plan(r.plan(sql))


@pytest.fixture(scope="module")
def fused_runner():
    r = DistributedQueryRunner()
    r.session.set("join_distribution_type", "PARTITIONED")
    return r


@pytest.fixture(scope="module")
def unfused_runner():
    r = DistributedQueryRunner()
    r.session.set("join_distribution_type", "PARTITIONED")
    r.session.set("pipeline_fusion", False)
    return r


@pytest.fixture(scope="module")
def single_node():
    return LocalQueryRunner()


# === the eligibility matrix (plan-level, no execution) ====================


class TestEligibilityMatrix:
    def test_fusable_chain_forms_one_unit(self):
        from trino_tpu.exec.fragments import fragment_fusable

        sub = _subplan(JOIN_SQL)
        units = fuse_groups(sub, fusable=fragment_fusable)
        fused = [u for u in units if isinstance(u, FusedFragment)]
        assert fused, "partitioned join chain did not fuse at all"
        unit = max(fused, key=lambda u: len(u.fragments))
        assert len(unit.fragments) >= 3
        # bottom-up member order: the consumer root is LAST
        assert unit.root is unit.fragments[-1]
        # the unit partition covers every fragment exactly once
        covered = sorted(
            fid
            for u in units
            for fid in (
                u.fragment_ids if isinstance(u, FusedFragment) else (u.id,)
            )
        )
        assert covered == sorted(f.id for f in sub.all_fragments())

    def test_blocked_fragment_stays_on_per_fragment_path(self):
        """A blocked id (the exec layer blocks spill-sized / streaming
        scans) never rides inside a fused unit."""
        from trino_tpu.exec.fragments import fragment_fusable

        sub = _subplan(JOIN_SQL)
        scan_fid = next(
            f.id
            for f in sub.all_fragments()
            if any(isinstance(n, P.TableScan) for n in P.walk_plan(f.root))
        )
        units = fuse_groups(
            sub, fusable=fragment_fusable, blocked=frozenset({scan_fid})
        )
        for u in units:
            if isinstance(u, FusedFragment):
                assert scan_fid not in u.fragment_ids

    def test_spill_threshold_feeds_the_blocked_set(self):
        """The exec layer's estimate-based gate: scans bigger than the
        spill threshold keep their fragments out of fusion ONLY when the
        dense join tier's graceful overflow is unavailable (then the
        spill fallback needs the per-fragment interpreter path). With
        dense_join on — the default — the spill bar is gone: overflow
        re-hashes at doubled capacity inside the retry ladder."""
        from trino_tpu.exec.fragments import FragmentedExecutor

        r = LocalQueryRunner()
        r.session.set("execution_mode", "distributed")
        r.session.set("join_distribution_type", "PARTITIONED")
        sub = fragment_plan(r.plan(JOIN_SQL))

        ex = FragmentedExecutor(r.engine.catalogs, r.session, r.engine.mesh)
        assert ex._fusion_blocked(sub) == set()

        r.session.set("spill_enabled", True)
        r.session.set("spill_threshold_rows", 1)
        # graceful overflow available (dense_join defaults on): the
        # spill threshold no longer bars anything from fusion
        assert ex._fusion_blocked(sub) == set()

        r.session.set("dense_join", False)
        blocked = ex._fusion_blocked(sub)
        scan_fids = {
            f.id
            for f in sub.all_fragments()
            if any(isinstance(n, P.TableScan) for n in P.walk_plan(f.root))
        }
        assert scan_fids <= blocked
        # pinning the strategy to sort also disables graceful overflow
        r.session.set("dense_join", True)
        r.session.set("join_strategy", "sort")
        assert scan_fids <= ex._fusion_blocked(sub)

    def test_skew_pair_absorbed_atomically(self):
        """A partitioned-join probe/build pair fuses both-or-neither: the
        probe exchange detects heavy hitters and the build exchange salts
        with the resulting hot set, so splitting the pair across a fusion
        boundary would break their co-partitioning contract."""
        from trino_tpu.exec.fragments import fragment_fusable

        sub = _subplan(JOIN_SQL)
        pairs = partitioned_join_pairs(sub)
        assert pairs, "partitioned equi-join should yield a probe/build pair"
        probe, build = pairs[0]

        units = fuse_groups(sub, fusable=fragment_fusable, skew_pairs=pairs)
        unit = next(
            u
            for u in units
            if isinstance(u, FusedFragment)
            and {probe, build} & set(u.fragment_ids)
        )
        assert {probe, build} <= set(unit.fragment_ids)

        # with room for only one more member the pair must NOT be split:
        # no unit may contain exactly one of the two
        units2 = fuse_groups(
            sub, fusable=fragment_fusable, skew_pairs=pairs, max_fragments=2
        )
        for u in units2:
            if isinstance(u, FusedFragment):
                overlap = {probe, build} & set(u.fragment_ids)
                assert len(overlap) != 1, (
                    f"skew pair split across a fusion boundary: {overlap}"
                )


# === fused == unfused == single-node over the TPC-H corpus ================


# five queries spanning the fusable shapes: scan+agg (1), 3-way join with
# topn (3), 6-way partitioned join (5), outer-ish join+agg (10), semi
# membership (12) — all outside the tracked interpreter-fallback census
EQUIVALENCE_QIDS = (1, 3, 5, 10, 12)


@pytest.mark.parametrize("qid", EQUIVALENCE_QIDS)
def test_fused_matches_unfused_and_single_node(
    qid, fused_runner, unfused_runner, single_node
):
    got, _ = fused_runner.execute(QUERIES[qid])
    want, _ = unfused_runner.execute(QUERIES[qid])
    ref, _ = single_node.execute(QUERIES[qid])
    assert got == want, f"Q{qid}: fused != unfused\n{got[:3]}\n{want[:3]}"
    assert got == ref, f"Q{qid}: fused != single-node\n{got[:3]}\n{ref[:3]}"


def test_chain_runs_in_at_most_two_round_trips(fused_runner, unfused_runner):
    """Acceptance: a >=3 fragment chain costs <=2 dispatch round-trips
    fused (vs one per fragment program unfused)."""
    sub = fragment_plan(fused_runner.plan(JOIN_SQL))
    assert len(sub.all_fragments()) >= 3
    res = fused_runner.engine.execute_statement(JOIN_SQL, fused_runner.session)
    ex = res.exchange_stats or {}
    assert ex.get("dispatchRoundTrips", 99) <= 2, ex
    assert ex.get("fusedFragments", 0) >= 3, ex
    res_u = unfused_runner.engine.execute_statement(
        JOIN_SQL, unfused_runner.session
    )
    ex_u = res_u.exchange_stats or {}
    assert ex_u.get("fusedFragments", 0) == 0, ex_u
    assert ex_u.get("dispatchRoundTrips", 0) > ex.get("dispatchRoundTrips", 0)
    assert res.rows == res_u.rows


def test_spill_sized_join_fuses_under_graceful_overflow(single_node):
    """Regression: before the dense join tier, a spill-eligible fragment
    was barred from fusion outright (the interpreter owned the overflow
    story).  With graceful overflow — dense_join on, the default — the
    same spill-sized join runs fused in strictly fewer dispatch
    round-trips, and the rows stay bit-identical to the barred path."""
    spill = {"spill_enabled": True, "spill_threshold_rows": 1}

    r = DistributedQueryRunner()
    r.session.set("join_distribution_type", "PARTITIONED")
    for k, v in spill.items():
        r.session.set(k, v)
    res = r.engine.execute_statement(JOIN_SQL, r.session)
    ex = res.exchange_stats or {}

    r_bar = DistributedQueryRunner()
    r_bar.session.set("join_distribution_type", "PARTITIONED")
    r_bar.session.set("dense_join", False)  # re-raise the spill bar
    for k, v in spill.items():
        r_bar.session.set(k, v)
    res_bar = r_bar.engine.execute_statement(JOIN_SQL, r_bar.session)
    ex_bar = res_bar.exchange_stats or {}

    # with the bar re-raised nothing fuses — the whole join drops to the
    # per-fragment interpreter path (no compiled dispatches at all)
    assert ex_bar.get("fusedFragments", 0) == 0, ex_bar
    # gracefully-overflowing run: fused, and in fewer dispatch
    # round-trips than one-per-fragment
    sub = fragment_plan(r.plan(JOIN_SQL))
    assert ex.get("fusedFragments", 0) >= 3, ex
    assert ex.get("dispatchRoundTrips", 99) <= 2 < len(sub.all_fragments())
    assert res.rows == res_bar.rows
    ref, _ = single_node.execute(JOIN_SQL)
    assert res.rows == ref


def test_repeat_query_hits_fused_program_cache(fused_runner):
    """Warm rerun of a fused plan: zero retraces, cache hits > 0, same
    rows — the fused program key must be stable across executions."""
    first = fused_runner.engine.execute_statement(
        JOIN_SQL, fused_runner.session
    )
    again = fused_runner.engine.execute_statement(
        JOIN_SQL, fused_runner.session
    )
    assert again.rows == first.rows
    assert again.trace_count == 0, (
        f"warm fused rerun retraced {again.trace_count} programs"
    )
    assert again.program_cache_hits > 0


# === RESOURCE_EXHAUSTED capacity-halving ladder ===========================


class TestCapacityHalving:
    def test_shrink_all_halves_and_floors(self):
        from trino_tpu.exec.fragments import _Caps

        caps = _Caps()
        caps.get("join", 1024)
        caps.get("small", 64)
        assert caps.shrink_all() is True
        assert caps.vals["join"] == 512
        assert caps.vals["small"] == 64  # already at the floor
        assert caps.provenance["join"].endswith("+halved")
        while caps.shrink_all():
            pass
        assert all(v == 64 for v in caps.vals.values())
        assert caps.shrink_all() is False  # nothing left: caller re-raises

    def test_resource_exhausted_classifier(self):
        from trino_tpu.exec.fragments import _is_resource_exhausted

        assert _is_resource_exhausted(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating ...")
        )
        assert _is_resource_exhausted(
            Exception("Scoped allocation of 2.1G exceeds the vmem limit")
        )
        assert not _is_resource_exhausted(ValueError("syntax error"))

    def test_retry_traced_halves_until_the_program_compiles(self):
        """A build fn whose program 'compiles' only below a capacity
        threshold: _retry_traced must walk the halving ladder instead of
        failing the query, and count each halving."""
        import jax.numpy as jnp

        from trino_tpu import types as T
        from trino_tpu.columnar import Batch, Column
        from trino_tpu.exec.fragments import FragmentedExecutor, _Caps
        from trino_tpu.exec.local import Result

        r = LocalQueryRunner()
        r.session.set("execution_mode", "distributed")
        ex = FragmentedExecutor(r.engine.catalogs, r.session, r.engine.mesh)
        caps = _Caps()
        caps.get("buf", 4096)

        class _FakeTracer:
            overflows = ()
            counters = ()
            exchange_static = {}
            aux_out = ()
            joins = {}

        def build(meta):
            cap = caps.get("buf", 4096)

            def f(x):
                if cap > 1024:  # static: decided at trace time
                    raise RuntimeError(
                        "RESOURCE_EXHAUSTED: scoped allocation of "
                        f"{cap} slots exceeds vmem"
                    )
                res = Result(
                    Batch([Column(T.BIGINT, x + 1)], x.shape[0]), {"x": 0}
                )
                meta.capture(res, _FakeTracer())
                return meta.outputs(res)

            return f

        out = ex._retry_traced(
            caps, build, (jnp.arange(8, dtype=jnp.int64),)
        )
        assert caps.vals["buf"] == 1024  # 4096 -> 2048 -> 1024
        assert caps.provenance["buf"].endswith("+halved")
        assert ex.exchange_stats.get("compile_halvings") == 2
        assert np.asarray(out.batch.columns[0].data).tolist() == list(
            range(1, 9)
        )

    def test_non_resource_errors_still_raise(self):
        import jax.numpy as jnp

        from trino_tpu.exec.fragments import FragmentedExecutor, _Caps

        r = LocalQueryRunner()
        r.session.set("execution_mode", "distributed")
        ex = FragmentedExecutor(r.engine.catalogs, r.session, r.engine.mesh)
        caps = _Caps()
        caps.get("buf", 4096)

        def build(meta):
            def f(x):
                raise ValueError("genuine bug, not capacity")

            return f

        with pytest.raises(ValueError, match="genuine bug"):
            ex._retry_traced(caps, build, (jnp.arange(4),))
        assert caps.vals["buf"] == 4096  # untouched: no halving for bugs


# === shared dbgen disk cache ==============================================


class TestDbgenDiskCache:
    def _batch(self):
        from trino_tpu import types as T
        from trino_tpu.columnar import Batch, Column, Dictionary

        return Batch(
            [
                Column(T.BIGINT, np.arange(5, dtype=np.int64)),
                Column(
                    T.parse_type("double"),
                    np.linspace(0.0, 1.0, 5),
                    np.array([True, True, False, True, True]),
                ),
                Column(
                    T.parse_type("varchar"),
                    np.array([0, 1, 0, 1, 0], np.int32),
                    None,
                    Dictionary(["AIR", "RAIL"]),
                ),
            ],
            5,
        )

    def test_round_trip_bit_identical(self, tmp_path):
        from trino_tpu.connectors.diskcache import DbgenDiskCache

        cache = DbgenDiskCache(directory=str(tmp_path), max_bytes=1 << 20)
        key = ("tpch", "tiny", "lineitem", ("a", "b", "c"), 0, 4)
        assert cache.get(key) is None and cache.misses == 1
        batch = self._batch()
        cache.put(key, batch)
        got = cache.get(key)
        assert got is not None and cache.hits == 1
        assert got.num_rows == batch.num_rows
        for g, w in zip(got.columns, batch.columns):
            assert str(g.type) == str(w.type)
            np.testing.assert_array_equal(np.asarray(g.data), np.asarray(w.data))
            if w.valid is None:
                assert g.valid is None
            else:
                np.testing.assert_array_equal(
                    np.asarray(g.valid), np.asarray(w.valid)
                )
            if w.dictionary is not None:
                assert list(g.dictionary.values) == list(w.dictionary.values)
        # a different split index is a different entry
        assert cache.get(("tpch", "tiny", "lineitem", ("a", "b", "c"), 1, 4)) is None

    def test_eviction_respects_the_size_bound(self, tmp_path):
        from trino_tpu.connectors.diskcache import DbgenDiskCache

        cache = DbgenDiskCache(directory=str(tmp_path), max_bytes=1)
        cache.put(("t", "s", "a", (), 0, 1), self._batch())
        cache.put(("t", "s", "b", (), 0, 1), self._batch())
        left = list(tmp_path.glob("*.npz"))
        assert len(left) == 0, f"1-byte bound must evict everything: {left}"

    def test_disabled_by_env(self, monkeypatch):
        from trino_tpu.connectors import diskcache

        monkeypatch.setenv("TRINO_TPU_DBGEN_CACHE", "off")
        cache = diskcache.DbgenDiskCache()
        assert not cache.enabled
        cache.put(("t", "s", "x", (), 0, 1), self._batch())  # no-op
        assert cache.get(("t", "s", "x", (), 0, 1)) is None

    def test_connector_reads_hit_across_instances(self, tmp_path, monkeypatch):
        """A second connector process (here: instance) reads the split a
        first one generated, bit-identical, without regenerating."""
        from trino_tpu.connectors.tpch import TpchConnector

        monkeypatch.setenv("TRINO_TPU_DBGEN_CACHE", str(tmp_path))
        first = TpchConnector()
        # the test session shares one in-memory batch cache across
        # connector instances (conftest shared_dbgen_cache); this test
        # is about the disk tier, so give each instance a private one
        first._batch_cache = {}
        splits = first.get_splits("tiny", "region", target_splits=1)
        cols = ["r_regionkey", "r_name"]
        b1 = first.read_split("tiny", "region", cols, splits[0])
        assert list(tmp_path.glob("*.npz")), "miss should write the entry"

        second = TpchConnector()
        second._batch_cache = {}
        hits_before = second._disk_cache.hits
        b2 = second.read_split("tiny", "region", cols, splits[0])
        assert second._disk_cache.hits == hits_before + 1
        assert b2.num_rows == b1.num_rows
        for g, w in zip(b2.columns, b1.columns):
            np.testing.assert_array_equal(np.asarray(g.data), np.asarray(w.data))


# === cluster: spooling boundary + chaos with fusion on ====================


FUSED_CLUSTER_PROPS = {
    "join_distribution_type": "PARTITIONED",
    "worker_execution": "fused",
}


@pytest.fixture(scope="module")
def cluster():
    from trino_tpu.testing import MultiProcessQueryRunner

    with MultiProcessQueryRunner(n_workers=2) as runner:
        yield runner


def _query_infos(runner):
    import json
    import urllib.request

    from trino_tpu.server import auth

    req = urllib.request.Request(
        f"{runner.coordinator_uri}/v1/query", headers=auth.headers()
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read().decode())


def _last_exchange_stats(runner, sql):
    infos = [
        q for q in _query_infos(runner) if q.get("query", "").strip() == sql.strip()
    ]
    assert infos, "query not found in coordinator query list"
    return infos[-1].get("exchangeStats") or {}


@pytest.mark.faults
class TestClusterFusion:
    def test_spooling_coexists_with_fusion(self, cluster):
        """Fusion and spooled exchange coexist: fused-unit output buffers
        ARE the spool pages, so turning spooling on keeps the exact same
        fused schedule (same fused fragments, no extra dispatch
        round-trips) while the unit boundaries become durable
        (spooledBytes > 0)."""
        base, _ = cluster.execute(JOIN_SQL, session_properties=FUSED_CLUSTER_PROPS)
        ex_fused = _last_exchange_stats(cluster, JOIN_SQL)
        assert ex_fused.get("fusedFragments", 0) >= 3, ex_fused

        spooled, _ = cluster.execute(
            JOIN_SQL,
            session_properties={
                **FUSED_CLUSTER_PROPS,
                "exchange_spooling": True,
                "retry_policy": "TASK",
            },
        )
        ex_spool = _last_exchange_stats(cluster, JOIN_SQL)
        assert spooled == base
        assert ex_spool.get("fusedFragments", 0) == ex_fused.get(
            "fusedFragments", 0
        ), (ex_spool, ex_fused)
        assert ex_spool.get("dispatchRoundTrips", 0) <= ex_fused.get(
            "dispatchRoundTrips", 0
        ), (ex_spool, ex_fused)
        infos = [
            q for q in _query_infos(cluster)
            if q.get("query", "").strip() == JOIN_SQL.strip()
            and q.get("retryPolicy") == "TASK"
        ]
        assert infos and infos[-1].get("spooledBytes", 0) > 0, (
            "unit-boundary output buffers never reached the spool"
        )

    def test_task_retry_chaos_with_fusion_on(self, cluster):
        """retry_policy=TASK with injected task crashes and fusion ON:
        fused-unit tasks retry/fall back like any other task and the rows
        stay bit-identical to a clean run."""
        clean, _ = cluster.execute(JOIN_SQL, session_properties=FUSED_CLUSTER_PROPS)
        injected = 0
        for seed in (7, 11, 23):
            chaos = {
                **FUSED_CLUSTER_PROPS,
                "retry_policy": "TASK",
                "task_retry_attempts": 8,
                "fault_injection_seed": seed,
                "fault_task_crash_p": 0.4,
                "retry_initial_delay_ms": 20,
                "retry_max_delay_ms": 200,
            }
            chaotic, _ = cluster.execute(JOIN_SQL, session_properties=chaos)
            assert chaotic == clean, f"seed={seed} diverged under chaos"
        retries = [q.get("taskRetries", 0) for q in _query_infos(cluster)]
        injected = sum(retries)
        assert injected > 0, (
            "crash_p=0.4 over 3 seeded runs should have injected at least "
            f"one task crash (retry counters: {retries})"
        )
