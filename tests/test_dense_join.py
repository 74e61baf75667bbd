"""TPU-native dense hash join (ops/dense_join.py + the executor tier).

Covers the join-engine-v2 PR: kernel units for the open-addressing
build/probe pair (graceful overflow re-hash at doubled capacity, null
keys, duplicate-key tie order, the duplicate-chain pathology capacity
growth can never fix), the join-as-matmul count contraction vs its
gather lowering, dense-vs-sort kernel bit-identity across 3 rng seeds,
the `_Caps` demotion ladder, end-to-end bit-identity across join_strategy
auto/sort/dense on TPC-H Q5/Q10 and a TPC-DS star query against the
single-node interpreter, the multiway star-join fusion win, and the
PR-15 history loop (warm repeat with zero overflow retries off a
history-seeded `densejoin@…` site).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from test_tpch_suite import QUERIES
from trino_tpu.ops import dense_join as DJ
from trino_tpu.ops.join import (
    MISSING,
    build_side,
    hash_keys,
    probe_join,
    verify_equal,
)
from trino_tpu.config import Session
from trino_tpu.testing import DistributedQueryRunner, LocalQueryRunner

def _keys(data, valid=None):
    data = jnp.asarray(data, jnp.int64)
    if valid is None:
        valid = jnp.ones(data.shape[0], jnp.bool_)
    return [(data, jnp.asarray(valid))]


def _sort_pairs(keys_p, keys_b, psel, bsel, out_cap, jt):
    """The trusted PR-0 tier: (probe_pos, build_pos) per live output."""
    ph, pv = hash_keys(keys_p)
    bh, bv = hash_keys(keys_b)
    sk, si, cnt = build_side(bh, bv, jnp.asarray(bsel))
    pp, bp, osel, total, ovf = probe_join(
        sk, si, cnt, ph, pv, jnp.asarray(psel), out_cap, jt
    )
    osel = verify_equal(keys_p, keys_b, pp, bp, osel)
    assert not bool(ovf)
    return _live(pp, bp, osel)


def _dense_pairs(keys_p, keys_b, psel, bsel, out_cap, jt, capacity):
    """The dense tier at a FIXED capacity; asserts no table overflow."""
    ph, pv = hash_keys(keys_p)
    bh, bv = hash_keys(keys_b)
    bbase = DJ.slot_base_hash(bh, capacity)
    table, tovf = DJ.build_table(bbase, bv, jnp.asarray(bsel), capacity)
    assert not bool(tovf)
    pbase = DJ.slot_base_hash(ph, capacity)
    pp, bp, osel, total, ovf = DJ.probe_table(
        table, bh, pbase, ph, pv, jnp.asarray(psel), out_cap, jt
    )
    osel = verify_equal(keys_p, keys_b, pp, bp, osel)
    assert not bool(ovf)
    return _live(pp, bp, osel)


def _live(pp, bp, osel):
    pp, bp, osel = np.asarray(pp), np.asarray(bp), np.asarray(osel)
    return list(zip(pp[osel].tolist(), bp[osel].tolist()))


class TestBuildTable:
    def test_distinct_keys_place_at_4x_load(self):
        n = 1024
        h, _ = hash_keys(_keys(np.arange(n) * 7 + 3))
        table, ovf = DJ.build_table(
            DJ.slot_base_hash(h, 4096),
            jnp.ones(n, jnp.bool_), jnp.ones(n, jnp.bool_), 4096,
        )
        assert not bool(ovf)
        t = np.asarray(table)
        live = t[t != np.iinfo(np.int32).max]
        # every row placed exactly once
        assert sorted(live.tolist()) == list(range(n))

    def test_overflow_rehashes_clean_at_doubled_capacity(self):
        """Graceful overflow: a too-small table trips the flag; doubling
        the capacity (what the executor's retry ladder does) re-spreads
        the slot bases and the SAME rows place — no interpreter, and the
        join emitted from the larger table equals the sort tier."""
        n = 1024
        rng = np.random.default_rng(3)
        bk = rng.integers(0, 1 << 40, n)
        pk = np.concatenate([bk[: n // 2], rng.integers(0, 1 << 40, n)])
        h, _ = hash_keys(_keys(bk))
        ones = jnp.ones(n, jnp.bool_)
        _, ovf = DJ.build_table(DJ.slot_base_hash(h, 512), ones, ones, 512)
        assert bool(ovf), "1024 rows cannot fit a 512-slot table"
        cap = 512
        while bool(
            DJ.build_table(DJ.slot_base_hash(h, cap), ones, ones, cap)[1]
        ):
            cap *= 2
            assert cap <= 8192, "doubling never converged"
        ps = np.ones(pk.shape[0], bool)
        bs = np.ones(n, bool)
        want = _sort_pairs(_keys(pk), _keys(bk), ps, bs, 4096, "inner")
        got = _dense_pairs(_keys(pk), _keys(bk), ps, bs, 4096, "inner", cap)
        assert sorted(got) == sorted(want)

    def test_null_keys_never_match(self):
        """NULL build keys stay out of the table; NULL probe keys match
        nothing (inner) but still emit their outer row (left)."""
        bk = _keys([1, 2, 3, 2], valid=[True, False, True, True])
        pk = _keys([2, 1, 9], valid=[True, True, False])
        ps, bs = np.ones(3, bool), np.ones(4, bool)
        inner = _dense_pairs(pk, bk, ps, bs, 16, "inner", 64)
        assert sorted(inner) == [(0, 3), (1, 0)]  # null build row 1 absent
        left = _dense_pairs(pk, bk, ps, bs, 16, "left", 64)
        assert sorted(left) == [(0, 3), (1, 0), (2, MISSING)]
        assert sorted(inner) == sorted(
            _sort_pairs(pk, bk, ps, bs, 16, "inner")
        )
        assert sorted(left) == sorted(_sort_pairs(pk, bk, ps, bs, 16, "left"))

    def test_dup_key_tie_order_is_ascending_build_id(self):
        """Duplicate build keys: both the jnp round-based scatter-min and
        the Pallas sequential insertion place equal keys in ascending row
        id along the probe window, so a probing row emits its matches in
        ascending build position — deterministic without a sort."""
        bk = _keys([5, 7, 5, 5, 7])
        pk = _keys([5, 7])
        ps, bs = np.ones(2, bool), np.ones(5, bool)
        got = _dense_pairs(pk, bk, ps, bs, 16, "inner", 64)
        assert got == [(0, 0), (0, 2), (0, 3), (1, 1), (1, 4)]

    def test_dup_chain_overflow_survives_capacity_growth(self):
        """The demotion rationale: 40 copies of one key share one slot
        base at EVERY capacity, so the chain can never fit the static
        16-entry probe window — growth is fruitless and the executor
        demotes the site to the sort tier after two doublings."""
        n = 40
        h, _ = hash_keys(_keys(np.full(n, 12345)))
        ones = jnp.ones(n, jnp.bool_)
        for cap in (64, 128, 256, 1024):
            _, ovf = DJ.build_table(DJ.slot_base_hash(h, cap), ones, ones, cap)
            assert bool(ovf), f"dup chain placed at capacity {cap}?"


class TestMatmulTier:
    def test_counts_equal_gather_lowering(self):
        rng = np.random.default_rng(5)
        dom = 256
        pb = jnp.asarray(rng.integers(0, dom, 5000), jnp.int32)
        bb = jnp.asarray(rng.integers(0, dom, 3000), jnp.int32)
        pu = jnp.asarray(rng.random(5000) < 0.8)
        bu = jnp.asarray(rng.random(3000) < 0.8)
        got = DJ.matmul_join_counts(pb, bb, pu, bu, dom)
        hist = np.bincount(np.asarray(bb)[np.asarray(bu)], minlength=dom)
        want = np.where(np.asarray(pu), hist[np.asarray(pb)], 0)
        assert np.array_equal(np.asarray(got), want)

    def test_identity_binning_is_collision_free(self):
        """Dense key domain <= capacity: slot_base_binned is a perfect
        hash — zero displacement, no overflow, matches the sort tier."""
        bk = np.arange(100, 164)  # 64 distinct keys, domain 64
        pk = np.array([100, 163, 99, 164, 130, 130])
        kmin = jnp.int64(100)
        bbase = DJ.slot_base_binned(jnp.asarray(bk), kmin, 64)
        assert np.array_equal(np.asarray(bbase), np.arange(64))
        ones = jnp.ones(64, jnp.bool_)
        table, ovf = DJ.build_table(bbase, ones, ones, 64)
        assert not bool(ovf)
        bh, _ = hash_keys(_keys(bk))
        ph, pv = hash_keys(_keys(pk))
        pbase = DJ.slot_base_binned(jnp.asarray(pk), kmin, 64)
        pp, bp, osel, _, ovf = DJ.probe_table(
            table, bh, pbase, ph, pv, jnp.ones(6, jnp.bool_), 16, "inner"
        )
        osel = verify_equal(_keys(pk), _keys(bk), pp, bp, osel)
        assert not bool(ovf)
        want = _sort_pairs(
            _keys(pk), _keys(bk), np.ones(6, bool), np.ones(64, bool),
            16, "inner",
        )
        assert sorted(_live(pp, bp, osel)) == sorted(want)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("jt", ["inner", "left"])
def test_dense_equals_sort_kernel(seed, jt):
    """The kernel acceptance loop: random keys with duplicates, NULLs and
    partial selection — the dense tier's live (probe, build) row set is
    bit-identical to the sort tier's for both join types."""
    rng = np.random.default_rng(seed)
    nb, npr = 700, 900
    bk = rng.integers(0, 400, nb)
    pk = rng.integers(0, 500, npr)
    bvalid = rng.random(nb) < 0.95
    pvalid = rng.random(npr) < 0.95
    bsel = rng.random(nb) < 0.8
    psel = rng.random(npr) < 0.8
    cap = 4096  # 4x the live build rows, the executor default load
    out = 8192
    want = _sort_pairs(
        _keys(pk, pvalid), _keys(bk, bvalid), psel, bsel, out, jt
    )
    got = _dense_pairs(
        _keys(pk, pvalid), _keys(bk, bvalid), psel, bsel, out, jt, cap
    )
    assert sorted(got) == sorted(want)
    assert len(want) > 0


class TestCapsDemotion:
    def test_two_fruitless_grows_demote_and_rekey_the_trace(self):
        from trino_tpu.exec.fragments import _Caps

        caps = _Caps()
        caps.get("densejoin123", 64)
        caps.get("join123", 1024)
        sig0 = caps.signature()
        caps.grow("densejoin123")
        assert "densejoin123" not in caps.demoted
        caps.grow("densejoin123")
        assert "densejoin123" in caps.demoted
        # the demotion set feeds the program signature: the retrace that
        # drops the table must key a NEW traced program
        assert caps.signature() != sig0
        assert caps.vals["densejoin123"] == 256
        # ordinary join sites never demote
        for _ in range(3):
            caps.grow("join123")
        assert caps.demoted == {"densejoin123"}

    def test_demotion_counts_survive_node_id_churn(self):
        # every retrace mints a fresh ``densejoin{id(node)}`` runtime
        # name for the same logical join — fruitless-grow counting must
        # ride the restart-stable alias or the ladder never demotes and
        # a dup-chain site exhausts CapacityRetryExceeded (TPC-DS q25)
        from trino_tpu.exec.fragments import _Caps

        caps = _Caps()
        caps.sites.update({"densejoin111": "densejoin@4#0"})
        caps.get("densejoin111", 64)
        caps.grow("densejoin111")
        assert not caps.demoted
        caps.sites.update({"densejoin222": "densejoin@4#0"})
        caps.get("densejoin222", 128)
        caps.grow("densejoin222")
        assert "densejoin@4#0" in caps.demoted

    def test_seeded_exposes_pending_floor(self):
        from trino_tpu.exec.fragments import _Caps

        caps = _Caps()
        assert caps.seeded("densejoin9") is None
        caps.seed("densejoin9", 2048, provenance="history")
        val, prov = caps.seeded("densejoin9")
        assert (val, prov) == (2048, "history")


# === end to end: strategies agree bit-identically =========================

STAR_SQL = """
    select i.i_category, d.d_year, sum(ss.ss_ext_sales_price) as s
    from tpcds.tiny.store_sales ss
    join tpcds.tiny.item i on ss.ss_item_sk = i.i_item_sk
    join tpcds.tiny.date_dim d on ss.ss_sold_date_sk = d.d_date_sk
    group by i.i_category, d.d_year
    order by i.i_category, d.d_year
"""


def _mem_tables(catalogs, n_facts=2000, n_dims=16, seed=7):
    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.connectors.api import ColumnSchema, TableSchema

    mem = catalogs.get("memory")
    rng = np.random.default_rng(seed)
    fk = rng.integers(1, n_dims + 1, n_facts).astype(np.int64)
    fv = rng.integers(0, 1000, n_facts).astype(np.int64)
    mem.create_table(
        "default", "facts",
        TableSchema("facts", (ColumnSchema("k", T.BIGINT),
                              ColumnSchema("v", T.BIGINT))))
    mem.insert("default", "facts",
               Batch([Column(T.BIGINT, fk), Column(T.BIGINT, fv)], n_facts))
    dk = np.arange(1, n_dims + 1, dtype=np.int64)
    mem.create_table(
        "default", "dims",
        TableSchema("dims", (ColumnSchema("k", T.BIGINT),
                             ColumnSchema("name", T.BIGINT))))
    mem.insert("default", "dims",
               Batch([Column(T.BIGINT, dk), Column(T.BIGINT, dk * 100)],
                     n_dims))


MEM_JOIN_SQL = ("select sum(f.v * d.name) as chk, count(*) as c "
                "from memory.default.facts f "
                "join memory.default.dims d on f.k = d.k")


E2E_QUERIES = {"q5": QUERIES[5], "q10": QUERIES[10], "star": STAR_SQL,
               "mem": MEM_JOIN_SQL}


@pytest.fixture(scope="module")
def strategy_runners():
    made = {}

    def get(strategy):
        if strategy not in made:
            r = DistributedQueryRunner()
            r.session.set("join_distribution_type", "PARTITIONED")
            r.session.set("join_strategy", strategy)
            _mem_tables(r.catalogs)
            made[strategy] = r
        return made[strategy]

    return get


@pytest.fixture(scope="module")
def interpreter_ref():
    # lazy per-query: a `-m 'not slow'` run never pays for the q10
    # interpreter reference it would not compare against
    r = LocalQueryRunner()
    _mem_tables(r.catalogs)
    cache = {}

    def get(k):
        if k not in cache:
            cache[k] = r.execute(E2E_QUERIES[k])[0]
        return cache[k]

    return get


# every strategy on the star query; auto and sort on Q5 (`auto` resolves
# to `sort` since PR 36, the chip's readings: `_join_strategy`, so the
# table tiers are covered by their pins on the star and the memory join;
# `auto` on the memory join is MORE_E2E_CASES, which
# tests/test_join_rule.py runs through the same function). q10 repeats the
# q5 evidence on a second join spine, so it rides in the slow lane. The
# small memory join runs under every tier, and there the ladder has to
# stay at rest as well.
E2E_CASES = [
    ("auto", "q5"), ("sort", "q5"),
    pytest.param("auto", "q10", marks=pytest.mark.slow),
    pytest.param("sort", "q10", marks=pytest.mark.slow),
    ("auto", "star"), ("sort", "star"), ("dense", "star"),
    ("sort", "mem"), ("dense", "mem"), ("matmul", "mem"),
]
MORE_E2E_CASES = [("auto", "mem")]


@pytest.mark.parametrize("strategy,qkey", E2E_CASES)
def test_strategies_bit_identical(strategy, qkey, strategy_runners,
                                  interpreter_ref):
    """Acceptance: TPC-H Q5/Q10 and the TPC-DS star query return
    bit-identical rows across join_strategy auto/sort/dense, and all
    match the single-node interpreter."""
    r = strategy_runners(strategy)
    res = r.engine.execute_statement(E2E_QUERIES[qkey], r.session)
    assert res.rows == interpreter_ref(qkey), f"{strategy} diverged on {qkey}"
    if qkey == "mem":
        # 16 build rows at the engineered load: the tier that was asked for
        # answers in compiled programs at its first capacities, with no
        # overflow, no re-hash and no demotion on the way
        ex = res.exchange_stats
        assert ex["overflow_retries"] == 0, ex
        assert ex["dispatchRoundTrips"] >= 1
        want = "sort" if strategy == "auto" else strategy
        assert set(ex["joinStrategy"].values()) == {want}, ex["joinStrategy"]


def star_query_fuses_multiway(pin, want):
    """Acceptance: under the default (broadcast) distribution the
    dimension builds fuse INTO the fact-probe program — one multiway
    fused star join in ONE dispatch round-trip, strictly more fragments
    fused and strictly fewer round-trips than with the dense tier off
    (broadcast links never fused pairwise), with the chosen strategy
    surfaced per site in exchangeStats.joinStrategy: sort-merge under
    ``auto`` (the fusion is ``dense_join``'s, not the kernel's), the table
    under its pin."""
    r = DistributedQueryRunner()
    r.session.set("join_strategy", pin)
    res = r.engine.execute_statement(STAR_SQL, r.session)
    ex = res.exchange_stats or {}

    rs = DistributedQueryRunner()
    rs.session.set("dense_join", False)  # pairwise reference plan
    res_s = rs.engine.execute_statement(STAR_SQL, rs.session)
    ex_s = res_s.exchange_stats or {}

    assert res.rows == res_s.rows
    strategies = ex.get("joinStrategy") or {}
    assert strategies, "no per-site join strategies surfaced"
    assert set(strategies.values()) == {want}
    assert all(s.startswith("densejoin@") for s in strategies)
    assert ex.get("dispatchRoundTrips", 99) == 1, ex
    assert ex.get("fusedFragments", 0) > ex_s.get("fusedFragments", 0)
    assert ex.get("dispatchRoundTrips", 99) < ex_s.get(
        "dispatchRoundTrips", 0
    )


def test_star_query_fuses_multiway():
    star_query_fuses_multiway("auto", "sort")


def test_matmul_strategy_pinned_by_session(tmp_path):
    """join_strategy=matmul on a single integer key: the identity-binned
    table runs and matches the sort tier bit-identically."""
    r = LocalQueryRunner()
    _mem_tables(r.catalogs)
    props = {"execution_mode": "distributed"}
    mm = r.engine.execute_statement(
        MEM_JOIN_SQL,
        Session(properties={**props, "join_strategy": "matmul"}))
    st = r.engine.execute_statement(
        MEM_JOIN_SQL,
        Session(properties={**props, "join_strategy": "sort"}))
    assert mm.rows == st.rows
    strategies = (mm.exchange_stats or {}).get("joinStrategy") or {}
    assert "matmul" in set(strategies.values()), strategies


def test_warm_repeat_zero_overflow_retries(tmp_path):
    """The PR-15 loop through the dense tier, under its explicit pin
    (``auto`` answers ``sort`` since PR 36): a history-halved
    ``densejoin@…`` site forces ONE graceful in-ladder re-hash (never
    the interpreter); the grown truth is recorded, and a FRESH engine
    sharing only the history_dir repeats with ZERO overflow retries off
    a history-provenance seed — bit-identical rows throughout. The seed
    is a capacity and nothing more: ``auto`` beside it stays on sort."""
    def _props(**extra):
        return {
            "execution_mode": "distributed",
            "history_dir": str(tmp_path),
            "join_strategy": "dense",
            **extra,
        }

    from trino_tpu.obs.history import QueryHistoryStore

    cold_runner = LocalQueryRunner()
    _mem_tables(cold_runner.catalogs)
    cold = cold_runner.engine.execute_statement(
        MEM_JOIN_SQL, Session(properties=_props()))
    assert cold.exchange_stats["overflow_retries"] == 0
    assert set(
        (cold.exchange_stats.get("joinStrategy") or {}).values()
    ) == {"dense"}

    store = QueryHistoryStore(str(tmp_path / "query_history.json"))
    entries = store.entries()
    assert len(entries) == 1
    fp, ent = entries[0]
    dj_sites = [s for s in ent["capacities"] if s.startswith("densejoin@")]
    assert dj_sites, f"no densejoin site recorded: {ent['capacities']}"
    # shrink the table site below the 16 live build rows: the next run
    # MUST overflow once and re-hash at doubled capacity (8 -> 16 holds
    # exactly the build set: n_live <= window guarantees placement)
    store.record(fp, {"capacities": {
        dj_sites[0]: {"value": 8, "provenance": "seeded+halved"}}})

    mid_runner = LocalQueryRunner()
    _mem_tables(mid_runner.catalogs)
    mid = mid_runner.engine.execute_statement(
        MEM_JOIN_SQL, Session(properties=_props()))
    assert mid.rows == cold.rows
    assert mid.exchange_stats["overflow_retries"] == 1
    # one re-hash, no demotion: the pinned tier answers
    strategies = mid.exchange_stats.get("joinStrategy") or {}
    assert set(strategies.values()) == {"dense"}, strategies

    # the in-ladder growth was the table site: the store now holds the
    # grown truth (8 -> 16) under the restart-stable densejoin site
    store2 = QueryHistoryStore(str(tmp_path / "query_history.json"))
    ent2 = dict(store2.entries())[fp]
    assert ent2["capacities"][dj_sites[0]]["value"] == 16
    assert "grown" in ent2["capacities"][dj_sites[0]]["provenance"]

    warm_runner = LocalQueryRunner()
    _mem_tables(warm_runner.catalogs)
    warm = warm_runner.engine.execute_statement(
        MEM_JOIN_SQL, Session(properties=_props()))
    assert warm.rows == cold.rows
    assert warm.exchange_stats["overflow_retries"] == 0
    # (the grown 16 is a floor below the engineered default of 1,024
    # slots: it never installs as the capacity itself, so nothing overflows)
    strategies = warm.exchange_stats.get("joinStrategy") or {}
    assert set(strategies.values()) == {"dense"}, strategies

    # the sort tier agrees bit-identically, closing the loop, and so does
    # auto with the history beside it: a seeded table site promotes nothing
    off_runner = LocalQueryRunner()
    _mem_tables(off_runner.catalogs)
    for pin in ("sort", "auto"):
        off = off_runner.engine.execute_statement(
            MEM_JOIN_SQL,
            Session(properties=_props(join_strategy=pin,
                                      query_history=pin == "auto")))
        assert off.rows == cold.rows
        assert set(off.exchange_stats["joinStrategy"].values()) == {"sort"}
