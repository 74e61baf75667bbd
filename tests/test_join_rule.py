"""What the compiled tier's joins run when the session says ``auto``
(``exec/fragments.py::_FragmentTracer._join_strategy``, PR 36): the kernel the
chip's readings name at the static shapes the trace sees, the explicit pins
and demotion as they were, Q3 through the slab loop on one device and on
four, and the slab programs without a join untouched."""

import types

import jax.numpy as jnp
import pytest

from trino_tpu.config import Session
from trino_tpu.exec.fragments import _Caps, _FragmentTracer
from trino_tpu.testing import DistributedQueryRunner, LocalQueryRunner

from test_dense_join import (  # noqa: F401  (the fixtures the function takes)
    MORE_E2E_CASES,
    interpreter_ref,
    star_query_fuses_multiway,
    strategy_runners,
    test_strategies_bit_identical as strategies_bit_identical,
)
from test_streaming import Q1, Q3, Q6, _mesh_runner

ONE_BIGINT = [(jnp.zeros(4, jnp.int64), jnp.ones(4, jnp.bool_))]
ONE_DOUBLE = [(jnp.zeros(4, jnp.float64), jnp.ones(4, jnp.bool_))]
TWO_BIGINT = ONE_BIGINT * 2


def _answer(keys=ONE_BIGINT, caps=None, site="densejoin7", **props):
    """``_join_strategy`` of a tracer that holds this session and these
    capacities, for a join node whose runtime capacity name is ``site``."""
    tracer = types.SimpleNamespace(
        session=Session(properties=props), caps=caps or _Caps())
    node = types.SimpleNamespace()
    # assigned, not ``setdefault``: a node of an earlier call is freed by now
    # and this one can be given its ``id()``, with the alias left behind
    tracer.caps.sites[f"densejoin{id(node)}"] = site
    return _FragmentTracer._join_strategy(tracer, node, keys)


@pytest.mark.parametrize("keys", [ONE_BIGINT, TWO_BIGINT, ONE_DOUBLE],
                         ids=["one-key", "two-keys", "a-double"])
def test_auto_answers_sort_merge(keys):
    """The readings left no boundary (sort-merge won at a 2,097,152-row build
    side, at 262,144 and at 1,024: ``scripts/join_crossover.py``), so the rule
    takes no shape and is asked none: Q3 below and the star and memory joins
    of ``test_dense_join.py`` run it at theirs."""
    assert _answer(keys) == "sort"
    assert _answer(keys, join_strategy="auto") == "sort"


@pytest.mark.parametrize("pin, keys, want", [
    ("sort", ONE_BIGINT, "sort"),
    ("dense", ONE_BIGINT, "dense"),
    ("dense", TWO_BIGINT, "dense"),
    ("matmul", ONE_BIGINT, "matmul"),
    # the matmul gate: one integer key lane, else the hashed table
    ("matmul", TWO_BIGINT, "dense"),
    ("matmul", ONE_DOUBLE, "dense"),
])
def test_a_pin_keeps_its_meaning(pin, keys, want):
    assert _answer(keys, join_strategy=pin) == want
    # the tier's switch off: every join is sort-merge, whatever the pin
    assert _answer(keys, join_strategy=pin, dense_join=False) == "sort"


@pytest.mark.parametrize("pin", ["auto", "sort", "dense", "matmul"])
def test_a_demoted_site_stays_on_sort_merge(pin):
    caps = _Caps()
    caps.demoted.add("densejoin@3#0")
    assert _answer(caps=caps, site="densejoin@3#0", join_strategy=pin) == "sort"
    # ... and only that site
    want = "sort" if pin in ("auto", "sort") else pin
    assert _answer(caps=caps, site="densejoin@4#0", join_strategy=pin) == want


def test_a_history_seed_no_longer_promotes_auto():
    """The promotion ``auto`` -> ``matmul`` off a history-seeded table
    capacity is gone with the readings: the seed stays what it is, a
    capacity, and the explicit pin is the one way to the table tiers."""
    caps = _Caps()
    node = types.SimpleNamespace()
    caps.seed(f"densejoin{id(node)}", 2048, floor_only=True, provenance="history")
    tracer = types.SimpleNamespace(session=Session(), caps=caps)
    assert _FragmentTracer._join_strategy(tracer, node, ONE_BIGINT) == "sort"


@pytest.mark.parametrize("strategy,qkey", MORE_E2E_CASES)
def test_more_strategies_bit_identical(strategy, qkey, strategy_runners, interpreter_ref):
    """``tests/test_dense_join.py``'s end-to-end check for the pair this PR
    adds: ``auto`` on the memory join, which has to answer ``sort`` with the
    ladder at rest."""
    strategies_bit_identical(strategy, qkey, strategy_runners, interpreter_ref)


def test_the_star_fuses_multiway_under_the_dense_pin_too():
    star_query_fuses_multiway("dense", "dense")


class _Spans:
    """A sink that keeps the attributes of the spans that name their joins."""

    def __init__(self):
        self.joins = []

    def record(self, span):
        if span.attrs.get("joins"):
            self.joins.append((span.name, span.attrs["joins"]))


@pytest.fixture()
def join_spans():
    from trino_tpu.obs.trace import get_tracer

    sink = _Spans()
    get_tracer().add_sink(sink)
    try:
        yield sink.joins
    finally:
        get_tracer().remove_sink(sink)


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("pin", ["auto", "dense"])
def test_q3_through_the_slab_names_its_kernels(devices, pin, join_spans):
    """Q3 at tpch.tiny in the compiled session: under ``auto`` both join sites
    (the slab step's and the fragment's below it) run sort-merge, say so in
    ``exchangeStats.joinStrategy`` and on the spans that cover their programs
    with the capacities they were chosen at, the stored programs of a warm
    query too; the rows are the interpreter's. ``dense`` still pins the table."""
    r = _mesh_runner(devices, **({} if pin == "auto" else {"join_strategy": pin}))
    sql = Q3.format(customer="tpch.tiny.customer", orders="tpch.tiny.orders")
    want = "sort" if pin == "auto" else pin
    cold = r.engine.execute_statement(sql, r.session)
    del join_spans[:]
    warm = r.engine.execute_statement(sql, r.session)
    assert cold.rows == warm.rows == LocalQueryRunner(engine=r.engine).execute(sql)[0]
    assert warm.trace_count == 0
    for res in (cold, warm):
        sites = res.exchange_stats["joinStrategy"]
        assert sorted(sites) == ["densejoin@2#0", "densejoin@3#0"], sites
        assert set(sites.values()) == {want}
    by_span = dict(join_spans)
    (slab,) = by_span["stream.slab"]
    assert slab["site"] == "densejoin@2#0" and slab["strategy"] == want
    # lineitem's step probes: 8,192 rows of each shard
    assert slab["probeCap"] == 8192 * devices and slab["buildCap"] >= 1024
    below = [j for name, joins in join_spans if name != "stream.slab" for j in joins]
    assert [(j["site"], j["strategy"]) for j in below] == [("densejoin@3#0", want)]
    assert below[0]["probeCap"] >= 15000 and below[0]["buildCap"] >= 1500


H2O_Q5 = "select id6, sum(v1), sum(v2), sum(v3) from h2o.g1_2e5_1e1.x group by id6 order by id6"


@pytest.mark.parametrize("name, sql", [
    ("Q1", Q1.format(90)),
    ("Q6", Q6.format(1994, 1995, "0.06", 24)),
    ("h2o q5", H2O_Q5),
])
def test_a_slab_program_without_a_join_never_asks_the_rule(name, sql, monkeypatch):
    """Q1's, Q6's and h2o q5's slab programs hold no ``Join``: the rule is
    not asked while they trace, so their StableHLO text is the same under
    this rule and under the parent's (``auto`` -> ``dense``)."""
    import jax
    import numpy as np

    from trino_tpu.exec import streaming as S

    asked, texts = [], {}
    orig = S.StreamingAggregator._slab_attempt

    def lowering(self, programs, slab, chunk_cols, num_rows, cap, span, meta=None):
        res = orig(self, programs, slab, chunk_cols, num_rows, cap, span, meta)
        _, meta, _ = programs[("slab", self.site, self.G, cap, slab is None, 1)]
        args = (self._init_state(meta), slab, np.int32(1), np.int64(num_rows[0]),
                self.params, ())
        texts.setdefault(rule, []).append(
            jax.jit(self._make_slab_program(meta, cap, chunk_cols)).lower(*args).as_text())
        return res

    def parents(self, node, lkeys):
        asked.append(node)
        return "dense"

    monkeypatch.setattr(S.StreamingAggregator, "_slab_attempt", lowering)
    for rule in (None, parents):
        if rule is not None:
            monkeypatch.setattr(_FragmentTracer, "_join_strategy", rule)
        r = DistributedQueryRunner(n_devices=1)
        r.session.set("stream_scan_threshold_rows", 1)
        r.session.set("stream_device_chunk_rows", 32768)
        r.engine.execute_statement(sql, r.session)
    assert not asked, name
    # (a budget outgrown on the way, as q5's is, is a program more in each)
    assert texts[None] and texts[None] == texts[parents], name
    assert all("stablehlo.while" in text for text in texts[None])
