"""Golden tests for the kernel substrate vs NumPy reference computations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column, Dictionary
from trino_tpu.compiler import ExprCompiler, days_from_civil
from trino_tpu.ir import call, const, input_ref, special
from trino_tpu.ops.aggregation import (
    DOMAIN_MAX_SLOTS,
    AggSpec,
    domain_slots,
    global_aggregate,
    group_aggregate,
)
from trino_tpu.ops.join import (
    build_side,
    hash_keys,
    merge_rank,
    probe_join,
    slot_owner,
    verify_equal,
    MISSING,
)
from trino_tpu.ops.sort import SortKey, sort_indices


def _col(t, values):
    return Column.from_values(t, values)


class TestColumnar:
    def test_roundtrip_ints(self):
        b = Batch([_col(T.BIGINT, [1, None, 3])], 3)
        assert b.to_pylist() == [(1,), (None,), (3,)]

    def test_roundtrip_strings(self):
        b = Batch([_col(T.VARCHAR, ["a", "b", "a", None])], 4)
        assert b.to_pylist() == [("a",), ("b",), ("a",), (None,)]

    def test_roundtrip_decimal(self):
        from decimal import Decimal

        b = Batch([_col(T.decimal(10, 2), ["1.25", None, "3.5"])], 3)
        assert b.to_pylist() == [(Decimal("1.25"),), (None,), (Decimal("3.50"),)]

    def test_roundtrip_date(self):
        b = Batch([_col(T.DATE, ["1995-03-15", None])], 2)
        assert b.to_pylist() == [("1995-03-15",), (None,)]

    def test_compact_with_sel(self):
        col = _col(T.BIGINT, [1, 2, 3, 4])
        b = Batch([col], 4, sel=np.array([True, False, True, False]))
        assert b.compact().to_pylist() == [(1,), (3,)]


class TestExprCompiler:
    def test_arith_add(self):
        cols = [_col(T.BIGINT, [1, 2, None]), _col(T.BIGINT, [10, None, 30])]
        e = call(
            "add", T.BIGINT, input_ref(0, T.BIGINT), input_ref(1, T.BIGINT)
        )
        data, valid = ExprCompiler(cols).evaluate(e)
        np.testing.assert_array_equal(np.asarray(data)[:1], [11])
        np.testing.assert_array_equal(np.asarray(valid), [True, False, False])

    def test_decimal_multiply(self):
        dec = T.decimal(10, 2)
        cols = [_col(dec, ["2.50"]), _col(dec, ["0.10"])]
        rt = T.decimal(18, 4)
        e = call("multiply", rt, input_ref(0, dec), input_ref(1, dec))
        data, valid = ExprCompiler(cols).evaluate(e)
        assert int(data[0]) == 2500  # 0.2500 at scale 4

    def test_decimal_add_mixed_scale(self):
        a = T.decimal(10, 2)
        b = T.decimal(10, 0)
        rt = T.decimal(18, 2)
        cols = [_col(a, ["1.25"]), _col(b, ["3"])]
        e = call("add", rt, input_ref(0, a), input_ref(1, b))
        data, _ = ExprCompiler(cols).evaluate(e)
        assert int(data[0]) == 425

    def test_comparison_null_semantics(self):
        cols = [_col(T.BIGINT, [1, None, 3])]
        e = call("lt", T.BOOLEAN, input_ref(0, T.BIGINT), const(2, T.BIGINT))
        c = ExprCompiler(cols)
        mask = c.predicate_mask(e)
        np.testing.assert_array_equal(np.asarray(mask), [True, False, False])

    def test_kleene_and_or(self):
        cols = [_col(T.BOOLEAN, [True, False, None])]
        x = input_ref(0, T.BOOLEAN)
        e_and = special("and", T.BOOLEAN, x, const(True, T.BOOLEAN))
        d, v = ExprCompiler(cols).evaluate(e_and)
        np.testing.assert_array_equal(np.asarray(v), [True, True, False])
        e_or = special("or", T.BOOLEAN, x, const(False, T.BOOLEAN))
        d, v = ExprCompiler(cols).evaluate(e_or)
        np.testing.assert_array_equal(np.asarray(v), [True, True, False])
        # NULL AND FALSE is FALSE
        e2 = special("and", T.BOOLEAN, x, const(False, T.BOOLEAN))
        d, v = ExprCompiler(cols).evaluate(e2)
        assert bool(v[2]) and not bool(d[2] & v[2])

    def test_string_eq_and_like(self):
        cols = [_col(T.VARCHAR, ["BUILDING", "MACHINERY", "BUILDING"])]
        e = call(
            "eq", T.BOOLEAN, input_ref(0, T.VARCHAR), const("BUILDING", T.VARCHAR)
        )
        mask = ExprCompiler(cols).predicate_mask(e)
        np.testing.assert_array_equal(np.asarray(mask), [True, False, True])
        e2 = call(
            "like", T.BOOLEAN, input_ref(0, T.VARCHAR), const("%CHIN%", T.VARCHAR)
        )
        mask2 = ExprCompiler(cols).predicate_mask(e2)
        np.testing.assert_array_equal(np.asarray(mask2), [False, True, False])

    def test_string_order_compare(self):
        cols = [_col(T.VARCHAR, ["apple", "pear", "fig"])]
        e = call(
            "lt", T.BOOLEAN, input_ref(0, T.VARCHAR), const("grape", T.VARCHAR)
        )
        mask = ExprCompiler(cols).predicate_mask(e)
        np.testing.assert_array_equal(np.asarray(mask), [True, False, True])

    def test_date_compare_and_extract(self):
        cols = [_col(T.DATE, ["1995-03-15", "1998-12-01", "1992-01-02"])]
        cutoff = days_from_civil(1995, 3, 15)
        e = call("le", T.BOOLEAN, input_ref(0, T.DATE), const(cutoff, T.DATE))
        mask = ExprCompiler(cols).predicate_mask(e)
        np.testing.assert_array_equal(np.asarray(mask), [True, False, True])
        ey = call("year", T.BIGINT, input_ref(0, T.DATE))
        data, _ = ExprCompiler(cols).evaluate(ey)
        np.testing.assert_array_equal(np.asarray(data), [1995, 1998, 1992])
        em = call("month", T.BIGINT, input_ref(0, T.DATE))
        data, _ = ExprCompiler(cols).evaluate(em)
        np.testing.assert_array_equal(np.asarray(data), [3, 12, 1])

    def test_cast_decimal_to_double(self):
        dec = T.decimal(10, 2)
        cols = [_col(dec, ["1.25"])]
        e = call("cast", T.DOUBLE, input_ref(0, dec))
        data, _ = ExprCompiler(cols).evaluate(e)
        assert float(data[0]) == 1.25

    def test_between(self):
        cols = [_col(T.BIGINT, [1, 5, 10])]
        e = special(
            "between",
            T.BOOLEAN,
            input_ref(0, T.BIGINT),
            const(2, T.BIGINT),
            const(9, T.BIGINT),
        )
        mask = ExprCompiler(cols).predicate_mask(e)
        np.testing.assert_array_equal(np.asarray(mask), [False, True, False])

    def test_division_by_zero_yields_null(self):
        cols = [_col(T.BIGINT, [10]), _col(T.BIGINT, [0])]
        e = call("divide", T.BIGINT, input_ref(0, T.BIGINT), input_ref(1, T.BIGINT))
        _, valid = ExprCompiler(cols).evaluate(e)
        assert not bool(valid[0])


class TestGroupAggregate:
    def test_sum_count_by_key(self):
        rng = np.random.default_rng(0)
        n = 1000
        keys = rng.integers(0, 7, n)
        vals = rng.integers(0, 100, n)
        sel = rng.random(n) < 0.8
        (kd, kv), results, num_groups, overflow = group_aggregate(
            keys=[(jnp.asarray(keys), jnp.ones(n, bool))],
            sel=jnp.asarray(sel),
            agg_inputs=[(jnp.asarray(vals), jnp.ones(n, bool)), None],
            agg_specs=[AggSpec("sum"), AggSpec("count_star")],
            max_groups=16,
        )
        assert not bool(overflow)
        got = {}
        ng = int(num_groups)
        ssum, scnt = results[0]
        for g in range(ng):
            got[int(kd[0][g])] = (int(ssum[g]), int(results[1][g]))
        expect = {}
        for k in np.unique(keys[sel]):
            m = sel & (keys == k)
            expect[int(k)] = (int(vals[m].sum()), int(m.sum()))
        assert got == expect

    def test_null_keys_form_one_group(self):
        keys = jnp.asarray([1, 1, 2, 0, 0])
        kvalid = jnp.asarray([True, True, True, False, False])
        vals = jnp.asarray([10, 20, 30, 40, 50])
        (kd, kv), results, num_groups, _ = group_aggregate(
            keys=[(keys, kvalid)],
            sel=jnp.ones(5, bool),
            agg_inputs=[(vals, jnp.ones(5, bool))],
            agg_specs=[AggSpec("sum")],
            max_groups=8,
        )
        assert int(num_groups) == 3
        by_key = {}
        ssum, cnt = results[0]
        for g in range(3):
            key = int(kd[0][g]) if bool(kv[0][g]) else None
            by_key[key] = int(ssum[g])
        assert by_key == {1: 30, 2: 30, None: 90}

    def test_min_max_avg(self):
        keys = jnp.asarray([0, 0, 1, 1])
        vals = jnp.asarray([3.0, 1.0, 8.0, 2.0])
        valid = jnp.asarray([True, True, True, True])
        (kd, kv), results, ng, _ = group_aggregate(
            keys=[(keys, valid)],
            sel=jnp.ones(4, bool),
            agg_inputs=[(vals, valid), (vals, valid), (vals, valid)],
            agg_specs=[AggSpec("min"), AggSpec("max"), AggSpec("avg")],
            max_groups=4,
        )
        mins = {int(kd[0][g]): float(results[0][0][g]) for g in range(2)}
        maxs = {int(kd[0][g]): float(results[1][0][g]) for g in range(2)}
        avgs = {
            int(kd[0][g]): float(results[2][0][g]) / float(results[2][1][g])
            for g in range(2)
        }
        assert mins == {0: 1.0, 1: 2.0}
        assert maxs == {0: 3.0, 1: 8.0}
        assert avgs == {0: 2.0, 1: 5.0}

    def test_global_aggregate(self):
        vals = jnp.asarray([1.0, 2.0, 3.0, 4.0])
        valid = jnp.asarray([True, True, False, True])
        sel = jnp.asarray([True, True, True, False])
        res = global_aggregate(
            sel, [(vals, valid), None], [AggSpec("sum"), AggSpec("count_star")]
        )
        s, cnt = res[0]
        assert float(s) == 3.0 and int(cnt) == 2
        assert int(res[1]) == 3


# --- the domain path against the sort path ----------------------------------
# name -> (key kinds, masks, n, aggregates, max_groups, which path it takes).
# A key kind is ("dict", domain[, codes it draws from]) or ("bool",); None as
# the domain is a key whose caller knows none.
_ALL_INT = ["sum", "count", "count_star", "min", "max", "avg"]
_DOMAIN_CASES = {
    "one-dict-key": ([("dict", 5)], [False], 1000, _ALL_INT, 16, "domain"),
    "q1-shape-odd-n": ([("dict", 3), ("dict", 2)], [False, False], 1537,
                       ["sum128", "sum128w", "avg", "count_star"], 4096, "domain"),
    "masks-nulls-misses": ([("dict", 3), ("dict", 2)], [True, True], 2000,
                           _ALL_INT + ["sum128", "sum128w"], 64, "domain"),
    "one-mask-of-two": ([("dict", 4), ("dict", 3)], [False, True], 900,
                        ["sum", "min", "count"], 32, "domain"),
    "bool-keys": ([("bool",), ("dict", 3)], [True, False], 1200,
                  ["sum", "count_star", "max"], 16, "domain"),
    "bool-key-alone": ([("bool",)], [False], 700, ["sum", "min"], 2, "domain"),
    "empty-selection": ([("dict", 3), ("dict", 2)], [True, False], 512,
                        _ALL_INT + ["sum128"], 32, "domain"),
    "slots-no-row-hits": ([("dict", 20, (0, 7, 19))], [True], 1000,
                          _ALL_INT, 32, "domain"),
    "negative-and-extreme": ([("dict", 3)], [True], 800,
                             ["sum", "sum128", "sum128w", "min", "max"], 8, "domain"),
    "wide-minmax": ([("dict", 3), ("bool",)], [True, True], 1100,
                    ["minw", "maxw", "sum128w"], 16, "domain"),
    "int32-and-bool-inputs": ([("dict", 4)], [False], 600,
                              ["sum32", "min32", "maxbool", "minf", "maxf"], 8, "domain"),
    # one key without a mask spans domain + 1 slots
    "slots-under-constant": ([("dict", DOMAIN_MAX_SLOTS - 2)], [False], 3000,
                             ["sum", "count"], 2 * DOMAIN_MAX_SLOTS, "domain"),
    "slots-at-constant": ([("dict", DOMAIN_MAX_SLOTS - 1)], [False], 3000,
                          ["sum", "count"], 2 * DOMAIN_MAX_SLOTS, "domain"),
    "slots-over-constant": ([("dict", DOMAIN_MAX_SLOTS)], [False], 3000,
                            ["sum", "count"], 2 * DOMAIN_MAX_SLOTS, "sort"),
    "slots-over-max-groups": ([("dict", 3, (0, 2)), ("dict", 2, (0, 1))], [False, False], 500,
                              ["sum"], 8, "sort"),
    "float-sum": ([("dict", 3)], [False], 500, ["sumf", "count"], 8, "sort"),
    "float-avg": ([("dict", 3)], [False], 500, ["avgf"], 8, "sort"),
    "none-domain": ([("dict", 3), ("dict", None)], [False, False], 500,
                    ["sum", "count_star"], 16, "sort"),
    "no-domains-at-all": ([("dict", 3)], [True], 500, ["sum"], 8, "sort"),
}


def _domain_case(name, rng):
    kinds, masks, n, aggs, max_groups, path = _DOMAIN_CASES[name]
    keys, domains = [], []
    for kind, mask in zip(kinds, masks):
        if kind[0] == "bool":
            data = rng.random(n) < 0.4
            domains.append(2)
        else:
            dom = kind[1]
            pool = kind[2] if len(kind) > 2 else range(-1, dom or 3)
            data = rng.choice(np.asarray(list(pool), np.int32), n)
            domains.append(dom)
        keys.append((jnp.asarray(data),
                     jnp.asarray(rng.random(n) < 0.85) if mask else None))
    sel = rng.random(n) < (0.0 if name == "empty-selection" else 0.8)
    big = name == "negative-and-extreme"
    lo, hi = (-(2**63), 2**63 - 1) if big else (-1000, 1000)

    def ints(dtype=np.int64):
        return jnp.asarray(rng.integers(lo, hi, n, dtype=np.int64).astype(dtype))

    def valid():
        return jnp.asarray(rng.random(n) < 0.9) if rng.random() < 0.7 else None

    inputs, specs = [], []
    for a in aggs:
        if a == "count_star":
            inputs.append(None)
        elif a in ("sum128w", "minw", "maxw"):
            # few distinct hi lanes, so that the lo lane decides extremes
            hi_lane = rng.integers(-2, 2, n) if not big else rng.integers(lo, hi, n)
            inputs.append((jnp.stack([jnp.asarray(hi_lane, jnp.int64), ints()], axis=1),
                           valid()))
        elif a in ("sum32", "min32"):
            inputs.append((ints(np.int32), valid()))
        elif a == "maxbool":
            inputs.append((jnp.asarray(rng.random(n) < 0.5), valid()))
        elif a.endswith("f"):
            inputs.append((jnp.asarray(rng.normal(size=n)), valid()))
        else:
            inputs.append((ints(), valid()))
        specs.append(AggSpec(
            {"minw": "min", "maxw": "max", "sum32": "sum", "min32": "min",
             "maxbool": "max", "minf": "min", "maxf": "max", "sumf": "sum",
             "avgf": "avg"}.get(a, a)))
    if name == "no-domains-at-all":
        domains = None
    return keys, jnp.asarray(sel), inputs, specs, max_groups, domains, path


@pytest.mark.parametrize("name", list(_DOMAIN_CASES))
def test_domain_path_equals_sort_path(name):
    """``group_aggregate`` with ``key_domains`` against itself without: the
    same keys in the same order, every result lane, ``num_groups`` and
    ``overflow``, whichever path the gate takes; and the gate takes the one
    the case names."""
    keys, sel, inputs, specs, max_groups, domains, path = _domain_case(
        name, np.random.default_rng(sorted(_DOMAIN_CASES).index(name))
    )
    slots = domain_slots(keys, inputs, specs, max_groups, domains)
    assert (slots is not None) == (path == "domain")

    def run(key_domains):
        return group_aggregate(keys, sel, inputs, specs, max_groups, key_domains)

    traced = str(jax.make_jaxpr(lambda: run(domains))())
    assert (" sort[" in traced) == (path == "sort")
    if path == "sort":
        # the gate's refusal leaves the program a call without domains gets
        assert traced == str(jax.make_jaxpr(lambda: run(None))())

    (kd, kv), got, ng, ovf = run(domains)
    (wkd, wkv), want, wng, wovf = run(None)
    assert int(ng) == int(wng) and ng.dtype == wng.dtype
    assert bool(ovf) == bool(wovf) is False and ovf.dtype == wovf.dtype
    g = int(ng)
    if name == "empty-selection":
        assert g == 0
    if name == "slots-no-row-hits":
        assert g < slots
    for a, b in zip(kd + kv, wkd + wkv):
        assert a.dtype == b.dtype and a.shape == b.shape == (max_groups,)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for spec, a, b in zip(specs, got, want):
        if spec.kind in ("count", "count_star"):
            lanes = [(a, b, max_groups)]
        else:
            # a dead row's extreme is whatever the sort path's scan left there
            rows = g if spec.kind in ("min", "max") else max_groups
            lanes = [(a[0], b[0], rows), (a[1], b[1], max_groups)]
        for x, y, rows in lanes:
            assert x.dtype == y.dtype and x.shape == y.shape, (spec, x.dtype, y.dtype)
            np.testing.assert_array_equal(np.asarray(x)[:rows], np.asarray(y)[:rows])


_JOIN_CAP = 8192  # the property test's one out_capacity
_JOIN_FILL = _JOIN_CAP + 2  # probe rows that top the total up, one match each


def _join_case(case, rng):
    """(build keys, valid, sel, probe keys, valid, sel) of one named case:
    600 build rows, 1,100 probe rows, then ``_JOIN_FILL`` unselected probe
    rows that each match build row 0 alone, so a test can choose ``total``
    by selecting some of them and every case has the same shapes."""
    nb, npr = 600, 1100
    bvalid, bsel = np.ones(nb, bool), np.ones(nb, bool)
    pvalid, psel = np.ones(npr, bool), np.ones(npr, bool)
    if case == "distinct":
        bk = rng.permutation(4 * nb)[:nb]
        pk = rng.permutation(4 * nb)[:npr]
    elif case == "duplicates":
        bk = rng.integers(0, 7, nb)
        pk = rng.integers(0, 9, npr)
    elif case == "absent":
        bk = rng.integers(0, 300, nb)
        pk = rng.integers(300, 600, npr)
        pk[::50] = bk[:22]
    elif case == "nulls-unselected":
        bk = rng.integers(0, 200, nb)
        pk = rng.integers(0, 250, npr)
        bvalid, pvalid = rng.random(nb) < 0.7, rng.random(npr) < 0.7
        bsel, psel = rng.random(nb) < 0.6, rng.random(npr) < 0.6
    elif case == "short-build":
        bk = rng.integers(0, 200, nb)
        pk = rng.integers(0, 200, npr)
        bsel = np.arange(nb) < 37  # build_count far below the capacity
    elif case == "emit-gaps":
        # long stretches of rows that emit nothing between emitting rows
        bk = rng.integers(0, 40, nb)
        pk = np.where(np.arange(npr) % 97 == 5, rng.integers(0, 40, npr), -1)
        psel = rng.random(npr) < 0.9
    else:
        raise AssertionError(case)
    fill_key = 1 << 40
    bk[0], bvalid[0], bsel[0] = fill_key, True, True
    fill = np.full(_JOIN_FILL, fill_key)
    return (
        bk, bvalid, bsel,
        np.concatenate([pk, fill]),
        np.concatenate([pvalid, np.ones(_JOIN_FILL, bool)]),
        np.concatenate([psel, np.zeros(_JOIN_FILL, bool)]),
    )


def _probe_join_by_search(sbk, sbi, build_count, ph, pv, psel, cap, jt):
    """The probe as it was before the sort-merge kernel: three binary
    searches. The reference the kernel has to equal in every live slot."""
    use = pv & psel
    maxv = jnp.iinfo(jnp.int64).max
    keys = jnp.where(use, ph, maxv - 1)
    lo = jnp.searchsorted(sbk, keys, side="left")
    hi = jnp.searchsorted(sbk, keys, side="right")
    hi = jnp.minimum(hi, build_count)
    lo = jnp.minimum(lo, hi)
    counts = jnp.where(use, hi - lo, 0)
    emit = jnp.where(psel, jnp.maximum(counts, 1), 0) if jt == "left" else counts
    offsets = jnp.cumsum(emit) - emit
    total = offsets[-1] + emit[-1]
    t = jnp.arange(cap, dtype=emit.dtype)
    ppos = jnp.searchsorted(offsets + emit, t, side="right").astype(jnp.int32)
    ppos = jnp.minimum(ppos, emit.shape[0] - 1)
    slot = lo[ppos] + (t - offsets[ppos])
    bpos = jnp.where(
        counts[ppos] > 0, sbi[jnp.clip(slot, 0, sbi.shape[0] - 1)], MISSING
    )
    return (keys, offsets, emit), (ppos, bpos, t < total, total, total > cap)


class TestJoin:
    def test_inner_join_with_duplicates(self):
        build_keys = np.array([1, 2, 2, 3, 5], dtype=np.int64)
        probe_keys = np.array([2, 3, 4, 2, 1], dtype=np.int64)
        bk = [(jnp.asarray(build_keys), jnp.ones(5, bool))]
        pk = [(jnp.asarray(probe_keys), jnp.ones(5, bool))]
        bh, bv = hash_keys(bk)
        ph, pv = hash_keys(pk)
        sbk, sbi, cnt = build_side(bh, bv, jnp.ones(5, bool))
        ppos, bpos, osel, total, ovf = probe_join(
            sbk, sbi, cnt, ph, pv, jnp.ones(5, bool), out_capacity=16
        )
        osel = verify_equal(pk, bk, ppos, bpos, osel)
        assert not bool(ovf)
        pairs = sorted(
            (int(probe_keys[ppos[i]]), int(build_keys[bpos[i]]))
            for i in range(16)
            if bool(osel[i])
        )
        assert pairs == [(1, 1), (2, 2), (2, 2), (2, 2), (2, 2), (3, 3)]

    def test_left_join_emits_unmatched(self):
        build_keys = np.array([1], dtype=np.int64)
        probe_keys = np.array([1, 7], dtype=np.int64)
        bk = [(jnp.asarray(build_keys), jnp.ones(1, bool))]
        pk = [(jnp.asarray(probe_keys), jnp.ones(2, bool))]
        bh, bv = hash_keys(bk)
        ph, pv = hash_keys(pk)
        sbk, sbi, cnt = build_side(bh, bv, jnp.ones(1, bool))
        ppos, bpos, osel, total, ovf = probe_join(
            sbk, sbi, cnt, ph, pv, jnp.ones(2, bool), out_capacity=8, join_type="left"
        )
        osel = verify_equal(pk, bk, ppos, bpos, osel)
        rows = [
            (int(ppos[i]), int(bpos[i])) for i in range(8) if bool(osel[i])
        ]
        assert (0, 0) in rows
        assert (1, MISSING) in rows

    def test_null_keys_never_match(self):
        bk = [(jnp.asarray([1, 2]), jnp.asarray([True, False]))]
        pk = [(jnp.asarray([2, 1]), jnp.asarray([False, True]))]
        bh, bv = hash_keys(bk)
        ph, pv = hash_keys(pk)
        sbk, sbi, cnt = build_side(bh, bv, jnp.ones(2, bool))
        ppos, bpos, osel, total, ovf = probe_join(
            sbk, sbi, cnt, ph, pv, jnp.ones(2, bool), out_capacity=8
        )
        osel = verify_equal(pk, bk, ppos, bpos, osel)
        matches = [(int(ppos[i]), int(bpos[i])) for i in range(8) if bool(osel[i])]
        assert matches == [(1, 0)]

    def test_overflow_reported(self):
        bkeys = np.ones(8, dtype=np.int64)
        pkeys = np.ones(8, dtype=np.int64)
        bk = [(jnp.asarray(bkeys), jnp.ones(8, bool))]
        pk = [(jnp.asarray(pkeys), jnp.ones(8, bool))]
        bh, bv = hash_keys(bk)
        ph, pv = hash_keys(pk)
        sbk, sbi, cnt = build_side(bh, bv, jnp.ones(8, bool))
        _, _, _, total, ovf = probe_join(
            sbk, sbi, cnt, ph, pv, jnp.ones(8, bool), out_capacity=16
        )
        assert bool(ovf) and int(total) == 64

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("jt", ["inner", "left"])
    @pytest.mark.parametrize(
        "case",
        ["distinct", "duplicates", "absent", "nulls-unselected",
         "short-build", "emit-gaps"],
    )
    def test_sort_merge_probe_equals_binary_search(self, case, jt, seed):
        """``merge_rank`` is ``searchsorted`` left and right, ``slot_owner``
        the old search for the row that owns a slot, and ``probe_join`` the
        old probe in every live slot, with ``total`` equal to, one under and
        one over ``out_capacity``."""
        cap = _JOIN_CAP
        rng = np.random.default_rng(seed)
        bk, bvalid, bsel, pk, pvalid, psel = _join_case(case, rng)
        bh, bv = hash_keys([(jnp.asarray(bk), jnp.asarray(bvalid))])
        ph, pv = hash_keys([(jnp.asarray(pk), jnp.asarray(pvalid))])
        sbk, sbi, cnt = build_side(bh, bv, jnp.asarray(bsel))
        # keep the longest prefix of the case's rows that leaves room for
        # a fill row, then choose the total by the fill rows selected
        (_, _, emit), _ = _probe_join_by_search(
            sbk, sbi, cnt, ph, pv, jnp.asarray(psel), cap, jt
        )
        psel &= np.cumsum(np.asarray(emit)) <= cap - 2
        base = int(np.asarray(emit)[psel].sum())
        assert base > 2
        n_case = pk.shape[0] - _JOIN_FILL
        for total in (cap, cap - 1, cap + 1):
            sel = psel.copy()
            sel[n_case : n_case + total - base] = True
            sel = jnp.asarray(sel)
            (keys, offsets, emit), want = _probe_join_by_search(
                sbk, sbi, cnt, ph, pv, sel, cap, jt
            )
            assert int(want[3]) == total
            lo, hi = merge_rank(sbk, keys)
            np.testing.assert_array_equal(
                lo, jnp.searchsorted(sbk, keys, side="left")
            )
            np.testing.assert_array_equal(
                hi, jnp.searchsorted(sbk, keys, side="right")
            )
            got = probe_join(sbk, sbi, cnt, ph, pv, sel, cap, jt)
            live = np.asarray(want[2])
            assert live.sum() == min(total, cap)
            np.testing.assert_array_equal(got[2], live)
            assert int(got[3]) == total
            assert bool(got[4]) == bool(want[4]) == (total > cap)
            ppos, bpos = np.asarray(got[0]), np.asarray(got[1])
            np.testing.assert_array_equal(ppos[live], np.asarray(want[0])[live])
            np.testing.assert_array_equal(bpos[live], np.asarray(want[1])[live])
            np.testing.assert_array_equal(
                np.asarray(slot_owner(offsets, emit, cap))[live], ppos[live]
            )
            # dead slots: indices still in range
            assert ppos.min() >= 0 and ppos.max() < pk.shape[0]
            assert ((bpos == MISSING) | ((bpos >= 0) & (bpos < bk.shape[0]))).all()

    @pytest.mark.parametrize("jt", ["inner", "left"])
    def test_probe_join_lowers_without_a_loop(self, jt):
        """No ``while`` in the probe's program: no binary search, and no
        scan or scatter that lowers to one."""
        n_probe, n_build, cap = 6000, 2048, 8192
        spec = jax.ShapeDtypeStruct
        text = probe_join.lower(
            spec((n_build,), jnp.int64), spec((n_build,), jnp.int32),
            spec((), jnp.int32), spec((n_probe,), jnp.int64),
            spec((n_probe,), jnp.bool_), spec((n_probe,), jnp.bool_),
            cap, jt,
        ).as_text()
        assert "sort" in text and "scatter" in text
        assert "while" not in text


class TestSort:
    def test_multikey_asc_desc(self):
        a = np.array([2, 1, 2, 1], dtype=np.int64)
        b = np.array([10.0, 20.0, 30.0, 40.0])
        perm = sort_indices(
            [(jnp.asarray(a), jnp.ones(4, bool)), (jnp.asarray(b), jnp.ones(4, bool))],
            [SortKey(ascending=True), SortKey(ascending=False)],
            jnp.ones(4, bool),
        )
        order = [int(i) for i in perm]
        assert [int(a[i]) for i in order] == [1, 1, 2, 2]
        assert [float(b[i]) for i in order] == [40.0, 20.0, 30.0, 10.0]

    def test_nulls_last_default(self):
        a = np.array([3, 1, 2], dtype=np.int64)
        valid = np.array([True, False, True])
        perm = sort_indices(
            [(jnp.asarray(a), jnp.asarray(valid))],
            [SortKey(ascending=True)],
            jnp.ones(3, bool),
        )
        assert [int(i) for i in perm] == [2, 0, 1]

    def test_negative_floats_desc(self):
        b = np.array([-1.5, 2.0, -3.0, 0.0])
        perm = sort_indices(
            [(jnp.asarray(b), jnp.ones(4, bool))],
            [SortKey(ascending=False)],
            jnp.ones(4, bool),
        )
        assert [float(b[int(i)]) for i in perm] == [2.0, 0.0, -1.5, -3.0]


class TestReviewRegressions:
    def test_float_modulus(self):
        cols = [_col(T.DOUBLE, [7.5]), _col(T.DOUBLE, [2.0])]
        e = call("modulus", T.DOUBLE, input_ref(0, T.DOUBLE), input_ref(1, T.DOUBLE))
        d, v = ExprCompiler(cols).evaluate(e)
        assert float(d[0]) == 1.5

    def test_date_vs_timestamp_compare(self):
        dcol = _col(T.DATE, ["1995-03-15"])
        ts = _col(T.TIMESTAMP, [days_from_civil(1995, 3, 15) * 86_400_000_000 + 1])
        e = call("le", T.BOOLEAN, input_ref(0, T.DATE), input_ref(1, T.TIMESTAMP))
        mask = ExprCompiler([dcol, ts]).predicate_mask(e)
        assert bool(mask[0])
        e2 = call("gt", T.BOOLEAN, input_ref(0, T.DATE), input_ref(1, T.TIMESTAMP))
        assert not bool(ExprCompiler([dcol, ts]).predicate_mask(e2)[0])

    def test_round_half_up_double(self):
        cols = [_col(T.DOUBLE, [2.5, 3.5, -2.5])]
        e = call("round", T.DOUBLE, input_ref(0, T.DOUBLE))
        d, _ = ExprCompiler(cols).evaluate(e)
        assert [float(x) for x in d] == [3.0, 4.0, -3.0]
        e2 = call("cast", T.BIGINT, input_ref(0, T.DOUBLE))
        d2, _ = ExprCompiler(cols).evaluate(e2)
        assert [int(x) for x in d2] == [3, 4, -3]

    def test_exact_decimal_ingest_large(self):
        from decimal import Decimal

        v = "12345678901234567.89"
        c = _col(T.decimal(18, 2), [v])
        assert int(c.data[0]) == 1234567890123456789
        b = Batch([c], 1)
        assert b.to_pylist() == [(Decimal(v),)]
