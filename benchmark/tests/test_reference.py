"""The reference over its own generated columns reproduces the published
TPC-H SF1 answers (the literals ``chip_smoke.py`` keeps, copied here), at the
validation parameters; and the control, the same in float32, does not. The
columns and the answers are those of the parent of PR 32, whose generator
made all orders at once and whose reference held its queries in one file."""

import hashlib
import json
import os
from decimal import Decimal as D

import numpy as np
import pytest

from benchmark import compare, control, files, reference, refdata, traffic

from .conftest import REPO

DATA_ROOT = os.path.join(REPO, "benchmark")
PARENT = json.load(open(os.path.join(os.path.dirname(__file__), "data", "parent_pr31.json")))
#: the 14 columns the first three templates read
READS = refdata.by_table(k.split(".") for k in PARENT["columns"]["1"])

PUBLISHED = {
    "q6": [(D("123141078.2283"),)],
    "q1": [
        ("A", "F", D("37734107.00"), D("56586554400.73"), D("53758257134.8700"),
         D("55909065222.827692"), D("25.52"), D("38273.13"), D("0.05"), 1478493),
        ("N", "F", D("991417.00"), D("1487504710.38"), D("1413082168.0541"),
         D("1469649223.194375"), D("25.52"), D("38284.47"), D("0.05"), 38854),
        ("N", "O", D("74476040.00"), D("111701729697.74"), D("106118230307.6056"),
         D("110367043872.497010"), D("25.50"), D("38249.12"), D("0.05"), 2920374),
        ("R", "F", D("37719753.00"), D("56568041380.90"), D("53741292684.6040"),
         D("55889619119.831932"), D("25.51"), D("38250.85"), D("0.05"), 1478870),
    ],
    "q3": [
        (2456423, D("406181.0111"), "1995-03-05", 0),
        (3459808, D("405838.6989"), "1995-03-04", 0),
        (492164, D("390324.0610"), "1995-02-19", 0),
        (1188320, D("384537.9359"), "1995-03-09", 0),
        (2435712, D("378673.0558"), "1995-02-26", 0),
        (4878020, D("378376.7952"), "1995-03-12", 0),
        (5521732, D("375153.9215"), "1995-03-13", 0),
        (2628192, D("373133.3094"), "1995-02-22", 0),
        (993600, D("371407.4595"), "1995-03-05", 0),
        (2300070, D("367371.1452"), "1995-03-13", 0),
    ],
}


@pytest.fixture(scope="module")
def tpch():
    return refdata.Dataset(DATA_ROOT, "tpch")


@pytest.fixture(scope="module")
def sf1(tpch):
    return tpch.load(1.0, READS)


@pytest.fixture(scope="module")
def tiny(tpch):
    return tpch.load(0.01, READS)


def digest(text_or_bytes) -> str:
    return hashlib.sha256(text_or_bytes).hexdigest()


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_reference_reproduces_the_published_sf1_answers(sf1, name):
    meta = traffic.load_json(os.path.join(DATA_ROOT, "templates", f"{name}.json"))
    got = reference.Reference(sf1).answer(meta["reference"], meta["validation"])
    assert got["rows"] == PUBLISHED[name]
    assert got["tie_rows"] == []
    assert sf1["lineitem"]["l_orderkey"].shape == (6001215,)
    assert digest(repr(got).encode()) == PARENT["validation_answers_sf1"][name]


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_the_float32_control_misses_them(sf1, name):
    meta = traffic.load_json(os.path.join(DATA_ROOT, "templates", f"{name}.json"))
    got = reference.Reference(sf1, "float32").answer(meta["reference"], meta["validation"])
    wrong, widest = compare.compare_answer(
        got["rows"], {"rows": PUBLISHED[name], "tie_rows": []}, meta["sort_key"])
    assert wrong >= 1 and 0 < widest < 1e-2


@pytest.mark.parametrize("column", sorted(PARENT["columns"]["1"]))
@pytest.mark.parametrize("scale", ["0.01", "1"])
def test_every_column_is_the_parents_to_the_last_bit(request, scale, column):
    tables = request.getfixturevalue("sf1" if scale == "1" else "tiny")
    table, name = column.split(".")
    a = tables[table][name]
    assert [digest(a.tobytes()), str(a.dtype), len(a)] == PARENT["columns"][scale][column]


@pytest.mark.parametrize("mix_name", ["q1-stream", "q3-stream", "q3-validation", "q6-stream"])
def test_every_answer_of_the_committed_grids_is_the_parents(tiny, mix_name):
    mix = traffic.Mix(DATA_ROOT, mix_name)
    ref = reference.Reference(tiny)
    for name, grid in mix.grids.items():
        h = hashlib.sha256()
        for params in grid:
            h.update(repr(ref.answer(mix.templates[name].meta["reference"], params)).encode())
        assert [h.hexdigest(), len(grid)] == PARENT["grid_answers_sf0.01"][f"{mix_name}/{name}"]


def test_every_q1_of_the_stream_at_sf1_is_the_parents(sf1):
    mix = traffic.Mix(DATA_ROOT, "q1-stream")
    ref = reference.Reference(sf1)
    h = hashlib.sha256()
    for params in mix.grids["q1"]:
        h.update(repr(ref.answer("q1", params)).encode())
    assert [h.hexdigest(), len(mix.grids["q1"])] == PARENT["q1_stream_answers_sf1"]


@pytest.mark.parametrize("scale", ["0.01", "1"])
def test_q1s_running_sums_by_day_are_the_direct_sums(request, scale):
    """Q1's exact answers come from sums by group and ship day, added up
    through the days; the sums over the kept lines, one DELTA at a time, are
    what the parent computed and what the float32 control still does."""
    li = request.getfixturevalue("sf1" if scale == "1" else "tiny")["lineitem"]
    q1 = files.load_module(os.path.join(DATA_ROOT, "references", "q1.py"), "reference")
    ar = reference.Arithmetic("exact")
    kept_direct, kept_running = {}, {}
    deltas = [60, 90, 120, 2500] if scale == "1" else [*range(60, 121), -400, 0, 2437, 2600]
    for delta in deltas:
        last_day = reference.days("1998-12-01") - delta
        count, sums = q1.direct(li, ar, 6, last_day, kept_direct)
        count2, sums2 = q1.through_day(li, ar, 6, last_day, kept_running)
        assert [int(n) for n in count[:6]] == [int(n) for n in count2], delta
        for name in sums:
            assert sums[name][:6] == list(sums2[name]), (delta, name)
    assert sum(int(n) for n in count2) < len(li["l_shipdate"])  # the last DELTA cuts lines


@pytest.mark.parametrize("block_orders", [1, 7, 1000, 1 << 20])
def test_the_generator_gives_the_same_columns_whatever_the_block(tiny, block_orders):
    provider = files.load_module(
        os.path.join(DATA_ROOT, "datasets", "tpch", "orders_lines_customers.py"), "provider")
    scale = 0.01 if block_orders > 1 else 0.0005
    want = tiny if block_orders > 1 else provider.generate(scale, READS, {})
    got = provider.generate(scale, READS, {}, block_orders=block_orders)
    for table, columns in READS.items():
        for c in columns:
            assert got[table][c].dtype == want[table][c].dtype
            assert np.array_equal(got[table][c], want[table][c]), c


def test_only_what_is_asked_is_generated(tpch):
    got = tpch.load(0.01, {"lineitem": ["l_tax"], "customer": ["c_custkey"]})
    assert {t: sorted(c) for t, c in got.items()} == {
        "lineitem": ["l_tax"], "customer": ["c_custkey"]}
    assert got.labels["lineitem"]["l_returnflag"] == ("R", "A", "N")


def test_grouped_sums_any_number_of_rows(sf1):
    """3 x SF1's lineitem is 18,003,645 rows, past one block of 2^24: the
    sums are three times SF1's, and a value of 2^48 still raises."""
    li = sf1["lineitem"]
    ar = reference.Arithmetic("exact")
    charge = (li["l_extendedprice"] * (100 - li["l_discount"].astype(np.int64))
              * (100 + li["l_tax"].astype(np.int64)))
    group = li["l_returnflag"].astype(np.int64) * 2 + li["l_linestatus"]
    once = ar.grouped(charge, group, 6)
    assert once == [int(charge[group == g].sum()) for g in range(6)]
    thrice = ar.grouped(np.tile(charge, 3), np.tile(group, 3), 6)
    assert len(charge) * 3 > reference.GROUPED_BLOCK
    assert thrice == [3 * s for s in once]
    with pytest.raises(ValueError, match="out of range"):
        ar.grouped(np.array([1, 1 << 48]), np.array([0, 0]), 1)
    with pytest.raises(ValueError, match="out of range"):
        ar.grouped(np.array([-1]), np.array([0]), 1)


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_control_is_not_correct_at_a_size_a_test_can_hold(tiny_root, seed):
    """The control, kept as a test: every cell of the tiny root, 40 answers."""
    for workload in ("q1-tiny-compiled", "q6-tiny-compiled", "q3-tiny-compiled"):
        verdict = control.control_verdict(tiny_root, workload, seed, 40)
        assert verdict["correct"] is False
        assert verdict["compared"]["answers_wrong"]["value"] >= 1, workload
        assert verdict["compared"]["answers_missing"]["value"] == 0


def test_refdata_cache_round_trips(tpch, tmp_path):
    """A column a file, plain arrays; a second load generates nothing."""
    reads = {"lineitem": ["l_shipdate", "l_tax"], "customer": ["c_mktsegment"]}
    a = tpch.load(0.01, reads, str(tmp_path))
    assert sorted(os.listdir(tmp_path / "tpch-sf0.01")) == [
        "customer.c_mktsegment.npy", "lineitem.l_shipdate.npy", "lineitem.l_tax.npy"]
    provider = tpch.provider["lineitem", "l_tax"]
    plain, provider.generate = provider.generate, None  # a call would raise
    try:
        b = tpch.load(0.01, reads, str(tmp_path))
    finally:
        provider.generate = plain
    for table in a:
        for col in a[table]:
            assert (a[table][col] == b[table][col]).all()
            assert a[table][col].dtype == b[table][col].dtype


def test_ties_on_the_order_by_are_left_open():
    ref = {"rows": [(1, D("9.0"), "d", 0), (2, D("8.0"), "d", 0)],
           "tie_rows": [(2, D("8.0"), "d", 0), (3, D("8.0"), "d", 0)]}
    key = [1, 2]
    assert compare.compare_answer(ref["rows"], ref, key) == (0, 0.0)
    assert compare.compare_answer([ref["rows"][0], (3, D("8.0"), "d", 0)], ref, key) == (0, 0.0)
    wrong, _ = compare.compare_answer([ref["rows"][0], (4, D("8.0"), "d", 0)], ref, key)
    assert wrong == 1
    wrong, _ = compare.compare_answer([ref["rows"][1], ref["rows"][0]], ref, key)
    assert wrong >= 1  # the ORDER BY decides these two
