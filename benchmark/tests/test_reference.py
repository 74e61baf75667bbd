"""The reference over its own generated columns reproduces the published
TPC-H SF1 answers (the literals ``chip_smoke.py`` keeps, copied here), at the
validation parameters; and the control, the same in float32, does not."""

import os
from decimal import Decimal as D

import pytest

from benchmark import compare, control, reference, refdata, traffic

from .conftest import REPO

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
def sf1():
    return refdata.generate(1.0)


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_reference_reproduces_the_published_sf1_answers(sf1, name):
    meta = traffic.load_json(os.path.join(REPO, "benchmark", "templates", f"{name}.json"))
    got = reference.Reference(sf1).answer(meta["reference"], meta["validation"])
    assert got["rows"] == PUBLISHED[name]
    assert got["tie_rows"] == []
    assert sf1["lineitem"]["l_orderkey"].shape == (6001215,)


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_the_float32_control_misses_them(sf1, name):
    meta = traffic.load_json(os.path.join(REPO, "benchmark", "templates", f"{name}.json"))
    got = reference.Reference(sf1, "float32").answer(meta["reference"], meta["validation"])
    wrong, widest = compare.compare_answer(
        got["rows"], {"rows": PUBLISHED[name], "tie_rows": []}, meta["sort_key"])
    assert wrong >= 1 and 0 < widest < 1e-2


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_control_is_not_correct_at_a_size_a_test_can_hold(tiny_root, seed):
    """The control, kept as a test: every cell of the tiny root, 40 answers."""
    for workload in ("q1-tiny-compiled", "q6-tiny-compiled", "q3-tiny-compiled"):
        verdict = control.control_verdict(tiny_root, workload, seed, 40)
        assert verdict["correct"] is False
        assert verdict["compared"]["answers_wrong"]["value"] >= 1, workload
        assert verdict["compared"]["answers_missing"]["value"] == 0


def test_refdata_cache_round_trips(tmp_path):
    a = refdata.load(0.01, ["lineitem", "customer"], str(tmp_path))
    b = refdata.load(0.01, ["lineitem", "customer"], str(tmp_path))
    assert os.listdir(tmp_path) == ["refdata-sf0.01.npz"]
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
