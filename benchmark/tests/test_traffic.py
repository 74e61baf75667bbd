"""Each mix is deterministic in the seed and stays inside the specification's
parameter ranges (TPC-H 2.4.1.3, 2.4.3.3, 2.4.6.3)."""

import datetime
import itertools
import os
from decimal import Decimal

import pytest

from benchmark import traffic

from .conftest import REPO

DATA = os.path.join(REPO, "benchmark")
MIXES = ["q1-stream", "q3-stream", "q6-stream"]


def take(mix, seed, stream=0, n=400):
    return list(itertools.islice(mix.schedule(seed, stream), n))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_queries_other_seed_other_order(name):
    mix = traffic.Mix(DATA, name)
    big = 2**31 + 977
    assert take(mix, big) == take(traffic.Mix(DATA, name), big)
    assert take(mix, big) != take(mix, big + 1)
    # every seed sends the same set of queries: whole shuffles of one grid
    (template,) = mix.templates
    grid = len(mix.grids[template])

    def as_set(draws):
        return sorted(sorted(p.items()) for _, p in draws)

    assert as_set(take(mix, 1, n=grid)) == as_set(take(mix, 2, n=grid))
    assert as_set(take(mix, 1, n=grid)) == sorted(sorted(p.items()) for p in mix.grids[template])


def test_q1_delta_range():
    mix = traffic.Mix(DATA, "q1-stream")
    deltas = {p["DELTA"] for _, p in take(mix, 5)}
    assert deltas == set(range(60, 121))


def test_q6_parameter_ranges():
    mix = traffic.Mix(DATA, "q6-stream")
    draws = [p for _, p in take(mix, 5)]
    assert {p["DATE"] for p in draws} == {f"{y}-01-01" for y in range(1993, 1998)}
    assert {Decimal(p["DISCOUNT"]) for p in draws} == {Decimal(d) / 100 for d in range(2, 10)}
    assert {p["QUANTITY"] for p in draws} == {24, 25}
    assert len(mix.grids["q6"]) == 5 * 8 * 2


def test_q3_parameter_ranges():
    mix = traffic.Mix(DATA, "q3-stream")
    draws = [p for _, p in take(mix, 5)]
    days = {datetime.date.fromisoformat(p["DATE"]) for p in draws}
    assert days == {datetime.date(1995, 3, d) for d in range(1, 32)}
    assert {p["SEGMENT"] for p in draws} == {"BUILDING"}
    assert "SEGMENT" in mix.spec["assumed"]


@pytest.mark.parametrize("name", MIXES)
def test_sql_text_carries_the_parameters_and_the_schema(name):
    mix = traffic.Mix(DATA, name)
    (template,) = mix.templates.values()
    sql = template.sql("tpch.sf1", template.meta["validation"])
    assert "{" not in sql and "tpch.sf1.lineitem" in sql
    for value in template.meta["validation"].values():
        assert str(value) in sql


def test_q3_validation_sends_the_validation_query_whatever_the_seed():
    mix = traffic.Mix(DATA, "q3-validation")
    assert {tuple(sorted(p.items())) for s in (1, 2**31 + 3) for _, p in take(mix, s, n=5)} == {
        tuple(sorted(mix.templates["q3"].meta["validation"].items()))}
    assert {"DATE", "SEGMENT"} <= set(mix.spec["assumed"])


def test_open_loops_are_refused(tmp_path):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "x.json").write_text('{"loop": "open", "streams": [], "parameters": {}}')
    with pytest.raises(ValueError, match="closed"):
        traffic.Mix(str(tmp_path), "x")
