"""TPC-H Q18, the first query that came to the benchmark as files and nothing
else (a template, its mix, its reference function and the provider of two
columns), rehearsed at tpch.tiny in both sessions. No committed cell runs it
yet, so the cells are this fixture's."""

import glob
import json
import os
import re

import pytest

from benchmark import refdata, reference, traffic

from .conftest import REPO
from .test_harness import run

DATA_ROOT = os.path.join(REPO, "benchmark")
#: SF 0.01 has two orders over 280 (and over 300), 67 over 250 and more than
#: the LIMIT's hundred over 230: each of these returns rows, the lowest a cut
QUANTITIES = [230, 250, 280]


@pytest.fixture
def q18_root(tiny_root):
    data = os.path.join(tiny_root, "benchmark")
    mix = traffic.load_json(os.path.join(data, "traffic", "q18-stream.json"))
    mix["parameters"]["q18"]["QUANTITY"] = {"values": QUANTITIES}
    with open(os.path.join(data, "traffic", "q18-low.json"), "w") as f:
        json.dump(mix, f)
    bench = traffic.load_json(os.path.join(tiny_root, "BENCHMARK.json"))
    bench["workloads"] += [
        {"name": f"q18-tiny-{session}", "config": f"tpch-tiny-{session}", "traffic": "q18-low",
         "chips": 1, "why": "rehearsal"} for session in ("default", "compiled")]
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tiny_root


@pytest.mark.parametrize("session", ["default", "compiled"])
def test_q18_is_correct_in_both_sessions(q18_root, capsys, session):
    result, err = run(q18_root, capsys, f"q18-tiny-{session}", seconds=2.0)
    assert result["correct"] is True, err[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert all(c["value"] == 0 for c in result["compared"].values())


FAULTS = {
    "a row dropped": lambda rows: rows[:-1],
    "a row delivered twice": lambda rows: rows + rows[-1:],
    "rows out of order": lambda rows: rows[::-1],
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_an_altered_q18_answer_is_not_correct(q18_root, capsys, monkeypatch, fault):
    """The float32 control cannot fail Q18: every sum is of an order's seven
    lines at the most, under 2^24, so float32 holds it exactly (see
    ``test_the_float32_control_cannot_fail_q18``). These planted faults are
    what shows that the comparison can read false for it."""
    from trino_tpu import client

    plain = client.Connection.execute
    calls = {"n": 0}

    def broken(self, sql):
        rows, names = plain(self, sql)
        calls["n"] += 1
        if calls["n"] == 3:  # past the two warm-ups: the window's first answer
            assert len(rows) >= 2
            rows = FAULTS[fault](rows)
        return rows, names

    monkeypatch.setattr(client.Connection, "execute", broken)
    result, _ = run(q18_root, capsys, "q18-tiny-default", seconds=0.5)
    assert calls["n"] >= 3
    assert result["correct"] is False
    assert result["compared"]["answers_wrong"]["value"] == 1


def test_the_float32_control_cannot_fail_q18():
    reads = traffic.load_json(os.path.join(DATA_ROOT, "templates", "q18.json"))["reads"]
    tables = refdata.Dataset(DATA_ROOT, "tpch").load(0.01, reads)
    for quantity in QUANTITIES:
        exact, low = (reference.Reference(tables, p).answer("q18", {"QUANTITY": quantity})
                      for p in ("exact", "float32"))
        assert exact == low and exact["rows"]
    cut = reference.Reference(tables).answer("q18", {"QUANTITY": 150})
    assert len(cut["rows"]) == 100
    prices = [r[4] for r in cut["rows"]]
    assert prices == sorted(prices, reverse=True)
    assert tables["customer"]["c_name"][41] == b"Customer#000000042"


def test_q18_came_as_files_alone():
    """No Python file directly under benchmark/ names the query or the
    columns that came with it, and no committed cell or other mix uses it."""
    named = re.compile(r"q18|o_totalprice|c_name")
    for path in glob.glob(os.path.join(DATA_ROOT, "*.py")):
        with open(path, encoding="utf-8") as f:
            assert not named.search(f.read()), path
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        assert not named.search(f.read())
    for name in ("templates/q18.sql", "templates/q18.json", "traffic/q18-stream.json",
                 "references/q18.py", "datasets/tpch/totalprice_and_name.py"):
        assert os.path.isfile(os.path.join(DATA_ROOT, name)), name
