"""``q5-compiled``: TPC-H Q5 at its validation parameters in the compiled
session (configuration ``tpch-sf1-joins``). The reference over the columns
the new provider gives reproduces the specification's published SF1 answer
and the float32 control misses it; the cell is found by name with no edit to
the harness; its two readers read a recorded sample of ``GET /v1/query`` and
nothing of a program without the counters; and Q5 is rehearsed at tpch.tiny
in both sessions through ``harness.main``."""

import json
import os
from decimal import Decimal as D

import pytest

from benchmark import compare, harness, refdata, reference, traffic

from .conftest import REPO
from .test_contract import NAME, line
from .test_harness import run

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
DATA = os.path.join(REPO, "benchmark")
READERS = ("join_out_slots", "build_rows")
#: TPC-H rev 3, 2.4.5.3's validation answer at SF1 (REGION ASIA, DATE 1994-01-01)
PUBLISHED = [
    ("INDONESIA", D("55502041.1697")),
    ("VIETNAM", D("55295086.9967")),
    ("CHINA", D("53724494.2566")),
    ("INDIA", D("52035512.0002")),
    ("JAPAN", D("45410175.6954")),
]


@pytest.fixture(scope="module")
def meta():
    return traffic.load_json(os.path.join(DATA, "templates", "q5.json"))


@pytest.fixture(scope="module")
def sf1(meta):
    return refdata.Dataset(DATA, "tpch").load(1.0, meta["reads"])


def test_the_reference_reproduces_the_published_sf1_answer(sf1, meta):
    got = reference.Reference(sf1).answer("q5", meta["validation"])
    assert got == {"rows": PUBLISHED, "tie_rows": []}
    assert sf1["lineitem"]["l_suppkey"].shape == (6001215,)
    assert sf1["nation"]["n_nationkey"].tolist() == list(range(25))


def test_the_float32_control_is_not_correct_at_sf1(sf1, meta):
    """Revenues of 5.5e7 at scale 4 are past float32's 24 bits."""
    got = reference.Reference(sf1, "float32").answer("q5", meta["validation"])
    wrong, widest = compare.compare_answer(
        got["rows"], {"rows": PUBLISHED, "tie_rows": []}, meta["sort_key"])
    assert wrong >= 1 and 0 < widest < 1e-2


def test_the_provider_gives_each_column_once():
    tpch = refdata.Dataset(DATA, "tpch")
    given = {("lineitem", "l_suppkey"), ("customer", "c_nationkey"), ("supplier", "s_suppkey"),
             ("supplier", "s_nationkey"), ("nation", "n_nationkey"), ("nation", "n_name"),
             ("nation", "n_regionkey"), ("region", "r_regionkey"), ("region", "r_name")}
    for pair in given:
        assert os.path.basename(tpch.provider[pair].__file__) == "suppliers_nations_regions.py"
    assert tpch.labels["region"]["r_name"][2] == "ASIA"
    # every supplier of a line is one of its part's four (4.2.3)
    tiny = tpch.load(0.01, {"lineitem": ["l_suppkey"], "supplier": ["s_suppkey"]})
    assert set(tiny["lineitem"]["l_suppkey"].tolist()) <= set(tiny["supplier"]["s_suppkey"].tolist())


def test_the_configuration_and_the_cell_are_found_by_name():
    entry = next(c for c in BENCH["configs"] if c["name"] == "tpch-sf1-joins")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and line(entry["source"]) and line(entry["why"])
    held = json.load(open(os.path.join(REPO, entry["file"])))
    assert held["name"] == entry["name"] and held["source"] == entry["source"]
    assert sorted(held["reduced"]) == entry["reduced"] == ["scale_factor"]
    one = json.load(open(os.path.join(DATA, "configs", "tpch-sf1-compiled.json")))
    for key in ("catalog", "schema", "scale_factor", "dataset", "session", "rows",
                "value_bytes", "guarantees"):
        assert held[key] == one[key], key
    _, data_root, cell, _, mix = harness.load_cell(REPO, "q5-compiled")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("tpch-sf1-joins", "q5-validation", 1)
    assert line(cell["why"]) and list(mix.templates) == ["q5"]
    assert mix.grids["q5"] == [{"DATE": "1994-01-01", "REGION": "ASIA"}]
    for name in READERS:
        (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert m["workloads"] == ["q5-compiled", "q3-compiled"] and m["moves"] == "query_s"
        assert callable(harness.load_reader(data_root, name))


def test_the_readers_read_a_recorded_sample_and_nothing_of_a_parent():
    """``data/infos_q5_joins.json``: warm Q5 and Q3 of the served path at
    tpch.tiny on one device with lineitem streamed (``stream_scan_threshold_rows``
    1000, ``stream_device_chunk_rows`` 4096), as ``GET /v1/query`` listed them
    (the times are a CPU's and no one's metric)."""
    infos = json.load(open(os.path.join(os.path.dirname(__file__), "data", "infos_q5_joins.json")))
    slots = harness.load_reader(DATA, "join_out_slots")
    rows = harness.load_reader(DATA, "build_rows")
    for query in ("q5", "q3"):
        stats = [q["queryStats"] for q in infos[query]]
        assert slots({"infos": infos[query]}) == sum(s["joinOutSlots"] for s in stats) / len(stats)
        assert rows({"infos": infos[query]}) == sum(s["buildRows"] for s in stats) / len(stats)
        assert slots({"infos": infos[query]}) > 0 and rows({"infos": infos[query]}) > 0
    # a program without the counters, and a failed query, read nothing
    parent = {"state": "FINISHED", "queryStats": {"slabSteps": 3.0}}
    failed = dict(infos["q5"][0], state="FAILED")
    for read in (slots, rows):
        assert read({"infos": [parent, failed]}) is None
        assert read({"infos": []}) is None


@pytest.fixture
def q5_root(tiny_root):
    bench = traffic.load_json(os.path.join(tiny_root, "BENCHMARK.json"))
    bench["workloads"] += [
        {"name": f"q5-tiny-{session}", "config": f"tpch-tiny-{session}", "traffic": "q5-validation",
         "chips": 1, "why": "rehearsal"} for session in ("default", "compiled")]
    with open(os.path.join(tiny_root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tiny_root


@pytest.mark.parametrize("session", ["default", "compiled"])
def test_q5_is_correct_in_both_sessions(q5_root, capsys, session):
    result, err = run(q5_root, capsys, f"q5-tiny-{session}", seed=2**31 + 5, seconds=1.0)
    assert result["correct"] is True, err[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert all(c["value"] == 0 for c in result["compared"].values())
