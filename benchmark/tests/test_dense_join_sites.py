"""``dense_join_sites`` (PR 36): its entry, its reader against a recorded
sample of ``GET /v1/query``, and what it reads of a program that lists no
join sites."""

import json
import os

from benchmark import harness

from .conftest import REPO

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
DATA = os.path.join(REPO, "benchmark")


def test_the_entry_lists_the_two_q3_cells():
    # (found by name: where it stands in the list is the next PR's to move)
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == "dense_join_sites"]
    assert entry == {
        "name": "dense_join_sites", "unit": "1/query", "better": "lower",
        "source": "program_counter", "layer": "executor", "moves": "query_s",
        "workloads": ["q3-compiled", "q3-mesh4"]}
    for cell in entry["workloads"]:
        assert harness.applies(entry, cell)
    assert not harness.applies(entry, "q3v-default")


def test_the_reader_counts_the_sites_off_sort_merge():
    """``data/infos_q3_joins.json``: two warm Q3 of the served path at
    tpch.tiny on one device under ``join_strategy=dense`` (what the parent's
    ``auto`` answered) and two under this PR's ``auto``, as ``GET /v1/query``
    listed them (the keys the Q3 cells' counter readers read; the times are a
    CPU's and no one's metric)."""
    infos = json.load(open(os.path.join(os.path.dirname(__file__), "data", "infos_q3_joins.json")))
    read = harness.load_reader(DATA, "dense_join_sites")
    assert all(q["state"] == "FINISHED" for side in infos.values() for q in side)
    assert read({"infos": infos["parent"]}) == 2.0
    assert read({"infos": infos["change"]}) == 0.0
    assert read({"infos": infos["parent"][:1] + infos["change"][:1]}) == 1.0
    # a matmul site counts; a failed query, and a program that lists no join
    # sites (the default session), read nothing and do not raise
    one = dict(infos["change"][0], exchangeStats={"joinStrategy": {
        "densejoin@3#0": "matmul", "densejoin@2#0": "sort"}})
    assert read({"infos": [one]}) == 1.0
    failed = dict(infos["parent"][0], state="FAILED")
    local = {"state": "FINISHED", "queryStats": {"phaseMs": {"execute": 3.0}},
             "exchangeStats": None}
    assert read({"infos": [failed, local]}) is None
    assert read({"infos": []}) is None
    assert read({"infos": [local] + infos["parent"]}) == 2.0
