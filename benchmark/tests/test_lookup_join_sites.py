"""``lookup_join_sites``: its entry, its reader over ``GET /v1/query``'s
``queryStats.lookupJoins``, and what it reads of a program without that
counter (the recorded sample of ``data/infos_q5_joins.json`` predates it)."""

import json
import os

from benchmark import harness

from .conftest import REPO

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
DATA = os.path.join(REPO, "benchmark")


def test_the_entry_lists_the_cells_whose_step_joins():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == "lookup_join_sites"]
    assert entry == {
        "name": "lookup_join_sites", "unit": "1/query", "better": "higher",
        "source": "program_counter", "layer": "executor", "moves": "query_s",
        "workloads": ["q5-compiled", "q3-compiled", "q3-mesh4"]}
    for cell in entry["workloads"]:
        assert harness.applies(entry, cell)
    assert not harness.applies(entry, "q1-compiled")


def test_the_reader_reads_the_counter_and_nothing_of_a_parent():
    infos = json.load(open(os.path.join(os.path.dirname(__file__), "data", "infos_q5_joins.json")))
    read = harness.load_reader(DATA, "lookup_join_sites")
    parent = infos["q5"] + infos["q3"]
    assert all("lookupJoins" not in q["queryStats"] for q in parent)
    assert read({"infos": parent}) is None
    change = [dict(q, queryStats={**q["queryStats"], "lookupJoins": n})
              for q, n in zip(parent, (3, 3, 1, 3))]
    assert read({"infos": change[:2]}) == 3.0
    assert read({"infos": change[1:3]}) == 2.0
    # a failed query, and one of a program with no slab join, count nothing
    failed = dict(change[0], state="FAILED", queryStats={"lookupJoins": 0})
    local = {"state": "FINISHED", "queryStats": {"phaseMs": {"execute": 3.0}}}
    assert read({"infos": [failed, local, change[0]]}) == 3.0
    assert read({"infos": []}) is None
