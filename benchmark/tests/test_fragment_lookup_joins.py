"""``fragment_lookup_joins``: its entry, its reader over ``GET /v1/query``'s
``queryStats.fragmentLookupJoins``, and what it reads of a program without that
counter (the recorded samples of ``data/infos_q5_joins.json`` predate it)."""

import json
import os

from benchmark import harness

from .conftest import REPO

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
DATA = os.path.join(REPO, "benchmark")


def test_the_entry_lists_the_cells_whose_fragments_join():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == "fragment_lookup_joins"]
    assert entry == {
        "name": "fragment_lookup_joins", "unit": "1/query", "better": "higher",
        "source": "program_counter", "layer": "executor", "moves": "query_s",
        "workloads": ["q3-compiled", "q3-mesh4", "q5-compiled"]}
    assert BENCH["per_layer"][-1] is entry
    for cell in entry["workloads"]:
        assert harness.applies(entry, cell)
    assert not harness.applies(entry, "q1-compiled")


def test_the_reader_reads_the_counter_and_nothing_of_a_parent():
    infos = json.load(open(os.path.join(os.path.dirname(__file__), "data", "infos_q5_joins.json")))
    read = harness.load_reader(DATA, "fragment_lookup_joins")
    parent = infos["q5"] + infos["q3"]
    assert all("fragmentLookupJoins" not in q["queryStats"] for q in parent)
    assert read({"infos": parent}) is None
    change = [dict(q, queryStats={**q["queryStats"], "fragmentLookupJoins": n})
              for q, n in zip(parent, (1, 1, 0, 1))]
    assert read({"infos": change[:2]}) == 1.0
    assert read({"infos": change[1:3]}) == 0.5
    # a failed query, and one of a program whose fragments do not join,
    # count nothing
    failed = dict(change[0], state="FAILED", queryStats={"fragmentLookupJoins": 0})
    local = {"state": "FINISHED", "queryStats": {"phaseMs": {"execute": 3.0}}}
    assert read({"infos": [failed, local, change[0]]}) == 1.0
    assert read({"infos": []}) is None
