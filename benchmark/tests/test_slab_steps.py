"""``slab_steps`` (PR 34): one ``per_layer`` entry appended, its reader found
by name, and nothing read from a program without the counter."""

import json
import os

from benchmark import harness

DATA = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = json.load(open(os.path.join(os.path.dirname(DATA), "BENCHMARK.json")))


def test_the_entry_comes_last_and_lists_the_cells_that_stream_through_a_slab():
    entry = BENCH["per_layer"][-1]
    assert entry == {
        "name": "slab_steps", "unit": "1/query", "better": "lower",
        "source": "program_counter", "layer": "executor", "moves": "query_s",
        "workloads": ["q1-compiled", "g1-q5-compiled"],
    }
    assert set(entry["workloads"]) <= {w["name"] for w in BENCH["workloads"]}


def test_the_reader_reads_the_programs_counter_and_nothing_of_a_parent():
    read = harness.load_reader(DATA, "slab_steps")
    parent = {"state": "FINISHED", "queryStats": {"aggAttempts": 1, "phaseMs": {"slab": 18.7}}}
    assert read({"infos": [parent]}) is None and read({"infos": []}) is None
    ours = [{"state": "FINISHED", "queryStats": {"slabSteps": s}} for s in (6, 6, 3)]
    assert read({"infos": ours + [parent]}) == 5.0
    assert read({"infos": [{"state": "FAILED", "queryStats": {"slabSteps": 48}}]}) is None
