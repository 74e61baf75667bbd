"""``h2o-groupby-1e8`` and its cell against the contract: what
``test_contract.py::test_configs`` checks of a configuration, with the rows of
the data set's own tables (one table, ``x``) in place of TPC-H's eight, and
what the new files have to hold for the harness to find them by name."""

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(REPO, "benchmark")
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
ENTRY = next(c for c in BENCH["configs"] if c["name"] == "h2o-groupby-1e8")
HELD = json.load(open(os.path.join(REPO, ENTRY["file"])))
QUESTIONS = ["g1q1", "g1q2", "g1q3", "g1q4", "g1q5"]


def test_the_entry_and_its_file_agree():
    assert set(ENTRY) == {"name", "source", "file", "reduced", "why"}
    assert 1 <= len(ENTRY["source"]) <= 200 and 1 <= len(ENTRY["why"]) <= 200
    assert HELD["name"] == ENTRY["name"] and HELD["source"] == ENTRY["source"]
    # 1e9 rows over 16 chips is 6.25e7 a chip; the source's 1e8 is taken whole
    assert ENTRY["reduced"] == [] and HELD["reduced"] == {}
    assert (HELD["catalog"], HELD["schema"], HELD["dataset"]) == ("h2o", "g1_1e8", "h2o")
    assert HELD["scale_factor"] == HELD["rows"]["x"] == 100_000_000
    assert HELD["session"] == {"execution_mode": "distributed"}
    assert HELD["value_bytes"] == 8 and "assumed" in HELD and "deployment" in HELD
    assert sum(c["source"] == ENTRY["source"] for c in BENCH["configs"]) == 1


def test_the_guarantees_are_the_tpch_configurations_word_for_word():
    tpch = json.load(open(os.path.join(DATA, "configs", "tpch-sf1-compiled.json")))
    assert HELD["guarantees"] == tpch["guarantees"] and len(HELD["guarantees"]) == 4


def test_rows_hold_every_table_the_data_sets_providers_give():
    """What ``test_contract.py::test_configs`` should ask of every
    configuration (it asks for TPC-H's eight tables by name)."""
    from benchmark import refdata

    tables = {table for table, _ in refdata.Dataset(DATA, HELD["dataset"]).provider}
    assert set(HELD["rows"]) == tables == {"x"}


def test_the_cell_and_its_metric():
    cell = next(w for w in BENCH["workloads"] if w["name"] == "g1-q5-compiled")
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert (cell["config"], cell["traffic"], cell["chips"]) == (ENTRY["name"], "g1-q5-stream", 1)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    new = [m for m in BENCH["per_layer"] if m.get("workloads") == ["g1-q5-compiled"]]
    assert {m["name"] for m in new} <= {"agg_attempts", "group_sort_ms"}
    assert "agg_attempts" in {m["name"] for m in new}
    for m in new:
        assert m["moves"] == "query_s" and m["better"] == "lower"
        assert os.path.isfile(os.path.join(DATA, "metrics", m["name"] + ".py"))
    # the accepted entries are as they were: the new ones come last
    assert [c["name"] for c in BENCH["configs"]][-1] == ENTRY["name"]
    assert [w["name"] for w in BENCH["workloads"]][-1] == "g1-q5-compiled"


def test_every_question_came_as_files_found_by_name():
    from benchmark import harness, reference, traffic

    for q in QUESTIONS:
        mix = traffic.Mix(DATA, f"g1-{q[2:]}-stream")
        assert list(mix.templates) == [q] and mix.grids[q] == [{}] and mix.think_s == 0
        meta = mix.templates[q].meta
        assert meta["reference"] == q and meta["validation"] == {}
        assert list(meta["reads"]) == ["x"]
        sql = mix.templates[q].sql("h2o.g1_1e8", {})
        keys = sql.split("group by ")[1].split(" order by ")
        assert keys[0] == keys[1], sql  # ORDER BY the grouping key(s)
        assert meta["sort_key"] == list(range(len(keys[0].split(","))))
        assert callable(reference.load_function(DATA, q))
        dataset, reads = harness.dataset_and_reads(DATA, HELD, mix)
        dataset.check(reads)


def test_the_reader_reads_the_programs_own_counter_and_nothing_of_a_parent():
    from benchmark import harness

    read = harness.load_reader(DATA, "agg_attempts")
    parent = {"state": "FINISHED", "queryStats": {"phaseMs": {"execute": 3.0}}}
    assert read({"infos": [parent]}) is None and read({"infos": []}) is None
    ours = [{"state": "FINISHED", "queryStats": {"aggAttempts": a}} for a in (1, 3)]
    assert read({"infos": ours + [parent]}) == 2.0
