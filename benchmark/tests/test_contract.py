"""BENCHMARK.json against the limits of the benchmark's contract that a file
can be checked for, and the files it names."""

import json
import os
import re

from .conftest import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmark"]
    assert all(line(w) for w in BENCH["command"]) and len(BENCH["command"]) <= 32


def test_configs():
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        held = json.load(open(os.path.join(REPO, c["file"])))
        assert held["name"] == c["name"] and held["source"] == c["source"]
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        assert held["guarantees"] and "assumed" in held
        # the data set whose providers make the reference's columns, and the
        # rows of every table of it, so that any template's scan can be counted
        assert os.path.isdir(os.path.join(REPO, "benchmark", "datasets", held["dataset"]))
        assert set(held["rows"]) == {"lineitem", "orders", "customer", "supplier", "part",
                                     "partsupp", "nation", "region"}
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    assert len({c["source"] for c in BENCH["configs"]}) == len(BENCH["configs"])


def test_workloads():
    cells = BENCH["workloads"]
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(REPO, "benchmark", "traffic", w["traffic"] + ".json"))


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES and line(m["layer"])
        assert os.path.exists(os.path.join(REPO, "benchmark", "metrics", m["name"] + ".py"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        reported = [m for m in BENCH["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(cell in m.get("workloads", cells) for m in BENCH["per_layer"])


def test_files_under_paths_are_named_from_a_names_characters():
    for base, dirs, files in os.walk(os.path.join(REPO, "benchmark")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(base, f)
