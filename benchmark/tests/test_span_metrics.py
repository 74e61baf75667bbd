"""The per-layer metrics that read the program's own spans and its compile
counter (``queryStats.phaseMs``, ``operatorMs``, ``xlaCompiles``): numbers from
a run of the harness, nothing from a program that has no such spans."""

import os

import pytest

from benchmark import harness

from .conftest import REPO
from .test_harness import cpu_as_device, run

READERS = ("plan_ms", "execute_ms", "scan_ms", "result_ms", "xla_compiles")


def test_the_readers_return_numbers_on_a_run_of_the_harness(tiny_root, capsys, monkeypatch):
    cpu_as_device(monkeypatch)
    result, err = run(tiny_root, capsys, "q1-tiny-default", seconds=1.5, trace=1)
    assert result["correct"] is True, err[-3000:]
    m = result["metrics"]
    assert set(READERS) <= set(m), sorted(m)
    assert m["xla_compiles"]["value"] == 0  # the warm-up compiled every shape
    assert m["execute_ms"]["value"] > m["scan_ms"]["value"] > 0
    assert m["plan_ms"]["value"] > 0 and m["result_ms"]["value"] > 0
    assert {m[n]["unit"] for n in READERS} == {"ms/query", "1/query"}


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_spans_gives_nothing_to_read(name):
    read = harness.load_reader(os.path.join(REPO, "benchmark"), name)
    parent = {"state": "FINISHED", "queryStats": {"elapsedMs": 3, "queuedMs": 1}}
    assert read({"infos": [parent]}) is None
    assert read({"infos": [{"state": "FINISHED"}]}) is None
    assert read({"infos": []}) is None


def test_the_phases_add_up_to_the_servers_elapsed_time():
    stats = {"elapsedMs": 100, "queuedMs": 4,
             "phaseMs": {"parse": 1.0, "plan": 2.0, "optimize": 3.0, "canonicalize": 0.5,
                         "execute": 80.0, "resultPull": 9.0},
             "operatorMs": {"TableScan": 30.0, "Aggregate": 50.0}, "xlaCompiles": 2}
    failed = {"state": "FAILED", "queryStats": {"phaseMs": {}, "xlaCompiles": 9}}
    infos = {"infos": [{"state": "FINISHED", "queryStats": stats}, failed]}
    values = {n: harness.load_reader(os.path.join(REPO, "benchmark"), n)(infos) for n in READERS}
    assert values == {"plan_ms": 6.5, "execute_ms": 80.0, "scan_ms": 30.0, "result_ms": 9.0,
                      "xla_compiles": 2}
    assert stats["queuedMs"] + values["plan_ms"] + values["execute_ms"] + values["result_ms"] \
        == pytest.approx(stats["elapsedMs"], rel=0.03)
