"""``q3-compiled`` and ``q3-mesh4``: the configuration ``tpch-sf1-mesh4``
against what ``test_contract.py::test_configs`` asks of a configuration, the
two cells found by name with no edit to the harness, the three readers they
bring against a recorded sample of ``GET /v1/query``, and both cells rehearsed
on the CPU at tpch.tiny through ``harness.main`` (the four-device one in a
process of its own, which is given four host devices)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import harness

from .conftest import DATA_DIRS, REPO, TINY
from .test_contract import NAME, line
from .test_harness import cpu_as_device, run

BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
DATA = os.path.join(REPO, "benchmark")
CELLS = {"q3-compiled": ("tpch-sf1-compiled", 1), "q3-mesh4": ("tpch-sf1-mesh4", 4)}
READERS = {"build_ms": ["q3-compiled", "q3-mesh4"],
           "join_step_traces": ["q3-compiled", "q3-mesh4"],
           "exchange_rows": ["q3-mesh4"]}


def test_the_configuration_is_one_test_configs_passes():
    entry = next(c for c in BENCH["configs"] if c["name"] == "tpch-sf1-mesh4")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and line(entry["source"]) and line(entry["why"])
    assert entry["file"] == "benchmark/configs/tpch-sf1-mesh4.json"
    held = json.load(open(os.path.join(REPO, entry["file"])))
    assert held["name"] == entry["name"] and held["source"] == entry["source"]
    assert sorted(held["reduced"]) == entry["reduced"] == ["scale_factor"]
    assert os.path.isdir(os.path.join(DATA, "datasets", held["dataset"]))
    assert set(held["rows"]) == {"lineitem", "orders", "customer", "supplier", "part",
                                 "partsupp", "nation", "region"}
    assert sum(c["source"] == entry["source"] for c in BENCH["configs"]) == 1
    # as tpch-sf1-compiled but for the mesh: the same data, the same session
    # (execution_mode and nothing else), the guarantees word for word
    one = json.load(open(os.path.join(DATA, "configs", "tpch-sf1-compiled.json")))
    for key in ("catalog", "schema", "scale_factor", "dataset", "session", "rows",
                "value_bytes", "guarantees", "assumed"):
        assert held[key] == one[key], key
    assert held["session"] == {"execution_mode": "distributed"}
    assert held["mesh"]["devices"] == 4 and held["mesh"]["axis"] == "shards"
    assert "SF25" in held["reduced"]["scale_factor"] and "deployment" in held


def test_the_entries_were_appended_and_nothing_else_moved():
    assert [c["name"] for c in BENCH["configs"]][-1] == "tpch-sf1-mesh4"
    assert [w["name"] for w in BENCH["workloads"]][-2:] == list(CELLS)
    assert [m["name"] for m in BENCH["per_layer"]][-3:] == list(READERS)
    for m in BENCH["per_layer"][-3:]:
        assert m["workloads"] == READERS[m["name"]] and m["moves"] == "query_s"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= len(BENCH["workloads"]) // 2
    accepted = [m for m in BENCH["per_layer"][:-3] if "workloads" in m]
    assert not [m["name"] for m in accepted if set(m["workloads"]) & set(CELLS)]


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_cell_is_found_by_name_with_no_harness_edit(cell):
    config, chips = CELLS[cell]
    _, data_root, entry, held, mix = harness.load_cell(REPO, cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (config, "q3-validation", chips)
    assert line(entry["why"]) and held["name"] == config
    assert list(mix.templates) == ["q3"] and mix.think_s == 0 and len(mix.streams) == 1
    assert mix.templates["q3"].meta["reference"] == "q3"
    assert mix.grids["q3"] == [{"DATE": "1995-03-15", "SEGMENT": "BUILDING"}]
    reported = [m["name"] for m in BENCH["per_layer"] if harness.applies(m, cell)]
    assert set(READERS) - {"exchange_rows"} <= set(reported)
    assert ("exchange_rows" in reported) == (chips == 4)
    for name in reported:
        assert callable(harness.load_reader(data_root, name))


def test_the_readers_read_a_recorded_sample_and_nothing_of_a_parent():
    """``data/infos_q3_mesh4.json``: two warm Q3 of the served path on four
    host devices at tpch.tiny, as ``GET /v1/query`` listed them (the keys the
    readers of this file's cells read; its times are a CPU's and are no one's
    metric)."""
    infos = json.load(open(os.path.join(os.path.dirname(__file__), "data", "infos_q3_mesh4.json")))
    assert len(infos) == 2 and all(q["state"] == "FINISHED" for q in infos)
    read = {name: harness.load_reader(DATA, name) for name in READERS}
    run = {"infos": infos}
    builds = [q["queryStats"]["phaseMs"]["build"] for q in infos]
    assert read["build_ms"](run) == pytest.approx(sum(builds) / 2) and min(builds) > 0
    assert read["join_step_traces"](run) == 0
    assert read["exchange_rows"](run) == 138.0
    assert all(q["queryStats"]["meshDevices"] == 4 for q in infos)
    # a program with no such span or counter (the parent), a query that failed
    parent = {"state": "FINISHED", "queryStats": {"phaseMs": {"execute": 3.0}},
              "exchangeStats": None}
    failed = dict(infos[0], state="FAILED")
    for name, reader in read.items():
        assert reader({"infos": [parent, failed]}) is None, name
        assert reader({"infos": []}) is None, name
    assert read["build_ms"]({"infos": infos + [parent]}) == pytest.approx(sum(builds) / 2)


def _tiny_root(tmp_path, cell, chips):
    """The committed BENCHMARK.json with ``cell``'s configuration replaced by
    a copy of itself cut to tpch.tiny whose lineitem still streams (as SF1's
    does), a new file beside the untouched committed ones."""
    root = tmp_path / "root"
    data = root / "benchmark"
    data.mkdir(parents=True)
    for sub in DATA_DIRS:
        shutil.copytree(os.path.join(DATA, sub), data / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(DATA, "peaks.json"), data / "peaks.json")
    os.symlink(os.path.join(REPO, "trino_tpu"), root / "trino_tpu")
    entry = next(c for c in BENCH["configs"] if c["name"] == CELLS[cell][0])
    cfg = json.load(open(os.path.join(REPO, entry["file"])))
    cfg.update(TINY, name="tpch-tiny-q3", session=dict(
        cfg["session"], stream_scan_threshold_rows=1, stream_device_chunk_rows=8192))
    (data / "configs" / "tpch-tiny-q3.json").write_text(json.dumps(cfg))
    bench = dict(BENCH)
    bench["configs"] = [dict(entry, name=cfg["name"], file="benchmark/configs/tpch-tiny-q3.json")]
    bench["workloads"] = [dict(next(w for w in BENCH["workloads"] if w["name"] == cell),
                               config=cfg["name"], chips=chips)]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_q3_compiled_rehearsed_on_one_device(tmp_path, capsys, monkeypatch):
    cpu_as_device(monkeypatch)
    root = _tiny_root(tmp_path, "q3-compiled", 1)
    result, err = run(root, capsys, "q3-compiled", seed=2**31 + 35, seconds=1.5, trace=1)
    assert result["correct"] is True and result["failed"] == 0, err[-3000:]
    m = result["metrics"]
    assert m["join_step_traces"]["value"] == 0 and m["xla_compiles"]["value"] == 0
    assert m["build_ms"]["value"] > 0 and "exchange_rows" not in m
    assert m["slab_steps"]["value"] >= 1 if "slab_steps" in m else True
    assert all(c["value"] == 0 for c in result["compared"].values())


def test_q3_mesh4_rehearsed_on_four_host_devices(tmp_path):
    root = _tiny_root(tmp_path, "q3-mesh4", 4)
    code = (
        "import re, sys, time; sys.path.insert(0, %r)\n"
        "from benchmark import harness, tracereduce\n"
        "tracereduce.DEVICE_PLANE = re.compile(r'^/host:CPU$')\n"
        "tracereduce.OP_LINE = re.compile(r'^tf_XLA')\n"
        "sys.exit(harness.main(['--workload', 'q3-mesh4', '--seed', %r, '--seconds', '1.5',"
        " '--trace', '1'], root=%r, platform='cpu', started=time.perf_counter()))\n"
    ) % (REPO, str(2**31 + 36), root)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0, out.stderr[-3000:]
    assert result["device"]["count"] == 4
    m = result["metrics"]
    assert m["join_step_traces"]["value"] == 0 and m["xla_compiles"]["value"] == 0
    assert m["exchange_rows"]["value"] > 0 and m["build_ms"]["value"] > 0
    assert all(c["value"] == 0 for c in result["compared"].values())
