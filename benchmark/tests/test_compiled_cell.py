"""``q1-compiled`` rehearsed on the CPU: the committed entry's own files (the
configuration cut to tpch.tiny, the committed ``q1-stream`` mix and readers)
through ``harness.main``, with lineitem made to stream through the slab
program as SF1's does (``stream_scan_threshold_rows`` 1; the one CPU device is
the one-device mesh of the chip)."""

import json
import os
import shutil

import pytest

from .conftest import DATA_DIRS, REPO, TINY
from .test_harness import cpu_as_device, run

CELL = "q1-compiled"
COMMITTED = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


@pytest.fixture
def streamed_root(tmp_path):
    """The committed BENCHMARK.json with the cell's configuration replaced by
    a tiny copy of itself, a new file beside the untouched committed ones."""
    root = tmp_path / "root"
    data = root / "benchmark"
    data.mkdir(parents=True)
    for sub in DATA_DIRS:
        shutil.copytree(os.path.join(REPO, "benchmark", sub), data / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "benchmark", "peaks.json"), data / "peaks.json")
    os.symlink(os.path.join(REPO, "trino_tpu"), root / "trino_tpu")
    cell = next(w for w in COMMITTED["workloads"] if w["name"] == CELL)
    entry = next(c for c in COMMITTED["configs"] if c["name"] == cell["config"])
    cfg = json.load(open(os.path.join(REPO, entry["file"])))
    cfg.update(TINY, name="tpch-tiny-streamed",
               session=dict(cfg["session"], stream_scan_threshold_rows=1))
    (data / "configs" / "tpch-tiny-streamed.json").write_text(json.dumps(cfg))
    bench = dict(COMMITTED)
    bench["configs"] = [dict(entry, name=cfg["name"],
                             file="benchmark/configs/tpch-tiny-streamed.json")]
    bench["workloads"] = [dict(cell, config=cfg["name"])]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def test_the_committed_entry_names_the_compiled_session():
    cell = next(w for w in COMMITTED["workloads"] if w["name"] == CELL)
    assert (cell["traffic"], cell["chips"]) == ("q1-stream", 1)
    entry = next(c for c in COMMITTED["configs"] if c["name"] == cell["config"])
    cfg = json.load(open(os.path.join(REPO, entry["file"])))
    assert cfg["session"] == {"execution_mode": "distributed"}
    assert cfg["schema"] == "sf1" and entry["reduced"] == ["scale_factor"]
    limited = {m["name"] for m in COMMITTED["per_layer"] if m.get("workloads") == [CELL]}
    assert limited == {"retraces", "dispatches", "slab_ms"}
    # a table that stops being resident shows in every cell's ledger lines
    h2d = next(m for m in COMMITTED["per_layer"] if m["name"] == "h2d_bytes")
    assert h2d["workloads"] == [CELL, "q1-default", "q3v-default"]


@pytest.fixture
def sent(monkeypatch):
    """The SQL texts the client sent, the two warm-ups first."""
    from trino_tpu import client

    texts = []
    plain = client.Connection.execute

    def noting(self, sql):
        texts.append(sql)
        return plain(self, sql)

    monkeypatch.setattr(client.Connection, "execute", noting)
    return texts


def test_every_delta_of_the_window_is_answered_from_one_program(streamed_root, capsys, sent):
    result, err = run(streamed_root, capsys, CELL, seed=2**31 + 4242, seconds=4.0)
    assert result["correct"] is True, err[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert len(set(sent[2:])) == result["attempted"]  # each another DELTA
    assert all(c["value"] == 0 for c in result["compared"].values())
    assert set(result["metrics"]) == {"query_s", "qph", "setup_s"}


def test_traced_rehearsal_reads_the_cells_metrics(streamed_root, capsys, monkeypatch, sent):
    """Distinct DELTAs after the warm-up: nothing traced, the slab span read,
    and every per-layer metric the cell has to report (those without a
    ``workloads`` list, and its own) comes out but two that need the chip."""
    cpu_as_device(monkeypatch)
    result, err = run(streamed_root, capsys, CELL, seconds=3.0, trace=1)
    assert result["correct"] is True, err[-3000:]
    assert len(set(sent[2:])) == result["attempted"] >= 2
    m = result["metrics"]
    assert m["retraces"]["value"] == 0 and m["xla_compiles"]["value"] == 0
    assert m["h2d_bytes"]["value"] == 0
    assert m["dispatches"]["value"] >= 1
    assert 0 < m["slab_ms"]["value"] <= m["execute_ms"]["value"]
    assert m["slab_ms"]["unit"] == "ms/query" and m["h2d_bytes"]["unit"] == "B/query"
    due = {p["name"] for p in COMMITTED["per_layer"] if CELL in p.get("workloads", [CELL])}
    # neither a line of launches in the CPU's trace nor a peak for the CPU
    assert due - set(m) == {"device_programs", "scan_roofline"}, sorted(due - set(m))
    # this tier opens no op: span, so the scan's self time has nothing to read
    assert "scan_ms" not in due and "scan_ms" not in m


def test_a_program_without_the_slab_span_gives_nothing_to_read():
    from benchmark import harness

    read = harness.load_reader(os.path.join(REPO, "benchmark"), "slab_ms")
    parent = {"state": "FINISHED", "queryStats": {"phaseMs": {"execute": 3.0}}}
    assert read({"infos": [parent]}) is None
    assert read({"infos": []}) is None
    ours = {"state": "FINISHED", "queryStats": {"phaseMs": {"execute": 3.0, "slab": 2.0}}}
    assert read({"infos": [ours, parent]}) == 2.0
