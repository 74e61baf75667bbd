"""Every cell end to end at tpch.tiny on the CPU, through ``harness.main``
(never the driver's command), the faults that ``correct`` has to catch, and
the runner's refusal without a TPU."""

import json
import os
import re
import subprocess
import sys
import time
from decimal import Decimal

import pytest

from benchmark import harness, tracereduce

from .conftest import REPO

COMMITTED_CELL = json.load(open(os.path.join(REPO, "BENCHMARK.json")))["workloads"][0]["name"]


def run(root, capsys, workload, seed=3, seconds=1.0, trace=0):
    rc = harness.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        root=root, platform="cpu", started=time.perf_counter(),
    )
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize(
    "workload", ["q1-tiny-compiled", "q6-tiny-compiled", "q3-tiny-compiled",
                 "q1-tiny-default", "mixed-tiny-compiled", "lines-tiny-default"])
def test_cell_end_to_end(tiny_root, capsys, workload):
    result, err = run(tiny_root, capsys, workload, seed=2**31 + 12345)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, err[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"query_s", "qph", "p95_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1
    streams = 2 if workload.startswith("mixed") else 1
    m = result["metrics"]
    assert m["query_s"]["value"] * m["qph"]["value"] == pytest.approx(3600.0 * streams)
    assert err.strip().splitlines()[-1].startswith("compared widest_gap = 0")


def cpu_as_device(monkeypatch):
    # on the CPU the operations run on host threads: read those as the device
    monkeypatch.setattr(tracereduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    monkeypatch.setattr(tracereduce, "OP_LINE", re.compile(r"^tf_XLA"))


@pytest.mark.parametrize("workload", ["q1-tiny-compiled", "q6-tiny-compiled"])
def test_literal_variants_retrace_nothing(tiny_root, capsys, monkeypatch, workload):
    """Numeric and date literals are hoisted: after the warm-up no execution
    of the template traces, stages or compiles anything."""
    cpu_as_device(monkeypatch)
    result, err = run(tiny_root, capsys, workload, seconds=1.5, trace=1)
    assert result["correct"] is True, err[-3000:]
    m = result["metrics"]
    assert m["retraces"]["value"] == 0
    assert m["h2d_bytes"]["value"] == 0
    assert m["dispatches"]["value"] >= 1
    assert "queries_listed" not in m


def test_traced_run_reads_counters_and_added_metric(tiny_root, capsys, monkeypatch):
    cpu_as_device(monkeypatch)
    result, err = run(tiny_root, capsys, "mixed-tiny-compiled", seconds=1.5, trace=1)
    assert result["correct"] is True, err[-3000:]
    m = result["metrics"]
    assert {"protocol_ms", "queued_ms", "retraces", "dispatches", "h2d_bytes",
            "device_busy_ms", "device_idle_pct", "queries_listed"} <= set(m)
    # the CPU's trace has no line of program launches: the reader returns
    # nothing and the metric is left out, never 0
    assert "device_programs" not in m
    assert 0 <= m["device_idle_pct"]["value"] <= 100
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    assert len(result["breakdown"]["device_ops"]) >= 1
    assert {name for name, _ in result["breakdown"]["idle_gaps"]} <= {
        "submit", "poll", "client", "between_queries"}
    assert not os.path.exists(os.path.join(tiny_root, ".cache", "benchmark", "trace"))


def test_q3_retraces_one_program_per_execution(tiny_root, capsys, monkeypatch):
    """What PERF.md says of Q3 today (S2): each warm execution traces again."""
    cpu_as_device(monkeypatch)
    result, err = run(tiny_root, capsys, "q3-tiny-compiled", trace=1)
    assert result["correct"] is True, err[-3000:]
    assert result["metrics"]["retraces"]["value"] >= 1


def alter_digit(rows):
    row = list(rows[0])
    i = next(i for i, v in enumerate(row) if isinstance(v, Decimal))
    row[i] = row[i] + Decimal(1).scaleb(row[i].as_tuple().exponent)
    return [tuple(row)] + rows[1:]


FAULTS = {
    "a digit altered": alter_digit,
    "a row dropped": lambda rows: rows[:-1],
    "a row delivered twice": lambda rows: rows + rows[-1:],
    "rows out of order": lambda rows: rows[::-1],
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_an_altered_answer_is_not_correct(tiny_root, capsys, monkeypatch, fault):
    """The rest of a run, with the timed path broken underneath: an answer
    altered where the client receives it."""
    from trino_tpu import client

    plain = client.Connection.execute
    calls = {"n": 0}

    def broken(self, sql):
        rows, names = plain(self, sql)
        calls["n"] += 1
        if calls["n"] == 4:  # past the two warm-ups: one answer of the window
            rows = FAULTS[fault](rows)
        return rows, names

    monkeypatch.setattr(client.Connection, "execute", broken)
    result, _ = run(tiny_root, capsys, "q1-tiny-compiled")
    assert calls["n"] >= 4
    assert result["correct"] is False
    assert result["compared"]["answers_wrong"]["value"] == 1
    assert result["failed"] == 0


def test_a_failed_query_is_counted_and_not_correct(tiny_root, capsys, monkeypatch):
    from trino_tpu import client

    plain = client.Connection.execute
    calls = {"n": 0}

    def broken(self, sql):
        calls["n"] += 1
        if calls["n"] == 3:
            raise client.QueryFailure({"errorName": "TEST", "message": "planted"})
        return plain(self, sql)

    monkeypatch.setattr(client.Connection, "execute", broken)
    result, err = run(tiny_root, capsys, "q6-tiny-compiled")
    assert result["correct"] is False
    assert result["failed"] == 1
    assert result["compared"]["answers_missing"]["value"] == 1
    assert "planted" in err


def test_unknown_cell_and_cpu_are_refused(tiny_root, capsys):
    args = ["--workload", "q1-tiny-compiled", "--seed", "1", "--seconds", "1"]
    assert harness.main(args, root=tiny_root, platform="tpu", started=0.0) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs a tpu device" in out.err
    args[1] = "no-such-cell"
    assert harness.main(args, root=tiny_root, platform="cpu", started=0.0) == 2
    assert capsys.readouterr().out == ""


def test_the_drivers_command_exits_nonzero_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--workload",
           COMMITTED_CELL, "--seed", "1", "--seconds", "1", "--trace", "0"]
    done = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 2 and done.stdout == ""
    assert "needs a tpu device" in done.stderr


def test_a_checkout_without_the_program_is_refused(tmp_path):
    import shutil

    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    cmd = [sys.executable, "benchmark/run.py", "--workload", COMMITTED_CELL,
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    done = subprocess.run(cmd, cwd=tmp_path, capture_output=True, text=True, timeout=120,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 2 and done.stdout == ""
    assert "no program to measure" in done.stderr
