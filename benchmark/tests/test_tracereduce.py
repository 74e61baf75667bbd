"""The reduction trace -> busy, idle gaps, largest operations, on a small
recorded trace (``data/trace_small.json`` says what it is a cut of)."""

import json
import os

import numpy as np
import pytest

from benchmark import tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    planes = json.load(open(os.path.join(HERE, "data", "trace_small.json")))["planes"]
    for p in planes:
        for ln in p["lines"]:
            ln["events"] = [tuple(e) for e in ln["events"]]
    return planes


def raster(planes, line, lo, hi, step=100.0):
    """A timeline of ``step`` ns cells, true where an event of ``line`` runs:
    the plain way to a union's length, good to a cell an event edge."""
    cells = np.zeros(int((hi - lo) / step) + 1, dtype=bool)
    for p in planes:
        if p["name"].startswith("/device:"):
            for ln in p["lines"]:
                if ln["name"] == line:
                    for _, s, d in ln["events"]:
                        a, b = max(s, lo), min(s + d, hi)
                        if b > a:
                            cells[int((a - lo) / step):int(np.ceil((b - lo) / step))] = True
    return cells.sum() * step


def test_recorded_trace(recorded):
    got = tracereduce.reduce(recorded)
    spans = [e for p in recorded if p["name"] == "/host:CPU"
             for ln in p["lines"] for e in ln["events"]]
    lo = min(s for _, s, _ in spans)
    hi = max(s + d for _, s, d in spans)
    assert got["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert got["window_s"] == pytest.approx(0.120, abs=1e-6)
    n_ops = sum(len(ln["events"]) for p in recorded for ln in p["lines"] if ln["name"] == "XLA Ops")
    # a cell at each edge of each event is the raster's error
    assert got["busy_s"] * 1e9 == pytest.approx(raster(recorded, "XLA Ops", lo, hi), abs=200.0 * n_ops)
    assert 0 < got["busy_s"] < got["window_s"]
    assert got["devices"] == 1
    assert got["programs"] == sum(
        len(ln["events"]) for p in recorded for ln in p["lines"] if ln["name"] == "XLA Modules")
    ops = got["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops == sorted(ops, key=lambda r: -r[1])
    assert all(name.startswith("%") and " = " not in name for name, _ in ops)
    idle = dict(got["breakdown"]["idle_gaps"])
    assert set(idle) <= {"submit", "poll", "client", "between_queries"}
    assert sum(idle.values()) == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-9)
    assert got["longest_gap_s"] <= got["window_s"] - got["busy_s"]


def test_spans_that_cover_operations_do_not_count_as_busy(recorded):
    """"XLA Modules" and "Steps" spans cover the operations: reading them as
    operations would call the device busy where it waits inside a program."""
    only_ops = tracereduce.reduce(recorded)["busy_s"]
    lo, hi = 0.0, 120e6
    assert raster(recorded, "XLA Modules", lo, hi) / 1e9 > only_ops


def test_hand_made_trace():
    ms = 1e6
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [("%a = f32[] add()", 10 * ms, 10 * ms), ("%b", 15 * ms, 10 * ms),
                                           ("%a = f32[] add()", 60 * ms, 20 * ms)]},
            {"name": "XLA Modules", "events": [("jit_f", 9 * ms, 18 * ms), ("jit_g", 59 * ms, 22 * ms)]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [("bench:slice_start", 0.0, 1.0), ("bench:execute", 0.0, 50 * ms),
                                           ("bench:submit", 0.0, 10 * ms), ("bench:poll", 12 * ms, 30 * ms),
                                           ("bench:execute", 55 * ms, 45 * ms), ("bench:poll", 56 * ms, 40 * ms),
                                           ("bench:slice_end", 100 * ms - 1, 1.0), ("other", 0.0, 500 * ms)]},
        ]},
    ]
    got = tracereduce.reduce(planes)
    assert got["window_s"] == pytest.approx(0.100)
    assert got["busy_s"] == pytest.approx(0.035)  # [10, 25) and [60, 80)
    assert got["programs"] == 2 and got["queries"] == 2
    assert got["longest_gap_s"] == pytest.approx(0.035)  # [25, 60)
    assert dict(got["breakdown"]["device_ops"]) == pytest.approx({"%a": 0.030, "%b": 0.010})
    idle = dict(got["breakdown"]["idle_gaps"])
    # gaps [0,10) submit; [25,60): poll to 42, client to 50, between to 55,
    # client to 56, poll to 60; [80,100): poll to 96, client to 100
    assert idle == pytest.approx(
        {"submit": 0.010, "poll": 0.017 + 0.004 + 0.016, "client": 0.008 + 0.001 + 0.004,
         "between_queries": 0.005})


def test_a_query_cut_by_the_end_of_the_trace_counts_as_in_flight():
    ms = 1e6
    planes = [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [("%w", 20 * ms, 30 * ms)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ("bench:slice_start", 0.0, 1.0), ("bench:begin", 5 * ms, 1.0),
            ("bench:slice_end", 100 * ms - 1, 1.0)]}]},
    ]
    idle = dict(tracereduce.reduce(planes)["breakdown"]["idle_gaps"])
    assert idle == pytest.approx({"between_queries": 0.005, "client": 0.015 + 0.050})


def test_no_device_operation_reads_nothing():
    planes = [{"name": "/host:CPU", "lines": [{"name": "t", "events": [("bench:execute", 0.0, 5.0)]}]}]
    assert tracereduce.reduce(planes) is None
    planes.append({"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": []}]})
    assert tracereduce.reduce(planes) is None
