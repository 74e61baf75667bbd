"""The five per-layer metrics that read the server's delivery account
(``queryStats.delivery``) and the host's wait for the device
(``queryStats.phaseMs.devicePull``): each the mean over the FINISHED queries of
a recorded ``infos`` list, nothing from a program that keeps no such account."""

import json
import os

import pytest

from benchmark import harness

from .conftest import REPO

COMMITTED = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
#: reader -> the key of ``queryStats.delivery`` it reads
DELIVERY = {"page_build_ms": "buildMs", "page_encode_ms": "encodeMs",
            "client_gap_ms": "clientGapMs", "result_pages": "pages"}
COMPILED_CELLS = ["q1-compiled", "g1-q5-compiled", "q3-compiled", "q3-mesh4"]


def _reader(name):
    return harness.load_reader(os.path.join(REPO, "benchmark"), name)


def _query(state="FINISHED", delivery=None, pull=None):
    stats = {"elapsedMs": 100, "phaseMs": {"execute": 80.0}}
    if delivery is not None:
        stats["delivery"] = delivery
    if pull is not None:
        stats["phaseMs"]["devicePull"] = pull
    return {"state": state, "queryStats": stats}


RECORDED = [
    _query(delivery={"pages": 245, "rows": 1000000, "bodyBytes": 33500000, "buildMs": 4100.0,
                     "encodeMs": 1640.0, "clientGapMs": 3200.0, "wallMs": 9000.0}, pull=10500.0),
    _query(delivery={"pages": 1, "rows": 4, "bodyBytes": 1808, "buildMs": 0.1,
                     "encodeMs": 0.05, "clientGapMs": 0.0, "wallMs": 0.4}, pull=7.5),
    _query("FAILED", delivery={"pages": 0, "rows": 0, "bodyBytes": 0, "buildMs": 0.0,
                               "encodeMs": 0.0, "clientGapMs": 0.0, "wallMs": 0.0}, pull=99.0),
]


@pytest.mark.parametrize("name", sorted(DELIVERY))
def test_a_delivery_reader_gives_the_mean_over_the_finished_queries(name):
    key = DELIVERY[name]
    finished = [q["queryStats"]["delivery"][key] for q in RECORDED if q["state"] == "FINISHED"]
    assert _reader(name)({"infos": RECORDED}) == pytest.approx(sum(finished) / 2)


def test_the_device_wait_is_the_mean_pull_of_the_finished_queries():
    assert _reader("device_wait_ms")({"infos": RECORDED}) == pytest.approx((10500.0 + 7.5) / 2)
    # a default-session query has the key, at 0: a number, not nothing
    assert _reader("device_wait_ms")({"infos": [_query(pull=0.0)]}) == 0.0


@pytest.mark.parametrize("name", sorted(DELIVERY) + ["device_wait_ms"])
def test_a_program_without_the_account_gives_nothing_to_read(name):
    read = _reader(name)
    assert read({"infos": [_query()]}) is None  # the parent: no delivery, no devicePull
    assert read({"infos": [{"state": "FINISHED"}]}) is None
    assert read({"infos": [{"state": "FINISHED", "queryStats": {"delivery": None}}]}) is None
    assert read({"infos": [RECORDED[2]]}) is None  # nothing FINISHED
    assert read({"infos": []}) is None


def test_where_the_five_apply():
    by_name = {m["name"]: m for m in COMMITTED["per_layer"]}
    for name in DELIVERY:
        entry = by_name[name]
        assert "workloads" not in entry  # every cell delivers a page
        assert (entry["layer"], entry["moves"], entry["better"]) \
            == ("client / protocol", "query_s", "lower")
    wait = by_name["device_wait_ms"]
    assert wait["workloads"] == COMPILED_CELLS
    assert (wait["layer"], wait["source"], wait["unit"]) == ("executor", "program_span", "ms/query")
    cells = [w["name"] for w in COMMITTED["workloads"]]
    assert [c for c in cells if harness.applies(wait, c)] \
        == [c for c in cells if c in COMPILED_CELLS]
    assert all(harness.applies(by_name[n], c) for n in DELIVERY for c in cells)
    assert by_name["result_pages"]["source"] == "program_counter"
    assert {by_name[n]["source"] for n in DELIVERY if n != "result_pages"} == {"program_span"}
