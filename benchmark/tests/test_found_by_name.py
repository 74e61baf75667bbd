"""The reference side takes files: a query's reference function and the
columns it reads are found by name (``references/<name>.py``, the providers of
``datasets/<dataset>/``), and what is not found is refused before anything
boots. ``tiny_root`` adds the query ``lines`` that way (``conftest.ADDED_FILES``)."""

import json
import os
import subprocess

import pytest

from benchmark import files, harness, reference, refdata

from .conftest import ADDED_FILES, REPO
from .test_harness import run


def test_no_committed_file_names_what_the_added_query_brings():
    out = subprocess.run(
        ["grep", "-rlE", "l_linenumber|lines-stream|references/lines", "--include=*",
         "--exclude-dir=tests", "--exclude-dir=__pycache__",
         os.path.join(REPO, "benchmark"), os.path.join(REPO, "BENCHMARK.json")],
        capture_output=True, text=True)
    assert out.stdout == ""
    assert set(ADDED_FILES) == {
        "templates/lines.sql", "templates/lines.json", "traffic/lines-stream.json",
        "references/lines.py", "datasets/tpch/linenumber.py"}


def test_an_altered_answer_of_the_added_query_is_not_correct(tiny_root, capsys, monkeypatch):
    from trino_tpu import client

    plain = client.Connection.execute
    calls = {"n": 0}

    def broken(self, sql):
        rows, names = plain(self, sql)
        calls["n"] += 1
        if calls["n"] == 3:
            rows = [(rows[0][0], rows[0][1] + 1, rows[0][2])] + rows[1:]
        return rows, names

    monkeypatch.setattr(client.Connection, "execute", broken)
    result, _ = run(tiny_root, capsys, "lines-tiny-default", seconds=0.5)
    assert result["correct"] is False
    assert result["compared"]["answers_wrong"]["value"] == 1
    assert result["compared"]["values_wrong"]["value"] == 1


def test_the_added_column_is_made_from_a_committed_one_and_cached(tiny_root):
    data = os.path.join(tiny_root, "benchmark")
    tables = refdata.Dataset(data, "tpch").load(
        0.01, {"lineitem": ["l_linenumber"]}, os.path.join(tiny_root, ".cache"))
    number = tables["lineitem"]["l_linenumber"]
    assert number.min() == 1 and number.max() == 7 and len(number) == 60175
    assert sorted(os.listdir(os.path.join(tiny_root, ".cache", "tpch-sf0.01"))) == [
        "lineitem.l_linenumber.npy", "lineitem.l_orderkey.npy"]
    assert list(tables) == ["lineitem"] and list(tables["lineitem"]) == ["l_linenumber"]


def refused(root, capsys, workload="lines-tiny-default"):
    rc = harness.main(["--workload", workload, "--seed", "1", "--seconds", "1"],
                      root=root, platform="cpu", started=0.0)
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "warm-up" not in out.err  # nothing booted
    return out.err


def test_an_unknown_reference_is_refused_before_the_server_boots(tiny_root, capsys):
    data = os.path.join(tiny_root, "benchmark")
    os.remove(os.path.join(data, "references", "lines.py"))
    assert "no reference function 'lines'" in refused(tiny_root, capsys)
    with open(os.path.join(data, "references", "lines.py"), "w") as f:
        f.write("def reply():\n    pass\n")
    assert "it needs answer()" in refused(tiny_root, capsys)
    with pytest.raises(files.Refused):
        reference.Reference({}).answer("no-such-query", {})


def test_a_column_with_no_provider_or_two_is_refused(tiny_root, capsys):
    data = os.path.join(tiny_root, "benchmark")
    provider = os.path.join(data, "datasets", "tpch", "linenumber.py")
    with open(os.path.join(data, "datasets", "tpch", "linenumber_again.py"), "w") as f:
        f.write(ADDED_FILES["datasets/tpch/linenumber.py"])
    assert "two providers of lineitem.l_linenumber" in refused(tiny_root, capsys)
    os.remove(os.path.join(data, "datasets", "tpch", "linenumber_again.py"))
    os.remove(provider)
    assert "no provider of lineitem.l_linenumber" in refused(tiny_root, capsys)
    with open(provider, "w") as f:  # made from itself
        f.write(ADDED_FILES["datasets/tpch/linenumber.py"].replace("l_orderkey", "l_linenumber"))
    assert "is made from itself" in refused(tiny_root, capsys)
    with open(provider, "w") as f:
        f.write("ROWS = 7\n")
    assert "is no column provider" in refused(tiny_root, capsys)


def test_a_configuration_names_its_data_set(tiny_root, capsys):
    path = os.path.join(tiny_root, "benchmark", "configs", "tpch-tiny-default.json")
    config = json.load(open(path))
    assert config["dataset"] == "tpch"
    config["dataset"] = "tpcds"
    json.dump(config, open(path, "w"))
    assert "no data set 'tpcds'" in refused(tiny_root, capsys)
    del config["dataset"]
    json.dump(config, open(path, "w"))
    assert 'names no "dataset"' in refused(tiny_root, capsys)
