"""CPU rehearsal of the benchmark. Not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY = {"scale_factor": 0.01, "schema": "tiny",
        "rows": {"lineitem": 60175, "orders": 15000, "customer": 1500, "supplier": 100,
                 "part": 2000, "partsupp": 8000, "nation": 25, "region": 5}}
#: what a cell is made of, each a directory of files found by name
DATA_DIRS = ("configs", "traffic", "templates", "metrics", "references", "datasets")

# A query that no committed file names, brought as files and nothing else: a
# template, its reference function, and the provider of a column
# (lineitem.l_linenumber, made from l_orderkey) that the committed provider
# does not give.
ADDED_FILES = {
    "templates/lines.sql": (
        "select l_linenumber, count(*) as lines, sum(l_quantity) as quantity\n"
        "from {SCHEMA}.lineitem where l_quantity < {QUANTITY}\n"
        "group by l_linenumber order by l_linenumber\n"),
    "templates/lines.json": json.dumps({
        "spec": "lines and quantity by a line's number in its order",
        "reference": "lines", "validation": {"QUANTITY": 24}, "sort_key": [0],
        "reads": {"lineitem": ["l_linenumber", "l_quantity"]}}),
    "traffic/lines-stream.json": json.dumps({
        "loop": "closed", "think_s": 0, "streams": [{"templates": ["lines"]}],
        "parameters": {"lines": {"QUANTITY": {"range": [20, 30]}}}}),
    "references/lines.py": (
        "import numpy as np\n"
        "from benchmark.reference import Arithmetic, dec\n\n\n"
        "def answer(tables, params, precision='exact', kept=None):\n"
        "    li = tables['lineitem']\n"
        "    keep = li['l_quantity'] < int(params['QUANTITY']) * 100\n"
        "    number = li['l_linenumber'][keep]\n"
        "    count = np.bincount(number, minlength=8)\n"
        "    total = Arithmetic(precision).grouped(\n"
        "        li['l_quantity'][keep].astype(np.int64), number, 8)\n"
        "    rows = [(n, int(count[n]), dec(total[n], 2)) for n in range(8) if count[n]]\n"
        "    return {'rows': rows, 'tie_rows': []}\n"),
    "datasets/tpch/linenumber.py": (
        "import numpy as np\n\n"
        "GIVES = {'lineitem': {'l_linenumber': {'lineitem': ['l_orderkey']}}}\n\n\n"
        "def generate(scale_factor, wanted, have):\n"
        "    key = have['lineitem']['l_orderkey']\n"
        "    first = np.r_[True, key[1:] != key[:-1]]\n"
        "    start = np.maximum.accumulate(np.where(first, np.arange(len(key)), 0))\n"
        "    return {'lineitem': {'l_linenumber': np.arange(len(key)) - start + 1}}\n"),
}


@pytest.fixture
def tiny_root(tmp_path):
    """A root whose BENCHMARK.json holds only cells that this fixture ADDS:
    two tiny configurations, one two-stream mix, one per-layer metric and one
    query with its reference function and its column provider, each a new
    file beside untouched copies of the committed ones."""
    root = tmp_path / "root"
    data = root / "benchmark"
    data.mkdir(parents=True)
    for sub in DATA_DIRS:
        shutil.copytree(os.path.join(REPO, "benchmark", sub), data / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for path, text in ADDED_FILES.items():
        assert not os.path.exists(os.path.join(REPO, "benchmark", path)), path
        (data / path).write_text(text)
    shutil.copy(os.path.join(REPO, "benchmark", "peaks.json"), data / "peaks.json")
    os.symlink(os.path.join(REPO, "trino_tpu"), root / "trino_tpu")
    committed = json.load(open(os.path.join(REPO, "BENCHMARK.json")))

    configs = []
    for base in ("tpch-sf1-compiled", "tpch-sf1-default"):
        name = base.replace("sf1", "tiny")
        cfg = json.load(open(data / "configs" / f"{base}.json"))
        cfg.update(TINY, name=name)
        (data / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        configs.append({"name": name, "source": cfg["source"],
                        "file": f"benchmark/configs/{name}.json",
                        "reduced": ["scale_factor"], "why": "rehearsal"})
    (data / "traffic" / "q1q6-2streams.json").write_text(json.dumps({
        "loop": "closed", "think_s": 0,
        "streams": [{"templates": ["q1", "q6"]}, {"templates": ["q6", "q1"]}],
        "parameters": {
            "q1": json.load(open(data / "traffic" / "q1-stream.json"))["parameters"]["q1"],
            "q6": json.load(open(data / "traffic" / "q6-stream.json"))["parameters"]["q6"],
        },
    }))
    (data / "metrics" / "queries_listed.py").write_text(
        "def read(run):\n    return float(len(run['infos'])) or None\n")
    cells = [
        ("q1-tiny-compiled", "tpch-tiny-compiled", "q1-stream"),
        ("q6-tiny-compiled", "tpch-tiny-compiled", "q6-stream"),
        ("q3-tiny-compiled", "tpch-tiny-compiled", "q3-stream"),
        ("q1-tiny-default", "tpch-tiny-default", "q1-stream"),
        ("mixed-tiny-compiled", "tpch-tiny-compiled", "q1q6-2streams"),
        ("lines-tiny-default", "tpch-tiny-default", "lines-stream"),
    ]
    bench = dict(committed)
    bench["configs"] = configs
    bench["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": 1, "why": "rehearsal"} for n, c, t in cells
    ]
    for section in ("end_to_end", "per_layer"):
        bench[section] = [
            {k: v for k, v in m.items() if k != "workloads"} for m in committed[section]
        ]
    bench["end_to_end"].append(
        {"name": "p95_s", "unit": "s", "better": "lower", "bound": 0.1, "source": "host_clock"})
    # readers whose files are committed for the compiled tier's later cells
    for name in ("retraces", "dispatches", "h2d_bytes"):
        bench["per_layer"].append(
            {"name": name, "unit": "1/query", "better": "lower", "source": "program_counter",
             "layer": "engine, program cache", "moves": "query_s",
             "workloads": [n for n, c, _ in cells if c.endswith("compiled")]})
    bench["per_layer"].append(
        {"name": "queries_listed", "unit": "1", "better": "higher", "source": "program_counter",
         "layer": "client / protocol", "moves": "query_s", "workloads": ["mixed-tiny-compiled"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)
