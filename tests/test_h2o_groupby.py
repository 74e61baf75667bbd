"""The h2oai db-benchmark groupby table (catalog ``h2o``) and its five basic
questions, at a small size on the CPU: N = 200,000 rows, K = 10, so ``id3``
and ``id6`` have 20,000 groups, which cross ``stream_group_budget`` (4,096).

The benchmark's side (``benchmark/``: the column provider, the reference
functions, the templates, ``compare.decide``) is loaded from its files; the
program's side is the served path in both sessions, the compiled one on a
one-device mesh with the table made to stream through the slab program as
the 1e8-row table does on the chip."""

import hashlib
import json
import os
import time

import numpy as np
import pytest

from benchmark import compare, refdata, reference
from benchmark.files import load_module

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA_ROOT = os.path.join(REPO, "benchmark")
N, K = 200_000, 10
SCHEMA = "g1_2e5_1e1"
QUESTIONS = ["g1q1", "g1q2", "g1q3", "g1q4", "g1q5"]
SESSIONS = {
    "default": {},
    # 4 steps of 65,536 rows; the first holds about 19,000 distinct id6
    "compiled": {"execution_mode": "distributed", "stream_scan_threshold_rows": 1,
                 "stream_device_chunk_rows": 1 << 16},
}


@pytest.fixture(scope="module")
def provider():
    return load_module(os.path.join(DATA_ROOT, "datasets", "h2o", "x.py"), "column provider")


@pytest.fixture(scope="module")
def tables(provider):
    return refdata.Tables({"x": provider.columns(N, K, provider.NAMES)}, provider.LABELS)


@pytest.fixture(scope="module")
def served():
    """One server over a one-device mesh (the slab path is the one-device
    path) and a connection a session."""
    from trino_tpu import client
    from trino_tpu.engine import Engine
    from trino_tpu.parallel.mesh import make_mesh
    from trino_tpu.server.http import TrinoTpuServer

    engine = Engine()
    engine.mesh = make_mesh(1)
    server = TrinoTpuServer(engine=engine, port=0).start()
    try:
        yield {
            name: client.Connection(
                server.base_uri,
                client.ClientSession(catalog="h2o", schema=SCHEMA, properties=dict(props)))
            for name, props in SESSIONS.items()
        }
    finally:
        server.stop()


def template(question):
    base = os.path.join(DATA_ROOT, "templates", question)
    with open(base + ".sql", encoding="utf-8") as f:
        sql = f.read().format(SCHEMA=f"h2o.{SCHEMA}").strip()
    with open(base + ".json", encoding="utf-8") as f:
        return sql, json.load(f)


def verdict(question, rows, tables):
    _, meta = template(question)
    ref = reference.Reference(tables, data_root=DATA_ROOT)
    return compare.decide(
        [{"template": question, "params": {}, "rows": rows}],
        lambda name, params: ref.answer(meta["reference"], params),
        {question: meta["sort_key"]})


def last_query(conn):
    """The newest query's ``/v1/query`` record, once it has ended (the query
    is FINISHING until just after the client has its last page)."""
    deadline = time.monotonic() + 5.0
    while True:
        info = conn.list_queries()[-1]
        if info["state"] == "FINISHED" or time.monotonic() > deadline:
            assert info["state"] == "FINISHED"
            return info
        time.sleep(0.01)


def last_stats(conn):
    return last_query(conn)["queryStats"]


# (a) the program's table is the reference's, column by column


def test_the_connectors_columns_are_the_providers(provider, tables):
    from trino_tpu.connectors.h2o import COLUMNS, H2oConnector

    conn = H2oConnector(split_rows=1 << 16)
    names = [name for name, _ in COLUMNS]
    assert tuple(names) == provider.NAMES
    assert conn.estimate_rows(SCHEMA, "x") == N
    splits = conn.get_splits(SCHEMA, "x", target_splits=8)
    assert len(splits) == 4
    parts = [conn.read_split(SCHEMA, "x", names, s) for s in splits]
    for j, name in enumerate(names):
        ours = np.concatenate([np.asarray(p.columns[j].data) for p in parts])
        theirs = tables["x"][name]
        assert ours.dtype == theirs.dtype, name
        assert hashlib.sha256(ours.tobytes()).hexdigest() == \
            hashlib.sha256(theirs.tobytes()).hexdigest(), name
    # a dictionary's text is the provider's label of the code
    for j, name in enumerate(names[:3]):
        d = parts[0].columns[j].dictionary
        assert [d.values[c] for c in (0, 9)] == [tables.labels["x"][name][c] for c in (0, 9)]
    assert parts[0].columns[2].dictionary.values[N // K - 1] == "id0000020000"


def test_the_columns_have_the_sources_ranges(tables):
    x = tables["x"]
    spans = {"id1": (0, K - 1), "id2": (0, K - 1), "id3": (0, N // K - 1), "id4": (1, K),
             "id5": (1, K), "id6": (1, N // K), "v1": (1, 5), "v2": (1, 15)}
    for name, (lo, hi) in spans.items():
        assert (int(x[name].min()), int(x[name].max())) == (lo, hi), name
    assert 0 <= int(x["v3"].min()) < 10_000 and 99_990_000 < int(x["v3"].max()) < 100_000_000
    assert len(np.unique(x["id6"])) > 4 * 4096  # the groups cross the budget twice over


def test_schemas_are_the_sources_sizes_by_name():
    from trino_tpu.connectors import h2o

    assert h2o.sizes("g1_1e8") == (100_000_000, 100)
    assert h2o.sizes("g1_1e7") == (10_000_000, 100)
    assert h2o.sizes(SCHEMA) == (N, K)
    conn = h2o.H2oConnector()
    assert {"g1_1e8", "g1_1e7", SCHEMA} <= set(conn.list_schemas())
    assert conn.get_table("g1_1e8", "x").column("v3").type == h2o.V3
    assert conn.get_table("sf1", "x") is None and conn.get_table("g1_1e8", "y") is None
    for bad in ("g1_1e8_3e0", "g2_1e8", "g1_100"):
        with pytest.raises(KeyError):
            h2o.sizes(bad)


# (b) the reference functions against a group-by in Python integers


def plain_group_by(x, keys, sums=(), averages=()):
    groups = {}
    columns = [x[c].tolist() for c in (*keys, *sums, *averages)]
    for row in zip(*columns):
        state = groups.setdefault(row[:len(keys)], [0] * (len(row) - len(keys) + 1))
        state[0] += 1
        for j, v in enumerate(row[len(keys):]):
            state[j + 1] += v
    return sorted(groups.items())


PLAIN = {
    "g1q1": (["id1"], ["v1"], []), "g1q2": (["id1", "id2"], ["v1"], []),
    "g1q3": (["id3"], ["v1"], ["v3"]), "g1q4": (["id4"], [], ["v1", "v2", "v3"]),
    "g1q5": (["id6"], ["v1", "v2", "v3"], []),
}


@pytest.mark.parametrize("question", QUESTIONS)
def test_the_reference_equals_a_group_by_in_python_integers(tables, question):
    from decimal import Decimal

    keys, sums, averages = PLAIN[question]
    labels = tables.labels["x"]
    want = []
    for key, (n, *totals) in plain_group_by(tables["x"], keys, sums, averages):
        row = [labels[c][code] if c in labels else code for c, code in zip(keys, key)]
        for c, total in zip(sums + averages, totals):
            if c in averages and c != "v3":
                row.append(total / n)  # avg(BIGINT) is a DOUBLE
            elif c in averages:
                row.append(Decimal((2 * total + n) // (2 * n)).scaleb(-6))
            else:
                row.append(Decimal(total).scaleb(-6) if c == "v3" else total)
        want.append(tuple(row))
    got = reference.Reference(tables, data_root=DATA_ROOT).answer(question, {})
    assert got["tie_rows"] == [] and got["rows"] == want
    # drawn with replacement: of 20,000 ids with 10 rows each one or two stay
    # away (at 1e8 rows, 100 a group, all 1,000,000 come)
    full = {"g1q1": K, "g1q2": K * K, "g1q3": N // K, "g1q4": K, "g1q5": N // K}[question]
    assert full - 5 <= len(want) <= full and (full > K * K or len(want) == full)


def test_an_exact_sum_that_float64_could_not_hold_is_refused():
    groupby = load_module(os.path.join(DATA_ROOT, "groupby.py"), "shared sums")
    values = np.array([1 << 52, 1 << 52], dtype=np.int64)
    group = np.zeros(2, dtype=np.int64)
    with pytest.raises(ValueError):
        groupby.sums(values, group, groupby.counts(group, 1))
    assert groupby.sums(values >> 1, group, groupby.counts(group, 1)) == [1 << 52]


# (c) both sessions against the reference, through the comparison that
# decides ``correct``; (e) the ladder's counters on the way


@pytest.mark.parametrize("session", sorted(SESSIONS))
@pytest.mark.parametrize("question", QUESTIONS)
def test_a_question_is_correct_in_a_session(served, tables, question, session):
    sql, _ = template(question)
    conn = served[session]
    rows, _ = conn.execute(sql)
    result = verdict(question, rows, tables)
    assert result["correct"] is True, result["compared"]
    assert all(c["value"] == 0 for c in result["compared"].values())
    cold = last_stats(conn)
    assert cold["resultRows"] == len(rows)
    rows_again, _ = conn.execute(sql)
    warm = last_stats(conn)
    assert rows_again == rows
    # every question has a grouped aggregate, so both counters are reported
    assert 1 <= warm["aggAttempts"] <= cold["aggAttempts"]
    assert warm["groupBudgetGrowths"] <= cold["groupBudgetGrowths"]
    assert cold["groupBudgetGrowths"] >= (1 if question in ("g1q3", "g1q5") else 0)
    if session == "compiled":
        # the grown budgets stay in the fingerprint's store: a warm query
        # does not climb the ladder again, and compiles nothing
        assert (warm["aggAttempts"], warm["groupBudgetGrowths"]) == (1, 0)
        assert warm["xlaCompiles"] == 0
        assert warm["phaseMs"]["slab"] > 0


def test_the_slabs_budget_grows_to_the_groups_it_counted(served):
    """20,000 groups from a budget of 4,096: the first chunk of 65,536 rows
    holds about 19,000 distinct ``id6``, and the flag carries that count, so
    the budget goes to 32,768 at once and no rung of the ladder lies between
    (4,096 -> 16,384 -> 65,536 before)."""
    conn = served["compiled"]
    conn.execute(template("g1q5")[0])
    caps = {v["site"]: v for v in last_query(conn)["exchangeStats"]["capacities"].values()}
    assert caps["agg@2#0"]["value"] == 32768
    assert caps["agg@2#0"]["provenance"] == "default+grown"


def test_the_grown_pass_of_the_cold_ladder_runs_wide():
    """q5 cold on a server of its own: the first pass (budget 4,096) takes the
    session's width, 4 steps of 65,536 rows; the budget grows to 32,768 and
    the pass at it takes one step of 524,288 (16 rows a group of the budget,
    ``slab_step_rows``): the narrow pass's width is not remembered against
    it. The second query finds the wide program: nothing traced, nothing
    staged again, ``slabSteps`` 1."""
    import urllib.request

    from trino_tpu import client
    from trino_tpu.engine import Engine
    from trino_tpu.parallel.mesh import make_mesh
    from trino_tpu.server.http import TrinoTpuServer

    engine = Engine()
    engine.mesh = make_mesh(1)
    server = TrinoTpuServer(engine=engine, port=0).start()
    try:
        conn = client.Connection(server.base_uri, client.ClientSession(
            catalog="h2o", schema=SCHEMA, properties=dict(SESSIONS["compiled"])))
        sql = template("g1q5")[0]
        infos, slabs = [], []
        for _ in range(2):
            rows, _ = conn.execute(sql)
            info = last_query(conn)
            with urllib.request.urlopen(
                    f"{conn.base_uri}/v1/query/{info['queryId']}/timeline") as f:
                timeline = json.load(f)
            spans = timeline["spans"] if isinstance(timeline, dict) else timeline
            infos.append(info)
            slabs.append([s["attrs"] for s in spans if s["name"] == "stream.slab"])
    finally:
        server.stop()
    cold, warm = slabs
    base = SESSIONS["compiled"]["stream_device_chunk_rows"]
    assert [(a["groups"], a["cap"], a["baseCap"], a["steps"], a["cacheHit"])
            for a in cold[:2]] == [
        (4096, base, base, 4, False), (32768, 8 * base, base, 1, False)]
    # a third pass, where the final aggregate's budget grew, hits the store
    assert all((a["cap"], a["cacheHit"]) == (8 * base, True) for a in cold[2:])
    assert [(a["groups"], a["cap"], a["baseCap"], a["steps"], a["cacheHit"], a["attempt"])
            for a in warm] == [(32768, 8 * base, base, 1, True, 1)]
    assert all(a["site"] == "agg@2#0" and a["groupBy"] == "sort" for a in cold + warm)
    cold_info, warm_info = infos
    assert cold_info["queryStats"]["slabSteps"] == warm_info["queryStats"]["slabSteps"] == 1
    assert cold_info["ingestStats"]["h2d_bytes"] == 4 * 8 * (1 << 22)  # 4 columns, padded
    assert warm_info["ingestStats"]["h2d_bytes"] == 0
    assert warm_info["traceCount"] == 0 and warm_info["queryStats"]["xlaCompiles"] == 0
    assert len(rows) > 19_990
    with engine._query_cache_lock:
        keys = [k for e in engine._query_cache.values() for k in e["programs"]]
    assert not [k for k in keys if isinstance(k, tuple) and k[0] == "slabcap"]
    assert sorted(k[2:4] for k in keys if isinstance(k, tuple) and k[0] == "slab") == [
        (4096, base), (32768, 8 * base)]


def test_the_default_sessions_ladder_names_its_capacities(served):
    import urllib.request

    conn = served["default"]
    conn.execute(template("g1q5")[0])
    info = last_query(conn)
    with urllib.request.urlopen(f"{conn.base_uri}/v1/query/{info['queryId']}/timeline") as f:
        timeline = json.load(f)
    spans = timeline["spans"] if isinstance(timeline, dict) else timeline
    agg = [s for s in spans if s["name"] == "op:Aggregate"]
    assert len(agg) == 1
    assert agg[0]["attrs"]["capacities"] == [4096, 32768]
    assert agg[0]["attrs"]["attempts"] == agg[0]["attrs"]["aggAttempts"] == 2
    assert agg[0]["attrs"]["groupBudgetGrowths"] == 1
    assert info["queryStats"]["aggAttempts"] == 2


# (d) planted faults read ``correct`` false

# (near the answer's end: ``compare_answer`` looks through the whole answer
# for a row that may stand in a mismatching one's place, and a group dropped
# early shifts every row after it)
FAULTS = {
    "a group dropped": lambda rows: rows[:-8] + rows[-7:],
    "a sum off by one": lambda rows: rows[:-8] + [
        rows[-8][:1] + (rows[-8][1] + 1,) + rows[-8][2:]] + rows[-7:],
    "two rows swapped": lambda rows: rows[:-8] + [rows[-7], rows[-8]] + rows[-6:],
    "a decimal off in its last place": lambda rows: rows[:-8] + [
        rows[-8][:3] + (rows[-8][3] + type(rows[-8][3])("0.000001"),)] + rows[-7:],
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_an_altered_q5_answer_is_not_correct(tables, fault):
    good = [tuple(r) for r in reference.Reference(tables, data_root=DATA_ROOT)
            .answer("g1q5", {})["rows"]]
    assert verdict("g1q5", good, tables)["correct"] is True
    bad = verdict("g1q5", FAULTS[fault](good), tables)
    assert bad["correct"] is False
    assert bad["compared"]["answers_wrong"]["value"] == 1
    assert bad["compared"]["values_wrong"]["value"] >= 1


# (f) the float32 control


@pytest.mark.parametrize("question", ["g1q3", "g1q4", "g1q5"])
def test_the_float32_control_is_not_correct(tables, question):
    """``v3`` runs to 99,999,999 at scale 6, past float32's 2^24 whole
    numbers, so a float32 engine loses its sums at any size."""
    exact, low = (reference.Reference(tables, p, DATA_ROOT).answer(question, {})["rows"]
                  for p in ("exact", "float32"))
    assert len(exact) == len(low)
    _, meta = template(question)
    # the first 200 groups: ``compare_answer`` looks through the whole answer
    # once for every row that differs, and here nearly every row does
    result = compare.decide(
        [{"template": question, "params": {}, "rows": low[:200]}],
        lambda name, params: {"rows": exact[:200], "tie_rows": []},
        {question: meta["sort_key"]})
    assert result["correct"] is False
    assert 0 < result["compared"]["widest_gap"]["value"] < 1e-3
    assert result["compared"]["answers_missing"]["value"] == 0


def test_float32_holds_the_small_sums_of_q1_and_q2(tables):
    """``sum(v1)`` of a group's 20,000 rows (q1) or 2,000 (q2) is under 2^24,
    so float32 holds it: those two questions have no float32 control here.
    (At 1e8 rows q1's sums, about 3,000,000 a group, and q2's, 30,000, still
    fit: the control of this data set is ``v3``.)"""
    for question in ("g1q1", "g1q2"):
        exact, low = (reference.Reference(tables, p, DATA_ROOT).answer(question, {})
                      for p in ("exact", "float32"))
        assert exact == low


# (g) the committed entries and files


def test_the_committed_cell_and_its_files():
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == "g1-q5-compiled")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "h2o-groupby-1e8", "g1-q5-stream", 1)
    checks = load_module(os.path.join(DATA_ROOT, "tests", "test_h2o_config.py"), "contract checks")
    # its "the new ones come last" is of the PR that brought the cell: what
    # later PRs appended (they may only append) comes after it
    for entries, last in (("configs", "h2o-groupby-1e8"), ("workloads", "g1-q5-compiled")):
        names = [e["name"] for e in checks.BENCH[entries]]
        assert names[:names.index(last) + 1] == [e["name"] for e in bench[entries]][:names.index(last) + 1]
        del checks.BENCH[entries][names.index(last) + 1:]
    for name in sorted(vars(checks)):
        if name.startswith("test_"):
            getattr(checks, name)()
