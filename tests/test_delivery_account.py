"""``queryStats.delivery``: the half of a query after ``execute_plan`` has
returned, timed inside the server a page at a time, and the ``result.page``
span; ``phaseMs.devicePull``, the host's wait for the device. Over a real
``TrinoTpuServer(port=0)`` and ``client.Connection`` on the CPU, with the
streaming pager and with the fixed-row path where a case applies to both."""

import json
import time
import urllib.request

import pytest

from trino_tpu import client
from trino_tpu.config import ServerConfig
from trino_tpu.server import http as http_module
from trino_tpu.server import querymanager
from trino_tpu.server.http import TrinoTpuServer

#: 2,000 rows: 20 pages of 100 rows under a 2,600-byte budget (26 B a row
#: as the pager sizes it) ...
SQL = "select o_orderkey, o_totalprice from tpch.tiny.orders where o_orderkey <= 8000"
ROWS = 2000
PAGERS = ["streaming", "fixed"]


@pytest.fixture
def serve(monkeypatch):
    """``serve(pager)`` boots a server whose pages hold 100 rows."""
    servers = []

    def boot(pager):
        # ... and the fixed-row path cuts its pages at the same 100 rows
        monkeypatch.setattr(http_module, "PAGE_ROWS", 100)
        budget = 2600 if pager == "streaming" else 0
        server = TrinoTpuServer(
            port=0, server_config=ServerConfig(result_page_max_bytes=budget)
        ).start()
        servers.append(server)
        return server, client.Connection(server.base_uri, client.ClientSession())

    yield boot
    for server in servers:
        server.stop()


@pytest.fixture
def fetched(monkeypatch):
    """What the client fetched, a response at a time."""
    seen = []
    plain = client.StatementClient._request_once

    def counted(self, method, uri, body=None):
        payload = plain(self, method, uri, body)
        seen.append((method, uri, payload))
        return payload

    monkeypatch.setattr(client.StatementClient, "_request_once", counted)
    return seen


def _get(server, path):
    with urllib.request.urlopen(f"{server.base_uri}{path}", timeout=10) as r:
        return json.loads(r.read().decode())


def _info(server, conn, sql=SQL):
    listed = [q for q in conn.list_queries() if q["query"] == sql][-1]
    return _get(server, f"/v1/query/{listed['queryId']}")


def _delivery(server, conn, sql=SQL):
    return _info(server, conn, sql)["queryStats"]["delivery"]


def _data_pages(fetched):
    return [p for _, _, p in fetched if "data" in p]


@pytest.mark.parametrize("pager", PAGERS)
def test_pages_rows_and_bytes_are_what_the_client_fetched(serve, fetched, monkeypatch, pager):
    server, conn = serve(pager)
    sizes = []
    plain = http_module._statement_response

    def sized(out):
        response = plain(out)
        if "data" in out:
            sizes.append(len(response.body))
        return response

    monkeypatch.setattr(http_module, "_statement_response", sized)
    rows, _ = conn.execute(SQL)
    assert len(rows) == ROWS
    pages = _data_pages(fetched)
    d = _delivery(server, conn)
    assert len(pages) == 20
    assert d["pages"] == len(pages)
    assert d["rows"] == sum(len(p["data"]) for p in pages) == ROWS
    # the bodies as they went on the wire: the client's own re-encoding of
    # what it parsed is as long (json.dumps both times, the same separators)
    assert d["bodyBytes"] == sum(sizes) == sum(len(json.dumps(p)) for p in pages)


@pytest.mark.parametrize("pager", PAGERS)
def test_a_slow_client_shows_in_the_gap_alone(serve, fetched, monkeypatch, pager):
    server, conn = serve(pager)
    plain = client.StatementClient._advance_state

    def slow(self, payload):
        plain(self, payload)
        if "data" in payload and payload.get("nextUri"):
            time.sleep(0.05)  # the client thinking between two pages

    monkeypatch.setattr(client.StatementClient, "_advance_state", slow)
    conn.execute(SQL)
    d = _delivery(server, conn)
    assert d["pages"] == 20
    assert d["clientGapMs"] >= 50.0 * (d["pages"] - 1)
    assert d["buildMs"] + d["encodeMs"] < 50.0
    assert d["wallMs"] >= d["clientGapMs"]


def test_a_slow_sizing_shows_in_the_build_alone(serve, monkeypatch):
    """The pager sizes a page by its one encoding, inside ``buildMs``; the body
    assembled around its bytes is all ``encodeMs`` holds."""
    server, conn = serve("streaming")
    conn.execute(SQL)
    before = _delivery(server, conn)
    plain = querymanager.encode_rows
    calls = []

    def slow_encode(rows):
        calls.append(rows)
        time.sleep(0.005)  # 5 ms a call more
        return plain(rows)

    # the pager's global alone: the fixed-row path imported its own name
    monkeypatch.setattr(querymanager, "encode_rows", slow_encode)
    sql = SQL.replace("8000", "8001")
    conn.execute(sql)
    d = _delivery(server, conn, sql)
    assert d["rows"] >= ROWS and d["pages"] >= 20
    assert len(calls) >= d["pages"]  # an encoding a page at the least
    assert d["buildMs"] >= 5.0 * len(calls)
    assert d["buildMs"] >= before["buildMs"] + 4.5 * d["pages"]
    assert d["encodeMs"] < 2.5 * d["pages"]


def test_a_page_cut_again_is_counted(serve, monkeypatch):
    server, conn = serve("streaming")
    # pages of up to 4,096 rows: the first run holds all 2,000 and passes
    # the 2,600-byte budget long before its last row
    monkeypatch.setattr(http_module, "PAGE_ROWS", 4096)
    rows, _ = conn.execute(SQL)
    d = _delivery(server, conn)
    q = next(q for q in server.query_manager.queries() if q.sql == SQL)
    again = querymanager.ResultPager(q.result.rows, 2600, 4096)
    recut, token = 0, 0
    while True:
        page, more = again.page(token)
        recut += page.recut
        if not more:
            break
        token += 1
    assert len(rows) == ROWS and d["pages"] == q._pager.pages_produced == token + 1
    assert d["recutPages"] == recut >= 1


def test_the_fixed_row_path_builds_nothing(serve):
    server, conn = serve("fixed")
    conn.execute(SQL)
    d = _delivery(server, conn)
    assert d["buildMs"] == 0 and d["pages"] == 20 and d["encodeMs"] > 0


@pytest.mark.parametrize("pager", PAGERS)
def test_the_parts_lie_inside_the_wall(serve, pager):
    server, conn = serve(pager)
    conn.execute(SQL)
    d = _delivery(server, conn)
    assert set(d) == {"pages", "rows", "recutPages", "bodyBytes", "buildMs",
                      "encodeMs", "clientGapMs", "wallMs"}
    assert all(v >= 0 for v in d.values())
    assert d["recutPages"] == 0  # every page is cut at its 100 rows
    assert d["buildMs"] + d["encodeMs"] + d["clientGapMs"] <= d["wallMs"] + 1.0
    assert d["encodeMs"] > 0 and d["clientGapMs"] > 0


@pytest.mark.parametrize("pager", PAGERS)
def test_a_token_requested_twice_counts_once(serve, monkeypatch, pager):
    server, conn = serve(pager)
    plain = client.StatementClient._request_once
    twice = []

    def retried(self, method, uri, body=None):
        payload = plain(self, method, uri, body)
        if "data" in payload and not twice:
            twice.append(len(payload["data"]))
            again = plain(self, method, uri, body)  # the idempotent retry
            assert again["data"] == payload["data"]
        return payload

    monkeypatch.setattr(client.StatementClient, "_request_once", retried)
    rows, _ = conn.execute(SQL)
    d = _delivery(server, conn)
    assert twice and len(rows) == ROWS
    assert (d["pages"], d["rows"]) == (20, ROWS)


@pytest.mark.parametrize("pager", PAGERS)
def test_the_final_account_is_served_after_the_last_page(serve, pager):
    """``_phase_stats`` is kept from the moment the result was ready; the
    account is read live, by ``GET /v1/query/{id}`` and by ``GET /v1/query``."""
    server, conn = serve(pager)
    stmt = client.StatementClient(server.base_uri, SQL, conn.session)
    rows = stmt.rows()
    for _ in range(150):  # into the second page
        next(rows)
    early = _get(server, f"/v1/query/{stmt.query_id}")["queryStats"]
    assert 1 <= early["delivery"]["pages"] <= 2
    assert early["phaseMs"]["execute"] > 0  # the reduction is cached by now
    assert len(list(rows)) == ROWS - 150
    late = _get(server, f"/v1/query/{stmt.query_id}")["queryStats"]
    listed = next(q for q in conn.list_queries() if q["queryId"] == stmt.query_id)
    assert late["delivery"]["pages"] == 20 and late["delivery"]["rows"] == ROWS
    assert listed["queryStats"]["delivery"] == late["delivery"]
    assert late["delivery"]["wallMs"] > early["delivery"]["wallMs"]
    assert late["phaseMs"] == early["phaseMs"]


@pytest.mark.parametrize("pager", PAGERS)
def test_one_span_a_data_page_under_the_root(serve, fetched, pager):
    server, conn = serve(pager)
    conn.execute(SQL)
    info = _info(server, conn)
    spans = _get(server, f"/v1/query/{info['queryId']}/timeline")["spans"]
    root = next(s for s in spans if s["name"] == "query")
    pages = sorted((s for s in spans if s["name"] == "result.page"),
                   key=lambda s: s["attrs"]["token"])
    served = _data_pages(fetched)
    assert len(pages) == len(served) == 20
    assert [s["attrs"]["token"] for s in pages] == list(range(20))
    for s, page in zip(pages, served):
        assert s["parentId"] == root["spanId"] and root["parentId"] is None
        assert s["traceId"] == info["queryId"]
        assert s["attrs"]["rows"] == len(page["data"])
        assert s["attrs"]["bytes"] == len(json.dumps(page))
        assert s["startNs"] < s["endNs"]
    # the polls that carried no data opened none
    assert len(fetched) > len(served)
    assert sum(s["attrs"]["bytes"] for s in pages) \
        == info["queryStats"]["delivery"]["bodyBytes"]


@pytest.mark.parametrize("pager", PAGERS)
def test_the_counters_are_exact_past_the_span_sink_s_cap(serve, pager):
    server, conn = serve(pager)
    server.span_sink.max_spans_per_trace = 8
    rows, _ = conn.execute(SQL)
    info = _info(server, conn)
    spans = _get(server, f"/v1/query/{info['queryId']}/timeline")["spans"]
    assert len(spans) == 8
    d = info["queryStats"]["delivery"]
    assert (d["pages"], d["rows"]) == (20, len(rows)) == (20, ROWS)
    assert d["bodyBytes"] > 20 * 100 * 20


def test_a_failed_and_a_cancelled_query_serve_the_account(serve):
    server, conn = serve("streaming")
    with pytest.raises(client.QueryFailure):
        conn.execute("select no_such_column from tpch.tiny.orders")
    failed = [q for q in conn.list_queries() if q["state"] == "FAILED"][-1]
    d = _get(server, f"/v1/query/{failed['queryId']}")["queryStats"]["delivery"]
    assert d == {"pages": 0, "rows": 0, "recutPages": 0, "bodyBytes": 0,
                 "buildMs": 0.0, "encodeMs": 0.0, "clientGapMs": 0.0,
                 "wallMs": 0.0}
    # cancelled in the middle of its delivery: what went out stays counted
    stmt = client.StatementClient(server.base_uri, SQL, conn.session)
    rows = stmt.rows()
    for _ in range(150):
        next(rows)
    urllib.request.urlopen(
        urllib.request.Request(stmt._next_uri, method="DELETE"), timeout=10
    ).close()
    info = _get(server, f"/v1/query/{stmt.query_id}")
    d = info["queryStats"]["delivery"]
    assert 1 <= d["pages"] <= 2 and d["rows"] == 100 * d["pages"]
    assert d["wallMs"] >= d["buildMs"] + d["encodeMs"] + d["clientGapMs"] - 1.0
    # and cancelled before it ever ran
    from trino_tpu.config import Session

    q = querymanager.ManagedQuery("select 1", Session())
    q.cancel()
    info = q.info()
    assert info["state"] == "CANCELED"
    assert info["queryStats"]["delivery"]["pages"] == 0
    assert info["queryStats"]["delivery"]["wallMs"] == 0.0


_Q1 = (
    "select l_returnflag, l_linestatus, sum(l_quantity), count(*) "
    "from tpch.tiny.lineitem where l_shipdate <= date '1998-12-01' - interval '90' day "
    "group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"
)


def test_the_device_pull_is_a_phase_of_the_compiled_session_alone():
    from trino_tpu.engine import Engine
    from trino_tpu.parallel.mesh import make_mesh

    engine = Engine()
    engine.mesh = make_mesh(1)  # the slab path is the one-device path
    server = TrinoTpuServer(engine=engine, port=0).start()
    try:
        compiled = client.Connection(server.base_uri, client.ClientSession(properties={
            "execution_mode": "distributed", "stream_scan_threshold_rows": 1}))
        default = client.Connection(server.base_uri, client.ClientSession())
        assert compiled.execute(_Q1)[0] == default.execute(_Q1 + " ")[0]
        ours = _info(server, compiled, _Q1)["queryStats"]["phaseMs"]
        theirs = _info(server, default, _Q1 + " ")["queryStats"]["phaseMs"]
    finally:
        server.stop()
    assert 0 < ours["devicePull"] <= ours["execute"]
    assert theirs["devicePull"] == 0 and theirs["execute"] > 0
