"""Serving-edge tests: event-loop front door robustness.

Covers the overload layer on top of the statement protocol: maxWait
parsing, token-bucket shedding with Retry-After, slowloris read
timeouts, client-abandonment reaping (cancel + admission slot release),
byte-budgeted streaming result pages, deterministic resource-group
waiter expiry, and graceful drain under load with zero dropped in-flight
queries.
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from trino_tpu.client import ClientSession, Connection
from trino_tpu.config import ServerConfig
from trino_tpu.engine import Engine
from trino_tpu.server.eventloop import (
    TenantRateLimiter,
    TokenBucket,
    parse_max_wait,
)


# ---------------------------------------------------------------------------
# maxWait helper (consolidated parse/clamp/NaN-guard)
# ---------------------------------------------------------------------------


class TestParseMaxWait:
    def test_plain_values_pass_through(self):
        assert parse_max_wait("5") == 5.0
        assert parse_max_wait(2.5) == 2.5
        assert parse_max_wait(0) == 0.0

    def test_clamped_to_bounds(self):
        assert parse_max_wait("99") == 30.0
        assert parse_max_wait("-3") == 0.0
        assert parse_max_wait("1e9") == 30.0

    def test_garbage_falls_back_to_default(self):
        assert parse_max_wait("soon", default=1.0) == 1.0
        assert parse_max_wait(None, default=2.0) == 2.0
        assert parse_max_wait("", default=1.0) == 1.0

    def test_nan_and_inf_guard(self):
        # a malicious maxWait=nan must never wedge a poll loop
        assert parse_max_wait("nan", default=1.0) == 1.0
        assert parse_max_wait(float("nan"), default=1.0) == 1.0
        assert parse_max_wait("inf", default=1.0) == 1.0
        assert parse_max_wait("-inf", default=1.0) == 1.0

    def test_custom_bounds(self):
        assert parse_max_wait("0.5", default=0.0, lo=1.0, hi=10.0) == 1.0
        assert parse_max_wait("20", default=0.0, lo=1.0, hi=10.0) == 10.0


# ---------------------------------------------------------------------------
# token buckets
# ---------------------------------------------------------------------------


class TestTokenBucket:
    def test_burst_then_shed(self):
        b = TokenBucket(rate=1.0, burst=2.0)
        assert b.try_acquire(now=100.0) == 0.0
        assert b.try_acquire(now=100.0) == 0.0
        wait = b.try_acquire(now=100.0)
        assert wait > 0.0  # bucket empty: hint until next token

    def test_refills_over_time(self):
        b = TokenBucket(rate=10.0, burst=1.0)
        assert b.try_acquire(now=50.0) == 0.0
        assert b.try_acquire(now=50.0) > 0.0
        assert b.try_acquire(now=50.2) == 0.0  # 0.2s * 10/s = 2 tokens

    def test_tenant_isolation(self):
        lim = TenantRateLimiter(qps=0.001, burst=1.0)
        assert lim.try_acquire("alice") == 0.0
        assert lim.try_acquire("alice") > 0.0  # alice exhausted her burst
        assert lim.try_acquire("bob") == 0.0   # bob unaffected

    def test_disabled_when_qps_zero(self):
        lim = TenantRateLimiter(qps=0.0, burst=1.0)
        for _ in range(100):
            assert lim.try_acquire("anyone") == 0.0


# ---------------------------------------------------------------------------
# deterministic resource-group waiter expiry
# ---------------------------------------------------------------------------


class TestTimerDrivenReap:
    def test_waiter_expires_without_activity(self):
        """Regression: a queue-timeout waiter must be rejected on time by
        the armed reap timer even when NO other submit/finish activity
        ever happens (previously expiry was only opportunistic)."""
        from trino_tpu.server.resourcegroups import (
            GroupConfig,
            ResourceGroupManager,
            Selector,
        )

        rgm = ResourceGroupManager(max_wait_seconds=0.3)
        rgm.configure(
            [GroupConfig("root", max_queued=10, hard_concurrency_limit=1)],
            [Selector(group="root")],
        )
        # occupy the only slot
        group, admitted = rgm.submit("holder", "", lambda g, e: None)
        assert admitted
        fired = []
        rgm.submit("waiter", "", lambda g, e: fired.append(e))
        # no finish(), no further submit() — only the timer can reap
        deadline = time.monotonic() + 2.0
        while not fired and time.monotonic() < deadline:
            time.sleep(0.02)
        assert fired, "waiter expiry never fired without activity"
        assert fired[0] is not None  # QueryQueueFullError
        assert rgm.info()[0]["queuedQueries"] == 0

    def test_abandon_frees_queue_slot(self):
        from trino_tpu.server.resourcegroups import (
            GroupConfig,
            ResourceGroupManager,
            Selector,
        )

        rgm = ResourceGroupManager(max_wait_seconds=30.0)
        rgm.configure(
            [GroupConfig("root", max_queued=10, hard_concurrency_limit=1)],
            [Selector(group="root")],
        )
        group, admitted = rgm.submit("holder", "", lambda g, e: None)
        assert admitted
        cb = lambda g, e: None  # noqa: E731
        g2, admitted2 = rgm.submit("waiter", "", cb)
        assert not admitted2
        assert rgm.info()[0]["queuedQueries"] == 1
        assert rgm.abandon(g2, cb)
        assert rgm.info()[0]["queuedQueries"] == 0
        assert not rgm.abandon(g2, cb)  # idempotent


# ---------------------------------------------------------------------------
# streaming result pager
# ---------------------------------------------------------------------------


def _serve_all(pager):
    """Every page of ``pager`` in token order."""
    pages, token = [], 0
    while True:
        page, more = pager.page(token)
        if page is not None:
            pages.append(page)
        if not more:
            return pages
        token += 1


def _two_pass_rows(rows):
    """``data`` as the server encoded it before a page was encoded once:
    a ``Decimal`` to ``str``, then ``json.dumps`` of the body."""
    from decimal import Decimal

    return [[str(v) if isinstance(v, Decimal) else v for v in row]
            for row in rows]


def _row_cuts(rows, budget, max_rows):
    """Row counts of the pages a row-at-a-time sizing cuts: a page ends at
    ``max_rows`` or at the row whose text brings it to ``budget``."""
    from trino_tpu.server.querymanager import encode_rows

    cuts, n, size = [], 0, 1
    for row in rows:
        size += len(encode_rows(row)) + (2 if n else 0)
        n += 1
        if size + 1 >= budget or n == max_rows:
            cuts.append(n)
            n, size = 0, 1
    return cuts + ([n] if n else [])


def _mixed_rows(n=600):
    from decimal import Decimal

    texts = ["plain", "ünïcødé ✓", 'quote " and \\ backslash', "tab\tnl\n",
             "", "\u2028 sep", "x" * 40]
    return [
        (
            (-1) ** i * (2**63 - 1 - i),
            None if i % 5 == 0 else Decimal(i * 7919) / 100,
            texts[i % len(texts)],
            [0.1, 1e300, -2.5e-8, 3.0][i % 4],
            None if i % 3 == 0 else i % 2 == 0,
        )
        for i in range(n)
    ]


def _wide_rows(n=400):
    return [(i, "w" * 300) for i in range(n)]


def _stepped_rows(n=900):
    # widths step up ten-fold every 150 rows: a run sized by the last
    # page's bytes a row passes the budget where the step falls inside it
    return [(i, "s" * (8 if (i // 150) % 2 == 0 else 80)) for i in range(n)]


class TestResultPager:
    def _pager(self, n_rows=1000, budget=2048):
        from trino_tpu.server.querymanager import ResultPager

        rows = [(i, "x" * 20) for i in range(n_rows)]
        return rows, ResultPager(rows, budget, max_rows_per_page=4096)

    def test_pages_cover_all_rows_in_order(self):
        rows, pager = self._pager()
        pages = _serve_all(pager)
        got = [tuple(r) for p in pages for r in json.loads(p.data)]
        assert got == rows
        assert [p.rows for p in pages] == _row_cuts(rows, 2048, 4096)
        assert pager.pages_produced > 3  # budget forced multiple pages

    def test_buffer_stays_bounded(self):
        _, pager = self._pager(n_rows=5000, budget=1024)
        token = 0
        while True:
            _, more = pager.page(token)
            # at most the served page + the one just produced stay
            # buffered; acked pages are freed as the client advances
            assert pager.buffered_bytes <= 3 * 1024 + 256
            if not more:
                break
            token += 1
        assert pager.pages_produced >= 10
        assert pager.peak_buffered_bytes <= 3 * 1024 + 256

    def test_token_retry_is_idempotent(self):
        _, pager = self._pager()
        first, more1 = pager.page(0)
        again, more2 = pager.page(0)
        assert first.data == again.data and more1 == more2
        assert first.rows == again.rows

    def test_empty_result(self):
        from trino_tpu.server.querymanager import ResultPager

        pager = ResultPager([], 1024)
        page, more = pager.page(0)
        assert page is None and not more

    @pytest.mark.parametrize("budget", [1 << 20, 700], ids=["rows", "bytes"])
    @pytest.mark.parametrize("make", [_mixed_rows, _wide_rows, _stepped_rows])
    def test_served_bodies_parse_as_the_two_pass_encoding(self, make, budget):
        from trino_tpu.server.http import _statement_response
        from trino_tpu.server.querymanager import ResultPager

        rows = make()
        pager = ResultPager(rows, budget, max_rows_per_page=128)
        at = 0
        for token, page in enumerate(_serve_all(pager)):
            head = {"id": "q", "infoUri": "u", "warnings": [], "stats": {},
                    "columns": [], "nextUri": f"n/{token}"}
            body = _statement_response({**head, "data": page}).body
            want = json.dumps(
                {**head, "data": _two_pass_rows(rows[at:at + page.rows])})
            # key for key, value for value, and as many bytes
            assert json.loads(body) == json.loads(want)
            assert len(body) == len(want)
            at += page.rows
        assert at == len(rows)

    @pytest.mark.parametrize(
        "make,budget,max_rows",
        [(_wide_rows, 2000, 4096), (_wide_rows, 5000, 64), (_mixed_rows, 1, 4096),
         (_stepped_rows, 1500, 4096), (_mixed_rows, 900, 4096),
         (_stepped_rows, 1 << 20, 100), (_mixed_rows, 1 << 20, 4096)],
        ids=["wide", "wide-capped", "one-byte", "stepped", "mixed", "stepped-rows",
             "mixed-rows"],
    )
    def test_recuts_are_counted_and_cut_at_the_budget(
        self, monkeypatch, make, budget, max_rows
    ):
        from trino_tpu.server import querymanager
        from trino_tpu.server.querymanager import ResultPager, encode_rows

        run_rows = []
        plain = querymanager.encode_rows

        def spy(arg):
            if isinstance(arg, list):  # a run, not a row sized alone
                run_rows.append(len(arg))
            return plain(arg)

        monkeypatch.setattr(querymanager, "encode_rows", spy)
        rows = make()
        pager = ResultPager(rows, budget, max_rows_per_page=max_rows)
        served, recut, token = [], 0, 0
        while True:
            run_rows.clear()
            page, more = pager.page(token)
            buffered = pager._pages.values()
            assert pager.buffered_bytes == sum(len(p.data) for p in buffered)
            if page is None:
                break
            # a recut page: its runs held rows past its last
            assert page.recut == (sum(run_rows) > page.rows)
            recut += page.recut
            served.append(page)
            token += 1
            if not more:
                break
        got = [tuple(r) for p in served for r in json.loads(p.data)]
        assert got == [tuple(_two_pass_rows([r])[0]) for r in rows]
        assert [p.rows for p in served] == _row_cuts(rows, budget, max_rows)
        at = 0
        for p in served:
            last = rows[at + p.rows - 1]
            # past the budget by at most its last row
            assert p.rows == 1 or len(p.data) - len(encode_rows(last)) - 2 < budget
            at += p.rows
        if budget < 1 << 20:
            assert recut > 0
        else:
            assert recut == 0  # cut by rows alone: every page one run


# ---------------------------------------------------------------------------
# serving edge over real HTTP
# ---------------------------------------------------------------------------


class SleepyEngine(Engine):
    """Engine whose statements take a configurable wall time."""

    def __init__(self, delay_s: float):
        super().__init__()
        self.delay_s = delay_s

    def execute_statement(self, sql, session, query_id=None, fire_events=True):
        time.sleep(self.delay_s)
        return super().execute_statement(
            sql, session, query_id=query_id, fire_events=fire_events
        )


def _post_statement(base_uri: str, sql: str, user: str = "u") -> dict:
    req = urllib.request.Request(
        f"{base_uri}/v1/statement",
        data=sql.encode(),
        method="POST",
        headers={"X-Trino-User": user},
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read().decode())


class TestSlowloris:
    def test_partial_request_times_out(self):
        from trino_tpu.server.http import TrinoTpuServer

        s = TrinoTpuServer(
            server_config=ServerConfig(read_timeout_s=0.2)
        ).start()
        try:
            sock = socket.create_connection((s.host, s.port), timeout=5)
            sock.sendall(b"GET /v1/info HTTP/1.1\r\nHost: x")  # never finishes
            sock.settimeout(5)
            data = sock.recv(4096)
            # server must terminate the connection (408 or plain close),
            # not park a thread on it forever
            assert data == b"" or b"408" in data
            sock.close()
            # and keep serving well-formed requests afterwards
            with urllib.request.urlopen(
                f"{s.base_uri}/v1/info", timeout=5
            ) as r:
                assert r.status == 200
        finally:
            s.stop()

    def test_abrupt_disconnect_mid_poll_is_harmless(self):
        from trino_tpu.server.http import TrinoTpuServer

        s = TrinoTpuServer(engine=SleepyEngine(0.5)).start()
        try:
            out = _post_statement(s.base_uri, "select 1")
            next_uri = out["nextUri"]
            path = next_uri[len(s.base_uri):]
            # long-poll the query, then slam the connection shut mid-wait
            sock = socket.create_connection((s.host, s.port), timeout=5)
            sock.sendall(
                f"GET {path} HTTP/1.1\r\nHost: x\r\n"
                "X-Trino-Max-Wait: 10s\r\n\r\n".encode()
            )
            time.sleep(0.1)
            sock.close()  # parked responder becomes a no-op
            # the server keeps serving; the query still completes
            deadline = time.monotonic() + 5
            state = None
            while time.monotonic() < deadline:
                with urllib.request.urlopen(
                    f"{s.base_uri}/v1/query/{out['id']}", timeout=5
                ) as r:
                    state = json.loads(r.read().decode())["state"]
                if state == "FINISHED":
                    break
                time.sleep(0.05)
            assert state == "FINISHED"
        finally:
            s.stop()


class TestShedding:
    def test_tenant_rate_limit_sheds_with_retry_after(self):
        from trino_tpu.server.http import TrinoTpuServer

        s = TrinoTpuServer(
            server_config=ServerConfig(
                tenant_rate_limit_qps=2.0, tenant_rate_limit_burst=1.0
            )
        ).start()
        try:
            _post_statement(s.base_uri, "select 1", user="alice")
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post_statement(s.base_uri, "select 2", user="alice")
            assert ei.value.code == 503
            assert ei.value.headers.get("Retry-After") is not None
            # another tenant is unaffected
            out = _post_statement(s.base_uri, "select 3", user="bob")
            assert out["id"]
            # shed counter incremented with the right reason
            with urllib.request.urlopen(
                f"{s.base_uri}/v1/metrics?format=json", timeout=5
            ) as r:
                snap = json.loads(r.read().decode())
            shed = [
                v for k, v in snap.get("counters", {}).items()
                if k.startswith("trino_tpu_requests_shed_total")
                and "tenant_rate_limit" in k
            ]
            assert shed and shed[0] >= 1
        finally:
            s.stop()

    def test_client_retries_after_shed_and_succeeds(self):
        from trino_tpu.server.http import TrinoTpuServer

        s = TrinoTpuServer(
            server_config=ServerConfig(
                tenant_rate_limit_qps=2.0, tenant_rate_limit_burst=1.0
            )
        ).start()
        try:
            conn = Connection(
                s.base_uri, ClientSession(user="carol", shed_retry_attempts=4)
            )
            # back-to-back statements: the second is shed at first, and
            # the client's Retry-After backoff carries it through
            assert conn.execute("select 1")[0] == [(1,)]
            assert conn.execute("select 2")[0] == [(2,)]
        finally:
            s.stop()


class TestAbandonedClient:
    def test_unpolled_query_is_canceled_and_slot_freed(self):
        from trino_tpu.server.http import TrinoTpuServer
        from trino_tpu.server.resourcegroups import (
            GroupConfig,
            ResourceGroupManager,
            Selector,
        )

        rgm = ResourceGroupManager(max_wait_seconds=30)
        rgm.configure(
            [GroupConfig("root", max_queued=10, hard_concurrency_limit=1)],
            [Selector(group="root")],
        )
        s = TrinoTpuServer(
            engine=SleepyEngine(1.0),
            resource_groups=rgm,
            server_config=ServerConfig(client_timeout_s=0.3),
        ).start()
        try:
            out = _post_statement(s.base_uri, "select 1")
            qid = out["id"]
            # ... and the client vanishes: no nextUri poll ever happens.
            # within client_timeout_s (+ sweep cadence) the reaper cancels
            deadline = time.monotonic() + 3.0
            state = None
            while time.monotonic() < deadline:
                q = s.query_manager.get(qid)
                state = q.state.get().value if q else None
                if state == "CANCELED":
                    break
                time.sleep(0.05)
            assert state == "CANCELED"
            # the admission slot frees once the engine call unwinds
            deadline = time.monotonic() + 3.0
            while time.monotonic() < deadline:
                if rgm.info()[0]["runningQueries"] == 0:
                    break
                time.sleep(0.05)
            assert rgm.info()[0]["runningQueries"] == 0
        finally:
            s.stop()

    def test_abandoned_queued_query_frees_queue_slot(self):
        """A canceled query that never got admitted must release its
        waiter so it cannot pin the resource-group queue."""
        from trino_tpu.server.querymanager import QueryManager
        from trino_tpu.server.resourcegroups import (
            GroupConfig,
            ResourceGroupManager,
            Selector,
        )
        from trino_tpu.config import Session

        rgm = ResourceGroupManager(max_wait_seconds=30)
        rgm.configure(
            [GroupConfig("root", max_queued=10, hard_concurrency_limit=1)],
            [Selector(group="root")],
        )
        engine = SleepyEngine(1.0)
        qm = QueryManager(engine, resource_groups=rgm)
        qa = qm.create_query("select 1", Session())
        deadline = time.monotonic() + 2.0
        while (
            rgm.info()[0]["runningQueries"] == 0
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        qb = qm.create_query("select 2", Session())
        deadline = time.monotonic() + 2.0
        while (
            rgm.info()[0]["queuedQueries"] == 0
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        assert rgm.info()[0]["queuedQueries"] == 1
        qb.cancel()
        assert rgm.info()[0]["queuedQueries"] == 0
        assert qb.state.get().value == "CANCELED"
        qm.shutdown(wait=False)


class TestStreamingResults:
    def test_paged_bit_identical_and_buffer_bounded(self):
        from trino_tpu.server.http import TrinoTpuServer

        budget = 8 << 10  # tiny page budget: forces many pages
        s = TrinoTpuServer(
            server_config=ServerConfig(result_page_max_bytes=budget)
        ).start()
        try:
            conn = Connection(s.base_uri)
            rows, _ = conn.execute("select o_orderkey from tpch.tiny.orders")
            assert len(rows) == 15000
            assert sorted(r[0] for r in rows) == sorted(
                set(r[0] for r in rows)
            )  # no dup/dropped rows
            # the pager really cut it into many bounded pages
            qs = [
                q for q in s.query_manager.queries()
                if "o_orderkey" in q.sql
            ]
            pager = qs[-1]._pager
            assert pager is not None
            assert pager.pages_produced >= 10
            assert pager.peak_buffered_bytes <= 3 * budget
        finally:
            s.stop()

    def test_streaming_matches_materialized_path(self):
        from trino_tpu.server.http import TrinoTpuServer

        sql = (
            "select o_orderpriority, count(*) c from tpch.tiny.orders "
            "group by o_orderpriority order by o_orderpriority"
        )
        engine = Engine()
        streamed = TrinoTpuServer(
            engine=engine,
            server_config=ServerConfig(result_page_max_bytes=1 << 10),
        ).start()
        try:
            rows_streamed, _ = Connection(streamed.base_uri).execute(sql)
        finally:
            streamed.stop()
        legacy = TrinoTpuServer(
            engine=engine,
            server_config=ServerConfig(result_page_max_bytes=0),
        ).start()
        try:
            rows_legacy, _ = Connection(legacy.base_uri).execute(sql)
        finally:
            legacy.stop()
        assert rows_streamed == rows_legacy


class TestDrainUnderLoad:
    def test_no_admitted_query_dropped(self):
        """Draining under concurrent load: every query the server
        ACCEPTED (assigned a queryId) completes with its rows; late
        arrivals are refused with 503 — never half-served."""
        from trino_tpu.server.http import TrinoTpuServer

        s = TrinoTpuServer(engine=SleepyEngine(0.2)).start()
        accepted: dict[int, list] = {}
        refused: list[int] = []
        errors: list = []
        lock = threading.Lock()

        def run(i):
            conn = Connection(
                s.base_uri, ClientSession(shed_retry_attempts=1)
            )
            try:
                rows, _ = conn.execute(f"select {i}")
                with lock:
                    accepted[i] = rows
            except urllib.error.HTTPError as e:
                with lock:
                    if e.code == 503:
                        refused.append(i)
                    else:
                        errors.append((i, e))
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append((i, e))

        threads = [
            threading.Thread(target=run, args=(i,)) for i in range(8)
        ]
        for t in threads[:4]:
            t.start()
        time.sleep(0.05)
        req = urllib.request.Request(
            f"{s.base_uri}/v1/info/state",
            data=b'"SHUTTING_DOWN"',
            method="PUT",
        )
        with urllib.request.urlopen(req, timeout=5) as r:
            assert r.status == 200
        for t in threads[4:]:
            t.start()
        for t in threads:
            t.join(30)
        assert not errors, f"non-shed failures during drain: {errors}"
        # the first wave was in flight before the drain began: all served
        for i, rows in accepted.items():
            assert rows == [(i,)], f"query {i} returned wrong rows"
        assert len(accepted) + len(refused) == 8
        assert accepted, "expected at least one in-flight query to finish"
