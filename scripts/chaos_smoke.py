"""Chaos smoke: TPC-H under task-crash injection plus a slow worker.

Boots a 2-worker cluster and runs three scenarios:

1. TPC-H Q1 fault-free vs ``fault_task_crash_p=0.3`` +
   ``retry_policy=TASK`` — results must be bit-identical and at least
   one task retry should fire.
2. A skewed partitioned join under the same crash injection.
3. ``slow-worker``: worker-1 deterministically slowed 10× via
   ``fault_slow_workers`` and ``fault_task_slow_factor`` with
   ``speculation=true`` — the straggler detector must hedge at least
   one attempt onto the healthy worker, results stay bit-identical,
   and the speculative counters land in the summary line.
4. ``concurrent-clients``: N threads fire literal-variant aggregations
   with cross-query batching enabled (``batch_window_ms``>0,
   ``execution_mode=distributed`` so the coordinator's own engine — the
   tier that batches — executes them). Every concurrent result must be
   bit-identical to its sequential run; batched-dispatch counters land
   in the summary line.
5. ``node-death`` (the 2-worker cluster's last scenario — a worker does
   not survive it): with ``retry_policy=TASK`` +
   ``exchange_spooling=true`` (execution pinned per-fragment), the
   worker that ran Q1's scan fragment ``os._exit``s right after that
   task finishes (``fault_worker_exit_site=2.0``; every task stalls 1s
   pre-execute so the partial-agg consumers provably pull AFTER the
   death). Spool recovery must keep the result bit-identical with NO
   query-level retry (queryAttempts == 1); spooled-bytes and
   recovered-task counters land in the summary.
6. ``fused-node-death`` (its own 3-worker cluster): fusion AND spooling
   on together. A join of two grouped subqueries fuses into two units
   feeding a worker-side join stage; the worker that ran the first
   unit's task is SIGKILLed right after it finishes. The stalled join
   consumers pull after the death, so recovery must engage at unit
   granularity — FAIL on row drift, on queryAttempts > 1, or on
   fusedFragments == 0 (the query silently not fusing would void the
   scenario); recovered/spooled/fused counters land in the summary.

7. ``star-join`` (its own 3-worker cluster): a TPC-DS star query whose
   broadcast dimension builds fuse INTO the fact-probe program (the
   dense join tier's multiway fusion) runs once clean, then with the
   worker that executed the fused unit's task SIGKILLed right after the
   task finishes. FAIL on row drift, on a query-level retry
   (queryAttempts > 1), on the query not fusing, or on a join site
   running another kernel than the one ``auto`` names
   (exchangeStats.joinStrategy: sort-merge since PR 36) —
   recovery must engage at unit granularity, same ladder as
   fused-node-death but across a multiway join program.

8. ``adaptive-warmup`` (in-process, no cluster): a Zipf-skewed
   partitioned join with skew handling OFF runs cold, recording
   observed truth (capacities) into a persistent query-history store;
   a FRESH engine sharing the same ``history_dir`` then repeats the
   query. FAIL unless the warm run shows ``overflow_retries == 0`` AND
   ``compile_halvings == 0`` AND bit-identical rows AND the same join
   kernel as the cold run (``joinStrategy``: the history seeds
   capacities and promotes no tier since PR 36). When the cold run
   actually grew a site, the warm run must additionally show at least
   one capacity with provenance ``history``.

Quick manual repro for the fault-tolerance stack (CI runs the same
scenarios as ``tests/test_fault_tolerance.py -m faults`` /
``tests/test_speculation.py`` / ``tests/test_spool.py``).

9. ``overload`` (own entry point: ``chaos_smoke.py overload``): an
   in-process coordinator with deliberately tiny admission capacity is
   offered 4× that capacity from closed-loop retrying clients while a
   burst tenant trips the token bucket. FAIL on row drift of any
   ADMITTED query, on a 503 that does not carry Retry-After, or on
   queue depth exceeding the closed-loop bound (unbounded growth means
   abandoned waiters are leaking).

Quick manual repro for the fault-tolerance stack (CI runs the same
scenarios as ``tests/test_fault_tolerance.py -m faults`` /
``tests/test_speculation.py`` / ``tests/test_spool.py``).

10. ``live-append`` (own entry point: ``chaos_smoke.py live-append``):
    reader threads hammer a RESULT-cached aggregation while a writer
    appends a new part to the scanned table mid-storm. Every result a
    reader observes must equal the pre-append snapshot or the
    post-append snapshot — never a torn mix — and the final read must
    show the appended rows (served via incremental maintenance, not a
    cold re-execution; maintained/invalidation counters land in the
    summary line).

11. ``post-mortem`` (rides the fused-node-death cluster): the death
    query journals its lifecycle to an on-disk flight recorder
    (``flight_dir`` → obs/flight.py). After the cluster — coordinator
    included — is torn down, the journal is replayed straight from disk
    and must ALONE explain the recovery: created→completed lifecycle,
    retry attempts and recovered levels matching the live /v1/query
    scrape, final queryStats and operatorStats. FAIL on any missing or
    mismatched piece; the verdict (per-check booleans) lands in the
    summary line under ``post_mortem``.

Usage: JAX_PLATFORMS=cpu python scripts/chaos_smoke.py
       [seed|overload|live-append]
"""

import json
import os
import sys
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from trino_tpu.testing import MultiProcessQueryRunner

Q1 = """select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
              sum(l_extendedprice) as sum_base_price,
              avg(l_discount) as avg_disc, count(*) as count_order
       from lineitem where l_shipdate <= date '1998-09-02'
       group by l_returnflag, l_linestatus
       order by l_returnflag, l_linestatus"""

# skewed partitioned join: least() collapses ~93% of order rows onto one
# join key, so the heavy-hitter path (ops/skew.py + salted exchange) and
# fault injection are exercised together
Q_SKEW = """select count(*) as c, sum(o.o_totalprice * c.c_custkey) as chk
       from orders o join customer c on least(o.o_custkey, 100) = c.c_custkey"""

# literal-variant shape for the concurrent-clients scenario: the four
# threads differ only in the hoisted comparison literal, so their plans
# share one canonical fingerprint and are batchable; ORDER BY pins row
# order (skew handling is off inside a batched dispatch)
Q_BATCH = """select l_returnflag, count(*) as c, sum(l_quantity) as s
       from lineitem where l_quantity < {} group by l_returnflag
       order by l_returnflag"""

# fused-node-death: two grouped subqueries fuse into two pipeline units
# feeding a worker-side join stage (PARTITIONED + fusion_max_fragments=2).
# The join's tasks are stallable, so killing a unit's worker right after
# the unit task finishes is provably observed — recovery must engage at
# unit granularity (spool re-point of the unit's boundary output, or an
# atomic whole-unit re-execution)
Q_FUSED = """select a.k, a.c, b.s from
       (select l_returnflag as k, count(*) as c from lineitem
        group by l_returnflag) a
       join (select l_returnflag as k, sum(l_quantity) as s from lineitem
        group by l_returnflag) b on a.k = b.k order by a.k"""

FUSED_PROPS = {
    "join_distribution_type": "PARTITIONED",
    "fusion_max_fragments": 2,
}

# star-join: fact probes against two broadcast dimension builds — with
# the dense join tier on (default) the dims are absorbed into ONE
# multiway fused program (planner/fragmenter.py broadcast_links), the
# shape the worker-SIGKILL scenario must recover at unit granularity
Q_STAR = """select i.i_category, d.d_year, sum(ss.ss_ext_sales_price) as s
       from tpcds.tiny.store_sales ss
       join tpcds.tiny.item i on ss.ss_item_sk = i.i_item_sk
       join tpcds.tiny.date_dim d on ss.ss_sold_date_sk = d.d_date_sk
       group by i.i_category, d.d_year
       order by i.i_category, d.d_year"""


def _fused_unit_site(sql, **props):
    """Fault site of the first fused unit's task ('{unit_root}.0'),
    computed from the same fuse_groups decision the scheduler makes."""
    from trino_tpu.exec.fragments import fragment_fusable
    from trino_tpu.planner.fragmenter import (
        FusedFragment,
        filtered_broadcast_fids,
        fragment_plan,
        fuse_groups,
        partitioned_join_pairs,
    )
    from trino_tpu.testing import LocalQueryRunner

    r = LocalQueryRunner()
    r.session.set("execution_mode", "distributed")
    for k, v in props.items():
        r.session.set(k, v)
    sub = fragment_plan(r.plan(sql))
    units = [
        u
        for u in fuse_groups(
            sub,
            fusable=fragment_fusable,
            max_fragments=max(1, int(r.session.get("fusion_max_fragments"))),
            skew_pairs=(
                partitioned_join_pairs(sub)
                if bool(r.session.get("skew_handling"))
                else ()
            ),
            include_root=False,
            broadcast_links=bool(r.session.get("dense_join")),
            blocked=(
                frozenset(filtered_broadcast_fids(sub))
                if bool(r.session.get("enable_dynamic_filtering"))
                else frozenset()
            ),
        )
        if isinstance(u, FusedFragment)
    ]
    if not units:
        return None
    return f"{units[0].id}.0"


def _operator_rollup(query_infos) -> dict:
    """Operator row-flow rollup across scraped /v1/query records: total
    rows in/out per operator kind plus the worst (largest out/in)
    partial-agg reduction ratio — the mid-query-adaptivity signal."""
    out: dict = {}
    worst = None
    for q in query_infos:
        for ent in (q.get("operatorStats") or {}).values():
            kind = str(ent.get("kind") or "")
            if not kind:
                continue
            key = kind.replace("-", "_")
            rin = int(ent.get("rows_in", 0) or 0)
            rout = int(ent.get("rows_out", 0) or 0)
            out[f"{key}_rows_in"] = out.get(f"{key}_rows_in", 0) + rin
            out[f"{key}_rows_out"] = out.get(f"{key}_rows_out", 0) + rout
            if kind == "partial-agg" and rin > 0:
                ratio = rout / rin
                worst = ratio if worst is None else max(worst, ratio)
    if worst is not None:
        out["worst_partial_agg_reduction"] = round(worst, 4)
    return out


def _post_mortem_verdict(events: list, live_info: dict) -> dict:
    """Judge whether the flight journal ALONE explains the fused-node-
    death recovery: it must carry the lifecycle (created→completed), the
    retry/recovery accounting matching the live /v1/query scrape, and
    the final stats — a coordinator that died right after this query
    would leave an operator with exactly these bytes."""
    names = [e.get("event") for e in events]
    completed = next(
        (e for e in reversed(events) if e.get("event") == "completed"), {}
    )
    qs = completed.get("queryStats") or {}
    checks = {
        "has_created": "created" in names,
        "has_completed": bool(completed),
        "finished": completed.get("state") == "FINISHED",
        "has_final_stats": bool(qs) and "elapsedMs" in qs,
        "has_operator_stats": bool(completed.get("operatorStats")),
        "attempts_match": (
            completed.get("queryAttempts") == live_info.get("queryAttempts")
        ),
        "recovery_match": (
            int(completed.get("recoveredTasks") or 0)
            == int(live_info.get("recoveredTasks") or 0)
            and (completed.get("recoveredTaskLevels") or {})
            == (live_info.get("recoveredTaskLevels") or {})
        ),
    }
    return {
        "events": names,
        "explains_recovery": all(checks.values()),
        "checks": checks,
        "query_attempts": completed.get("queryAttempts"),
        "recovered_tasks": completed.get("recoveredTasks"),
        "recovered_levels": completed.get("recoveredTaskLevels"),
        "state": completed.get("state"),
    }


def _adaptive_warmup(seed: int) -> dict:
    """Cold overflowing skewed join, then the same query on a FRESH
    engine sharing the persistent history store. The warm engine has no
    in-process program cache or stats for the query — everything it
    knows arrives through ``{history_dir}/query_history.json`` — so a
    clean warm run proves the record → seed feedback loop end to end."""
    import tempfile

    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.config import Session
    from trino_tpu.connectors.api import ColumnSchema, TableSchema
    from trino_tpu.testing import LocalQueryRunner

    n = 1 << 16
    sql = ("select sum(f.v * d.name) as chk, count(*) as c "
           "from memory.default.facts f "
           "join memory.default.dims d on f.k = d.k")

    def _seed(catalogs):
        mem = catalogs.get("memory")
        rng = np.random.default_rng(seed)
        raw = rng.zipf(1.2, size=6 * n)
        keys = raw[raw <= 8][:n].astype(np.int64)  # ~43% on one key
        vals = rng.integers(0, 1000, n).astype(np.int64)
        mem.create_table(
            "default", "facts",
            TableSchema("facts", (ColumnSchema("k", T.BIGINT),
                                  ColumnSchema("v", T.BIGINT))))
        mem.insert("default", "facts",
                   Batch([Column(T.BIGINT, keys), Column(T.BIGINT, vals)], n))
        dk = np.arange(1, 9, dtype=np.int64)
        mem.create_table(
            "default", "dims",
            TableSchema("dims", (ColumnSchema("k", T.BIGINT),
                                 ColumnSchema("name", T.BIGINT))))
        mem.insert("default", "dims",
                   Batch([Column(T.BIGINT, dk), Column(T.BIGINT, dk * 100)],
                         8))

    with tempfile.TemporaryDirectory() as hdir:
        props = {
            "execution_mode": "distributed",
            "join_distribution_type": "PARTITIONED",
            "skew_handling": False,  # force the cold overflow
            "history_dir": hdir,
        }

        def _run(runner):
            return runner.engine.execute_statement(
                sql, Session(properties=props)
            )

        cold_runner = LocalQueryRunner()
        _seed(cold_runner.catalogs)
        cold = _run(cold_runner)
        # FRESH engine: no shared program cache, no in-process stats —
        # only the on-disk history store carries the observed truth over
        warm_runner = LocalQueryRunner()
        _seed(warm_runner.catalogs)
        warm = _run(warm_runner)

    wex = warm.exchange_stats or {}
    cex = cold.exchange_stats or {}
    provs = sorted({
        str(site.get("provenance", "")).split("+")[0]
        for site in (wex.get("capacities") or {}).values()
    })
    return {
        "cold_retries": cex.get("overflow_retries", 0),
        "cold_halvings": cex.get("compile_halvings", 0),
        "cold_strategies": sorted(
            set((cex.get("joinStrategy") or {}).values())),
        "warm_retries": wex.get("overflow_retries", 0),
        "warm_halvings": wex.get("compile_halvings", 0),
        "warm_strategies": sorted(
            set((wex.get("joinStrategy") or {}).values())),
        "warm_provenance": provs,
        "history_seeds": wex.get("history_seeds", 0),
        "drift": warm.rows != cold.rows,
    }


def overload() -> int:
    """4× admission-capacity overload against the event-loop front door.

    Capacity is 2 concurrent queries (hard_concurrency_limit=2); 8
    closed-loop clients keep 4× that admitted-or-waiting at all times,
    while per-tenant token buckets shed their statement bursts with
    503 + Retry-After and the clients' jittered backoff retries carry
    them through. Invariants: admitted queries stay bit-identical to
    their sequential runs, every shed carries Retry-After, and queue
    depth never exceeds the closed-loop bound of one outstanding query
    per client."""
    import threading
    import time
    import urllib.error

    from trino_tpu.client import ClientSession, Connection
    from trino_tpu.config import ServerConfig
    from trino_tpu.engine import Engine
    from trino_tpu.server.http import TrinoTpuServer
    from trino_tpu.server.resourcegroups import (
        GroupConfig,
        ResourceGroupManager,
        Selector,
    )

    clients = 8
    capacity = 2  # offered load is 4x this
    summary: dict = {"scenario": "overload", "partial": True}
    try:
        rgm = ResourceGroupManager(max_wait_seconds=30.0)
        rgm.configure(
            [
                GroupConfig(
                    "root",
                    max_queued=100,
                    hard_concurrency_limit=capacity,
                )
            ],
            [Selector(group="root")],
        )
        engine = Engine()
        server = TrinoTpuServer(
            engine=engine,
            resource_groups=rgm,
            server_config=ServerConfig(
                tenant_rate_limit_qps=20.0,
                tenant_rate_limit_burst=4.0,
                max_inflight_requests=64,
            ),
        ).start()
        sql = (
            "select l_returnflag, sum(l_quantity), count(*)"
            " from tpch.tiny.lineitem where l_quantity < {}"
            " group by l_returnflag order by l_returnflag"
        )
        lits = [10 + 2 * (i % 8) for i in range(clients * 4)]
        from trino_tpu.config import Session

        seq_rows = {
            lit: engine.execute_statement(sql.format(lit), Session()).rows
            for lit in sorted(set(lits))
        }

        # queue-depth monitor: closed-loop clients have at most one
        # statement outstanding each and the burst tenant fires at most
        # burst_posts fire-and-forget statements, so queuedQueries above
        # clients + burst_posts means waiters are leaking (the
        # "unbounded growth" failure mode)
        burst_posts = 8
        peak_queued = [0]
        stop = threading.Event()

        def monitor() -> None:
            while not stop.is_set():
                info = rgm.info()[0]
                peak_queued[0] = max(peak_queued[0], info["queuedQueries"])
                stop.wait(0.02)

        mon = threading.Thread(target=monitor, daemon=True)
        mon.start()

        drift = [0]
        completed = [0]
        errors: list = []
        lock = threading.Lock()

        def client(c: int) -> None:
            conn = Connection(
                server.base_uri,
                ClientSession(user=f"tenant-{c % 4}", shed_retry_attempts=8),
            )
            for r in range(4):
                lit = lits[(r * clients + c) % len(lits)]
                try:
                    rows, _ = conn.execute(sql.format(lit))
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(f"client {c}: {e!r}")
                    continue
                with lock:
                    completed[0] += 1
                    if [list(t) for t in rows] != [
                        list(t) for t in seq_rows[lit]
                    ]:
                        drift[0] += 1

        ts = [
            threading.Thread(target=client, args=(c,)) for c in range(clients)
        ]
        t0 = time.time()
        for t in ts:
            t.start()

        # while the fleet saturates admission, trip the token bucket
        # directly and verify the shed contract: 503 AND Retry-After
        sheds_seen = 0
        bad_sheds = 0
        for _ in range(burst_posts):
            req = urllib.request.Request(
                f"{server.base_uri}/v1/statement",
                data=b"select 1",
                method="POST",
                headers={"X-Trino-User": "burster"},
            )
            try:
                urllib.request.urlopen(req, timeout=10).read()
            except urllib.error.HTTPError as e:
                if e.code == 503:
                    sheds_seen += 1
                    if e.headers.get("Retry-After") is None:
                        bad_sheds += 1
                e.read()

        for t in ts:
            t.join(120)
        stop.set()
        mon.join(2)
        wall = time.time() - t0

        snap = {}
        with urllib.request.urlopen(
            f"{server.base_uri}/v1/metrics?format=json", timeout=10
        ) as r:
            snap = json.loads(r.read().decode())
        shed_counters = {
            k: v
            for k, v in snap.get("counters", {}).items()
            if k.startswith("trino_tpu_requests_shed_total")
        }
        server.stop()

        summary.update(
            {
                "clients": clients,
                "capacity": capacity,
                "completed": completed[0],
                "row_drift": drift[0],
                "errors": errors[:5],
                "peak_queued": peak_queued[0],
                "burst_sheds": sheds_seen,
                "sheds_without_retry_after": bad_sheds,
                "shed_counters": shed_counters,
                "wall_s": round(wall, 2),
                "partial": False,
            }
        )
        if errors:
            print(f"FAIL: overload clients errored: {errors[:3]}")
            summary["ok"] = False
            return 1
        if drift[0]:
            print(f"FAIL: {drift[0]} admitted queries drifted under overload")
            summary["ok"] = False
            return 1
        if completed[0] != clients * 4:
            print(
                f"FAIL: only {completed[0]}/{clients * 4} queries completed"
            )
            summary["ok"] = False
            return 1
        if peak_queued[0] > clients + burst_posts:
            print(
                f"FAIL: queue grew to {peak_queued[0]} with only {clients}"
                f" closed-loop clients + {burst_posts} burst posts —"
                " waiters are leaking"
            )
            summary["ok"] = False
            return 1
        if sheds_seen == 0:
            print("FAIL: burst tenant was never shed — overload never bit")
            summary["ok"] = False
            return 1
        if bad_sheds:
            print(f"FAIL: {bad_sheds} 503s arrived without Retry-After")
            summary["ok"] = False
            return 1
        print(
            "OK: bit-identical under 4x admission overload"
            f" ({completed[0]} queries, {sheds_seen} sheds all carrying"
            " Retry-After, bounded queue)"
        )
        summary["ok"] = True
        return 0
    finally:
        print(json.dumps(summary), flush=True)


def live_append() -> int:
    """Result-cache consistency under a live append: reader threads
    hammer a cached aggregation while a writer appends a part mid-storm.

    Invariants: every observed result equals the pre-append snapshot OR
    the post-append snapshot (atomic entry replacement — never a torn
    mix of old cached rows and new delta rows), and the final read shows
    the appended data. The post-append serve should arrive via
    incremental maintenance (delta splits only); a maintained count of
    zero only WARNs, because the writer can race the version re-check
    and legitimately force an invalidation instead."""
    import threading
    import time

    import numpy as np

    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.config import Session
    from trino_tpu.connectors.api import ColumnSchema, TableSchema
    from trino_tpu.testing import LocalQueryRunner

    readers, iters = 4, 12
    sql = ("select k, sum(v) as s, count(*) as c "
           "from memory.default.live group by k")
    schema = TableSchema("live", (ColumnSchema("k", T.BIGINT),
                                  ColumnSchema("v", T.BIGINT)))
    props = {"execution_mode": "distributed", "result_cache": True,
             "incremental_maintenance": True}
    summary: dict = {"scenario": "live-append", "partial": True}
    try:
        def _batch(n: int, seed: int) -> Batch:
            rng = np.random.default_rng(seed)
            k = rng.integers(0, 9, n).astype(np.int64)
            v = rng.integers(0, 101, n).astype(np.int64)
            return Batch([Column(T.BIGINT, k), Column(T.BIGINT, v)], n)

        part_a, part_b = _batch(4096, 1), _batch(512, 2)

        # ground truth for both table states, from scratch engines with
        # the result cache OFF — the storm's observations must match one
        # of these two snapshots exactly
        def _snap(parts) -> list:
            r = LocalQueryRunner()
            mem = r.catalogs.get("memory")
            mem.create_table("default", "live", schema)
            for p in parts:
                mem.insert("default", "live", p)
            res = r.engine.execute_statement(
                sql, Session(properties={"execution_mode": "distributed"})
            )
            return sorted(map(tuple, res.rows))

        snap_a = _snap([part_a])
        snap_b = _snap([part_a, part_b])

        runner = LocalQueryRunner()
        mem = runner.catalogs.get("memory")
        mem.create_table("default", "live", schema)
        mem.insert("default", "live", part_a)
        runner.engine.execute_statement(sql, Session(properties=props))

        barrier = threading.Barrier(readers + 1)
        lock = threading.Lock()
        torn: list = []
        errors: list = []

        def _reader() -> None:
            barrier.wait()
            for _ in range(iters):
                try:
                    res = runner.engine.execute_statement(
                        sql, Session(properties=props)
                    )
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errors.append(repr(e))
                    return
                got = sorted(map(tuple, res.rows))
                if got != snap_a and got != snap_b:
                    with lock:
                        torn.append(got[:3])

        def _writer() -> None:
            barrier.wait()
            time.sleep(0.05)  # let the storm get going first
            mem.insert("default", "live", part_b)

        threads = [threading.Thread(target=_reader) for _ in range(readers)]
        threads.append(threading.Thread(target=_writer))
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)

        final = sorted(map(tuple, runner.engine.execute_statement(
            sql, Session(properties=props)
        ).rows))
        snap = runner.engine.result_cache.snapshot()
        summary.update(
            readers=readers,
            iters=iters,
            torn=len(torn),
            errors=errors[:3],
            hits=snap["hits"],
            maintained=snap["maintained"],
            invalidations=snap["invalidations"],
            partial=False,
        )
        if errors:
            print(f"FAIL: live-append readers errored: {errors[:3]}")
            summary["ok"] = False
            return 1
        if torn:
            print(f"FAIL: {len(torn)} reads saw a torn result (neither the"
                  " pre- nor the post-append snapshot)")
            summary["ok"] = False
            return 1
        if final != snap_b:
            print("FAIL: final read does not show the appended part")
            summary["ok"] = False
            return 1
        if snap["maintained"] == 0:
            print("WARN: append was absorbed by invalidation, not"
                  " incremental maintenance — the writer raced the"
                  " version re-check")
        print(
            "OK: live append stayed atomic under a"
            f" {readers}-reader storm ({snap['hits']} cache hits,"
            f" {snap['maintained']} maintained serves)"
        )
        summary["ok"] = True
        return 0
    finally:
        print(json.dumps(summary), flush=True)


def main() -> int:
    # default seed 3: both partitions of Q1's scan fragment draw below
    # 0.3 on attempt 1 and survive on attempt 2 — guaranteed retries
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    chaos = {
        "retry_policy": "TASK",
        "task_retry_attempts": 8,
        "fault_injection_seed": seed,
        "fault_task_crash_p": 0.3,
        "retry_initial_delay_ms": 20,
        "retry_max_delay_ms": 200,
    }
    skew_props = {"join_distribution_type": "PARTITIONED"}
    # slow-worker scenario: worker-1 runs every task 10× slower (sleep
    # after compute, before emit — so a speculative cancel can still
    # abort delivery); speculation hedges onto the healthy worker-0
    slow_props = {
        # hedging needs sibling tasks fanned out across workers: a fused
        # pipeline unit is a single task and can never be speculated
        "pipeline_fusion": False,
        "retry_policy": "TASK",
        "fault_injection_seed": seed,
        "fault_slow_workers": "worker-1",
        "fault_task_slow_factor": 10.0,
        "speculation": True,
        "speculation_floor_ms": 100,
        "speculation_multiplier": 2.0,
        "speculation_max_fraction": 1.0,
    }
    # node-death: the worker hosting Q1's scan task (fragment 2,
    # partition 0 — Q1 fragments as root 0 <- partial agg 1 <- scan 2)
    # kills itself 300ms after that task finishes; the 1s pre-execute
    # stall on every task guarantees the fragment-1 consumers pull after
    # the death, so spooled output / lineage recovery must absorb it
    death_props = {
        "retry_policy": "TASK",
        "exchange_spooling": True,
        # pin per-fragment execution: the 2.0 exit site addresses the
        # per-fragment task tree (under the fused default Q1's scan is
        # interior to a unit and the site would never fire); the
        # fused-node-death scenario below covers the fused ladder
        "worker_execution": "per_fragment",
        "task_retry_attempts": 8,
        "retry_initial_delay_ms": 20,
        "retry_max_delay_ms": 200,
        "fault_worker_exit_site": "2.0",
        "fault_worker_exit_delay_ms": 300,
        "fault_task_stall_ms": 1000,
    }
    # the summary dict is built incrementally and emitted in a finally, so
    # a crash mid-scenario still prints one machine-readable JSON line with
    # whatever was gathered (partial: true)
    summary: dict = {"seed": seed, "partial": True}
    try:
        with MultiProcessQueryRunner(n_workers=2) as runner:
            clean, _ = runner.execute(Q1)
            chaotic, _ = runner.execute(Q1, session_properties=chaos)
            skew_clean, _ = runner.execute(
                Q_SKEW, session_properties=skew_props
            )
            skew_chaotic, _ = runner.execute(
                Q_SKEW, session_properties={**chaos, **skew_props}
            )
            slow_spec, _ = runner.execute(Q1, session_properties=slow_props)
            # concurrent-clients: sequential ground truth first, then N
            # threads with batching on; coordinator-local execution
            # (execution_mode=distributed) is where the collector lives
            import threading

            batch_lits = (10, 20, 30, 40)
            batch_props = {
                "execution_mode": "distributed",
                "batch_window_ms": 300,
                "batch_max_size": len(batch_lits),
            }
            seq_batch = {
                lit: runner.execute(
                    Q_BATCH.format(lit),
                    session_properties={"execution_mode": "distributed"},
                )[0]
                for lit in batch_lits
            }
            conc_rows: dict = {}
            conc_errs: dict = {}

            def _client(lit: int) -> None:
                try:
                    conc_rows[lit] = runner.execute(
                        Q_BATCH.format(lit), session_properties=batch_props
                    )[0]
                except Exception as e:  # noqa: BLE001
                    conc_errs[lit] = str(e)

            cthreads = [
                threading.Thread(target=_client, args=(lit,))
                for lit in batch_lits
            ]
            for t in cthreads:
                t.start()
            for t in cthreads:
                t.join()
            # LAST scenario: one worker dies mid-query and stays dead
            death, _ = runner.execute(Q1, session_properties=death_props)
            from trino_tpu.server import auth

            req = urllib.request.Request(
                f"{runner.coordinator_uri}/v1/query", headers=auth.headers()
            )
            with urllib.request.urlopen(req, timeout=10) as r:
                queries = json.loads(r.read().decode())
            # coordinator metrics snapshot (task retries/attempt histograms)
            # must be scraped before the cluster shuts down
            with urllib.request.urlopen(
                f"{runner.coordinator_uri}/v1/metrics?format=json", timeout=10
            ) as r:
                summary["metrics"] = json.loads(r.read().decode())
        # fused-node-death gets its OWN 3-worker cluster: the previous
        # cluster is down a worker for good, and the fused ladder should
        # be measured against a full quorum
        from trino_tpu.server import auth

        fused_site = _fused_unit_site(Q_FUSED, **FUSED_PROPS)
        # post-mortem scenario: the death query journals its lifecycle to
        # an on-disk flight recorder (obs/flight.py); after the cluster is
        # torn down the journal ALONE must explain the recovery
        import tempfile

        flight_tmp = tempfile.mkdtemp(prefix="chaos-flight-")
        fused_death_props = {
            **FUSED_PROPS,
            "retry_policy": "TASK",
            "exchange_spooling": True,
            "task_retry_attempts": 8,
            "retry_initial_delay_ms": 20,
            "retry_max_delay_ms": 200,
            "fault_worker_exit_site": fused_site or "2.0",
            "fault_worker_exit_delay_ms": 300,
            "fault_task_stall_ms": 1000,
            "flight_dir": flight_tmp,
        }
        with MultiProcessQueryRunner(n_workers=3) as runner3:
            fused_clean, _ = runner3.execute(
                Q_FUSED, session_properties=FUSED_PROPS
            )
            fused_death, _ = runner3.execute(
                Q_FUSED, session_properties=fused_death_props
            )
            req = urllib.request.Request(
                f"{runner3.coordinator_uri}/v1/query", headers=auth.headers()
            )
            with urllib.request.urlopen(req, timeout=10) as r:
                fused_queries = json.loads(r.read().decode())
        fused_info = next(
            (
                q
                for q in reversed(fused_queries)
                if q.get("retryPolicy") == "TASK"
            ),
            {},
        )
        # post-mortem: the 3-worker cluster (coordinator included) is
        # gone; read the journal straight off disk and judge it
        from trino_tpu.obs.flight import replay_dir

        pm_events = replay_dir(flight_tmp)
        summary["post_mortem"] = _post_mortem_verdict(pm_events, fused_info)
        summary["fused_node_death"] = {
            "unit_site": fused_site,
            "fused_fragments": (fused_info.get("exchangeStats") or {}).get(
                "fusedFragments", 0
            ),
            "recovered_tasks": fused_info.get("recoveredTasks", 0),
            "recovered_levels": fused_info.get("recoveredTaskLevels", {}),
            "spooled_bytes": fused_info.get("spooledBytes", 0),
            "query_attempts": fused_info.get("queryAttempts", 1),
            "drift": fused_death != fused_clean,
        }
        # star-join gets its OWN 3-worker cluster too: the SIGKILLed
        # worker stays dead, and the multiway ladder deserves a full
        # quorum rather than the fused-node-death cluster's survivors
        star_site = _fused_unit_site(Q_STAR)  # dense_join defaults on
        star_death_props = {
            "retry_policy": "TASK",
            "exchange_spooling": True,
            "task_retry_attempts": 8,
            "retry_initial_delay_ms": 20,
            "retry_max_delay_ms": 200,
            "fault_worker_exit_site": star_site or "2.0",
            "fault_worker_exit_delay_ms": 300,
            "fault_task_stall_ms": 1000,
        }
        with MultiProcessQueryRunner(n_workers=3) as runner4:
            star_clean, _ = runner4.execute(Q_STAR)
            star_death, _ = runner4.execute(
                Q_STAR, session_properties=star_death_props
            )
            req = urllib.request.Request(
                f"{runner4.coordinator_uri}/v1/query", headers=auth.headers()
            )
            with urllib.request.urlopen(req, timeout=10) as r:
                star_queries = json.loads(r.read().decode())
        star_info = next(
            (
                q
                for q in reversed(star_queries)
                if q.get("retryPolicy") == "TASK"
            ),
            {},
        )
        sex = star_info.get("exchangeStats") or {}
        summary["star_join"] = {
            "unit_site": star_site,
            "fused_fragments": sex.get("fusedFragments", 0),
            "join_strategies": sorted(
                set((sex.get("joinStrategy") or {}).values())
            ),
            "recovered_tasks": star_info.get("recoveredTasks", 0),
            "recovered_levels": star_info.get("recoveredTaskLevels", {}),
            "spooled_bytes": star_info.get("spooledBytes", 0),
            "query_attempts": star_info.get("queryAttempts", 1),
            "drift": star_death != star_clean,
        }
        # adaptive-warmup runs in-process (fresh engines + a shared
        # persistent history store), after the clusters are down
        summary["adaptive_warmup"] = _adaptive_warmup(seed)
        retries = max(q.get("taskRetries", 0) for q in queries)
        spec_attempts = max(q.get("speculativeAttempts", 0) for q in queries)
        spec_wins = max(q.get("speculativeWins", 0) for q in queries)
        death_info = max(
            (q for q in queries if q.get("spooledBytes", 0) > 0
             or q.get("recoveredTasks", 0) > 0),
            key=lambda q: q.get("recoveredTasks", 0),
            default={},
        )
        recovered = death_info.get("recoveredTasks", 0)
        spooled = death_info.get("spooledBytes", 0)
        death_attempts = death_info.get("queryAttempts", 1)
        # device-profiler rollup across every scraped query record:
        # FLOPs sum / peak HBM max as merged by the coordinator from
        # worker task stats (all-zero on backends with no cost model)
        device = {"programs_profiled": 0, "total_flops": 0.0,
                  "peak_hbm_bytes": 0}
        for q in queries:
            ds = q.get("deviceStats") or {}
            device["programs_profiled"] += int(
                ds.get("programs_profiled") or 0
            )
            device["total_flops"] += float(ds.get("total_flops") or 0.0)
            device["peak_hbm_bytes"] = max(
                device["peak_hbm_bytes"], int(ds.get("peak_hbm_bytes") or 0)
            )
        summary["device"] = device
        # operator row-flow rollup (exec/fragments.py op! channel) across
        # every scraped query record, incl. the worst partial-agg
        # reduction ratio
        summary["operators"] = _operator_rollup(
            list(queries) + [fused_info, star_info]
        )
        # cross-query batching counters (size-labelled dispatch family)
        batched_counters = {
            k: v
            for k, v in summary.get("metrics", {})
            .get("counters", {})
            .items()
            if k.startswith("trino_tpu_batched_dispatches_total")
        }
        summary["batched_dispatches"] = batched_counters
        summary["concurrent_clients"] = len(batch_lits)
        summary.update(
            seed=seed,
            rows=len(chaotic),
            task_retries=retries,
            speculative_attempts=spec_attempts,
            speculative_wins=spec_wins,
            recovered_tasks=recovered,
            recovered_levels=death_info.get("recoveredTaskLevels", {}),
            spooled_bytes=spooled,
            node_death_query_attempts=death_attempts,
            partial=False,
        )
        print(
            f"seed={seed} rows={len(chaotic)} task_retries={retries}"
            f" speculative_attempts={spec_attempts}"
            f" speculative_wins={spec_wins}"
            f" recovered_tasks={recovered} spooled_bytes={spooled}"
        )
        if chaotic != clean:
            print("FAIL: chaotic result differs from fault-free result")
            summary["ok"] = False
            return 1
        if skew_chaotic != skew_clean:
            print("FAIL: skewed-join chaotic result differs from fault-free")
            summary["ok"] = False
            return 1
        if slow_spec != clean:
            print("FAIL: slow-worker speculative result differs from fault-free")
            summary["ok"] = False
            return 1
        if conc_errs:
            print(f"FAIL: concurrent-clients errors: {conc_errs}")
            summary["ok"] = False
            return 1
        for lit in batch_lits:
            if sorted(conc_rows[lit]) != sorted(seq_batch[lit]):
                print(
                    "FAIL: concurrent-clients row drift at literal"
                    f" {lit} (batched vs sequential)"
                )
                summary["ok"] = False
                return 1
        if not batched_counters:
            print("WARN: no batched dispatches — the window never"
                  " collected concurrent arrivals")
        if death != clean:
            print("FAIL: node-death result differs from fault-free")
            summary["ok"] = False
            return 1
        if death_attempts > 1:
            print(
                "FAIL: node-death escalated to a query-level retry"
                f" (queryAttempts={death_attempts})"
            )
            summary["ok"] = False
            return 1
        fd = summary["fused_node_death"]
        if fd["drift"]:
            print("FAIL: fused-node-death result differs from fault-free")
            summary["ok"] = False
            return 1
        if fd["query_attempts"] > 1:
            print(
                "FAIL: fused-node-death escalated to a query-level retry"
                f" (queryAttempts={fd['query_attempts']})"
            )
            summary["ok"] = False
            return 1
        if fd["fused_fragments"] == 0:
            print("FAIL: fused-node-death query never fused — the scenario"
                  " silently exercised the per-fragment path")
            summary["ok"] = False
            return 1
        if fd["recovered_tasks"] == 0:
            print("WARN: fused-node-death recovered nothing — the unit"
                  " death raced the consumer pull")
        pm = summary["post_mortem"]
        if not pm["explains_recovery"]:
            bad = [k for k, v in pm["checks"].items() if not v]
            print(
                "FAIL: post-mortem — flight journal alone does not explain"
                f" the fused-node-death recovery (failed checks: {bad})"
            )
            summary["ok"] = False
            return 1
        sj = summary["star_join"]
        if sj["drift"]:
            print("FAIL: star-join result differs from fault-free")
            summary["ok"] = False
            return 1
        if sj["query_attempts"] > 1:
            print(
                "FAIL: star-join escalated to a query-level retry"
                f" (queryAttempts={sj['query_attempts']})"
            )
            summary["ok"] = False
            return 1
        if sj["fused_fragments"] == 0:
            print("FAIL: star-join query never fused — the multiway"
                  " broadcast absorption silently did not happen")
            summary["ok"] = False
            return 1
        if sj["join_strategies"] != ["sort"]:
            print(
                "FAIL: star-join left the kernel auto names"
                f" (joinStrategy={sj['join_strategies']}, want sort)"
            )
            summary["ok"] = False
            return 1
        if sj["recovered_tasks"] == 0:
            print("WARN: star-join recovered nothing — the unit death"
                  " raced the consumer pull")
        aw = summary["adaptive_warmup"]
        if aw["drift"]:
            print("FAIL: adaptive-warmup warm result differs from cold")
            summary["ok"] = False
            return 1
        if aw["warm_retries"] != 0 or aw["warm_halvings"] != 0:
            print(
                "FAIL: adaptive-warmup warm run still corrected itself"
                f" (overflow_retries={aw['warm_retries']},"
                f" compile_halvings={aw['warm_halvings']}) — history"
                " seeding did not carry the observed capacities over"
            )
            summary["ok"] = False
            return 1
        learned = aw["cold_retries"] > 0 or aw["cold_halvings"] > 0
        if learned and "history" not in aw["warm_provenance"]:
            print(
                "FAIL: adaptive-warmup cold run grew a capacity but the"
                " warm run has no history-seeded site"
                f" (provenance={aw['warm_provenance']})"
            )
            summary["ok"] = False
            return 1
        if aw["warm_strategies"] != aw["cold_strategies"]:
            print(
                "FAIL: adaptive-warmup warm run joined by another kernel"
                f" than the cold one ({aw['cold_strategies']} -> warm"
                f" {aw['warm_strategies']}) — history seeds capacities,"
                " it promotes no tier"
            )
            summary["ok"] = False
            return 1
        if aw["cold_retries"] == 0:
            print("WARN: adaptive-warmup cold run never overflowed — the"
                  " warm zero-retry check proves nothing at this size")
        if recovered == 0:
            print("WARN: no recovered tasks — the worker-exit fault"
                  " never bit a consumer")
        if retries == 0:
            print("WARN: no retries at this seed — injection never fired")
        if spec_attempts == 0:
            print("WARN: no speculative attempts — straggler never flagged")
        print(
            "OK: bit-identical under 30% task-crash injection"
            " (incl. skewed join, 10x slow worker, concurrent batched"
            " clients, node death, fused node death, multiway star join,"
            " adaptive warmup)"
        )
        summary["ok"] = True
        return 0
    finally:
        print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "overload":
        sys.exit(overload())
    if len(sys.argv) > 1 and sys.argv[1] == "live-append":
        sys.exit(live_append())
    sys.exit(main())
