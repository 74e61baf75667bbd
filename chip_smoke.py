"""Start the served SQL path on the chip and check what it answers.

One process, no children. Boots ``TrinoTpuServer`` in-process, talks to it
only through ``trino_tpu.client.Connection`` (POST /v1/statement ->
nextUri), runs TPC-H Q6, Q1 and Q3 at scale factor 1 (cold, then warm)
under the default session and under ``execution_mode=distributed``, and
compares every row with the TPC-H SF1 answers kept below as literals. Each
query of the compiled session then runs with other literals, and the two
sessions have to agree on that answer too.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # the 4-device mesh phase only

Nothing is caught: a failed query, a mismatch or a failed assertion ends
the run with a traceback and a non-zero exit. Needs a TPU; there is no CPU
mode. ``smoke_seconds`` is a wall-clock label for the reader, not a metric.
"""

import argparse
import json
import sys
import time
import urllib.request
from datetime import date
from decimal import Decimal as D

import jax

import trino_tpu
from trino_tpu import native
from trino_tpu.benchmarks.tpch import queries
from trino_tpu.client import ClientSession, Connection
from trino_tpu.server.http import TrinoTpuServer

# TPC-H SF1 qualification answers (the specification's answer set prints
# the money columns rounded to two places; these are the exact sums the
# same rows give). No code in this repo computed them.
EXPECTED = {
    6: [(D("123141078.2283"),)],
    1: [
        ("A", "F", D("37734107.00"), D("56586554400.73"),
         D("53758257134.8700"), D("55909065222.827692"),
         D("25.52"), D("38273.13"), D("0.05"), 1478493),
        ("N", "F", D("991417.00"), D("1487504710.38"),
         D("1413082168.0541"), D("1469649223.194375"),
         D("25.52"), D("38284.47"), D("0.05"), 38854),
        ("N", "O", D("74476040.00"), D("111701729697.74"),
         D("106118230307.6056"), D("110367043872.497010"),
         D("25.50"), D("38249.12"), D("0.05"), 2920374),
        ("R", "F", D("37719753.00"), D("56568041380.90"),
         D("53741292684.6040"), D("55889619119.831932"),
         D("25.51"), D("38250.85"), D("0.05"), 1478870),
    ],
    3: [
        (2456423, D("406181.0111"), date(1995, 3, 5), 0),
        (3459808, D("405838.6989"), date(1995, 3, 4), 0),
        (492164, D("390324.0610"), date(1995, 2, 19), 0),
        (1188320, D("384537.9359"), date(1995, 3, 9), 0),
        (2435712, D("378673.0558"), date(1995, 2, 26), 0),
        (4878020, D("378376.7952"), date(1995, 3, 12), 0),
        (5521732, D("375153.9215"), date(1995, 3, 13), 0),
        (2628192, D("373133.3094"), date(1995, 2, 22), 0),
        (993600, D("371407.4595"), date(1995, 3, 5), 0),
        (2300070, D("367371.1452"), date(1995, 3, 13), 0),
    ],
}

DISTRIBUTED = {"execution_mode": "distributed"}
# What each session runs on one chip; the compiled tier goes first. Q3 is
# not in the distributed list: all six pairs passed on the chip (PR 24) but
# took 1,435 s cold, 85% of it compiling, and the contract is 1,200 s. Q3 in
# the distributed session reads 325 s cold and 4.6 s warm since its joins
# are sort-merge (PR 36; 788 s and 36.1 s before): the smoke would not
# stay inside its contract with it beside Q1 and Q6 (ROADMAP.md S4), so it
# stays in the default session and in --chips 4, and the benchmark cell
# q3-compiled holds the compiled Q3 to the published answer.
DISTRIBUTED_QUERIES = (6, 1)
LOCAL_QUERIES = (6, 1, 3)
# A second literal for each compiled query: the same plan fingerprint, so
# the stored programs answer it, and another answer. With one literal a
# query, PR 24's smoke passed while the compiled tier answered every variant
# with its first execution's rows (PERF.md section 6, PR 28).
VARIANTS = {
    6: (("date '1994-01-01'", "date '1995-01-01'"),
        ("l_quantity < 24", "l_quantity < 25")),
    1: (("interval '90' day", "interval '75' day"),),
}


def require(ok, why) -> None:
    """A check that ``python -O`` does not strip."""
    if not ok:
        raise AssertionError(why)


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def normalise(rows: list[tuple]) -> list[tuple]:
    """Dates arrive as ISO strings on the wire; everything else is typed."""
    return [
        tuple(
            date.fromisoformat(v)
            if isinstance(v, str) and len(v) == 10 and v[4] == "-" == v[7]
            else v
            for v in row
        )
        for row in rows
    ]


def run_query(conn: Connection, sql: str) -> tuple[list[tuple], dict, float]:
    """One statement through the client; returns rows, the server's own
    ``GET /v1/query/{id}`` record for it, and the client's wall seconds."""
    seen = {q["queryId"] for q in conn.list_queries()}
    t0 = time.perf_counter()
    rows, _ = conn.execute(sql)
    seconds = time.perf_counter() - t0
    (qid,) = {q["queryId"] for q in conn.list_queries()} - seen
    deadline = time.monotonic() + 5.0
    while True:  # the record turns FINISHED just after the last page is out
        with urllib.request.urlopen(f"{conn.base_uri}/v1/query/{qid}", timeout=30) as r:
            info = json.loads(r.read().decode())
        if info["state"] != "FINISHING" or time.monotonic() > deadline:
            break
        time.sleep(0.01)
    require(info["state"] == "FINISHED", info)
    return normalise(rows), info, seconds


def check(qn: int, session: str, rows: list[tuple]) -> None:
    want = EXPECTED[qn]
    require(len(rows) == len(want), (qn, session, len(rows), len(want)))
    for i, (got, exp) in enumerate(zip(rows, want)):
        require(got == exp, f"Q{qn} [{session}] row {i}: got {got!r}, want {exp!r}")


def assert_compiled(qn: int, info: dict) -> dict:
    """The distributed session must have run compiled fragment programs,
    not the interpreter fallback. The server counts ``dispatchRoundTrips``
    (compiled programs dispatched) and the operator row counters only on
    the fragment tier's surviving attempt; a fallback leaves them at 0.
    ``fusedFragments`` counts members of multi-fragment fused programs and
    is 0 where a streamed scan keeps its fragment apart, so it is printed."""
    ex = info["exchangeStats"]
    require(ex is not None, f"Q{qn}: no exchangeStats")
    require(ex["dispatchRoundTrips"] >= 1, (qn, ex))
    require(ex.get("operators"), (qn, ex))
    traced = info["traceCount"] or 0
    hits = info["programCacheHits"] or 0
    require(traced + hits >= 1, (qn, traced, hits))
    return ex


def run_pair(conn, qn: int, session: str, sql: str, peak) -> list[tuple]:
    distributed = session == "distributed"
    results = []
    for temperature in ("cold", "warm"):
        rows, info, seconds = run_query(conn, sql)
        check(qn, session, rows)
        line = {
            "query": f"Q{qn}",
            "session": session,
            "run": temperature,
            "smoke_seconds": seconds,
            "compile_ms": info["compileMs"],
            "traceCount": info["traceCount"],
            "programCacheHits": info["programCacheHits"],
            "peak_bytes_in_use": peak(),
        }
        if distributed:
            ex = assert_compiled(qn, info)
            line["dispatchRoundTrips"] = ex["dispatchRoundTrips"]
            line["fusedFragments"] = ex["fusedFragments"]
        say(**line)
        results.append(rows)
    require(results[0] == results[1], f"Q{qn} [{session}]: cold != warm")
    return results[0]


def variant(qn: int, sql: str) -> str:
    for old, new in VARIANTS[qn]:
        require(old in sql, f"Q{qn}: no {old!r} to vary")
        sql = sql.replace(old, new)
    return sql


def run_variant(conn, qn: int, session: str, sql: str, base) -> list[tuple]:
    """The query with its other literals: an answer of its own, and in the
    compiled session out of the programs the first literals left."""
    rows, info, seconds = run_query(conn, variant(qn, sql))
    require(rows != base, f"Q{qn} [{session}]: the variant got the first answer")
    if session == "distributed":
        assert_compiled(qn, info)
        require(info["traceCount"] == 0, (qn, "the variant traced", info["traceCount"]))
        require(info["programCacheHits"] >= 1, (qn, info["programCacheHits"]))
    say(query=f"Q{qn}", session=session, run="variant", smoke_seconds=seconds,
        traceCount=info["traceCount"], programCacheHits=info["programCacheHits"])
    return rows


def one_chip(server: TrinoTpuServer, device) -> None:
    def peak():
        return (device.memory_stats() or {}).get("peak_bytes_in_use")

    text = queries("tpch.sf1")
    dist = Connection(server.base_uri, ClientSession(properties=dict(DISTRIBUTED)))
    local = Connection(server.base_uri, ClientSession())
    answers, variants = {}, {}
    for qn in DISTRIBUTED_QUERIES:
        answers[qn] = run_pair(dist, qn, "distributed", text[qn], peak)
        variants[qn] = run_variant(dist, qn, "distributed", text[qn], answers[qn])
    for qn in LOCAL_QUERIES:
        rows = run_pair(local, qn, "local", text[qn], peak)
        require(
            qn not in answers or rows == answers[qn],
            f"Q{qn}: the two sessions disagree",
        )
        if qn in variants:
            require(
                run_variant(local, qn, "local", text[qn], rows) == variants[qn],
                f"Q{qn}: the two sessions disagree on the variant",
            )


def four_chips(server: TrinoTpuServer, devices) -> None:
    text = queries("tpch.sf1")
    dist = Connection(server.base_uri, ClientSession(properties=dict(DISTRIBUTED)))
    for qn in (3, 1):
        rows, info, seconds = run_query(dist, text[qn])
        check(qn, "distributed", rows)
        ex = assert_compiled(qn, info)
        say(query=f"Q{qn}", session="distributed", chips=len(devices),
            smoke_seconds=seconds, compile_ms=info["compileMs"],
            traceCount=info["traceCount"],
            programCacheHits=info["programCacheHits"], exchangeStats=ex)
    # where a scanned column really lives: the largest table of the
    # engine's DeviceTableCache (the build sides' scans), and lineitem in
    # the slabs the tpch connector staged for the streamed aggregates
    # (row-sharded over the mesh since PR 35)
    cache = server.engine.table_cache
    with cache._lock:
        entries = [(key, batch) for key, (batch, _) in cache._entries.items()]
    require(entries, "the engine's DeviceTableCache is empty")
    key, batch = max(entries, key=lambda e: e[1].capacity)
    held = [(key[2], key[4][0], batch.columns[0].data)]
    slabs = server.engine.catalogs.get("tpch")._device_slabs
    require(slabs, "the tpch connector staged no slab")
    for (_, table, columns, _), (slab, _) in slabs.items():
        held.append((table, columns[0], slab.columns[0].data))
    for table, column, arr in held:
        per_device = {
            str(s.device): int(s.data.nbytes) for s in arr.addressable_shards
        }
        say(table=table, column=column, padded_rows=int(arr.shape[0]),
            devices=len(arr.sharding.device_set), shard_bytes=per_device)
        require(len(arr.sharding.device_set) == 4, arr.sharding)
        require(
            max(per_device.values()) <= 0.40 * sum(per_device.values()),
            per_device,
        )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found "
              f"{devices[0].platform!r} ({len(devices)} device(s))",
              file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    require(native.NATIVE_AVAILABLE, "native columnar library did not build")
    say(compile_cache_dir=jax.config.jax_compilation_cache_dir,
        native=native.NATIVE_AVAILABLE, jax=jax.__version__,
        trino_tpu=trino_tpu.__version__)

    server = TrinoTpuServer(port=0).start()
    try:
        if args.chips == 4:
            four_chips(server, devices)
        else:
            one_chip(server, devices[0])
    finally:
        server.stop()

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
