#!/usr/bin/env python
"""Which span of the program was open while the device sat idle.

    python scripts/hostgaps.py --workload q1-default [--queries 2] [--seed 1]
    python scripts/hostgaps.py --workload q3v-default --queries 1 \\
        --params '{"DATE": "1995-03-07"}'

Drives the served path as the benchmark does (``benchmark.harness.load_cell``
names the configuration, the mix and its templates; one ``TrinoTpuServer``,
one ``client.Connection``, each template once cold and once warm), then
profiles a few warm queries with ``jax.profiler`` and reads the trace with
``benchmark.tracereduce``. The program's spans are in that trace as
``trino:<name>`` events (``obs/trace.py``), on the clock of the device's
operations, so each idle gap of the first device is put down to the
innermost span open at that instant, and each program launch (line "XLA
Modules") and each of the largest device operations to the ``op:`` span it
began under. One JSON line:

- ``idle_by_span``: idle seconds per query (of the queries after the first,
  which carries the profiler's start-up) under each innermost span
  (``op:Join#5`` is plan node 5, pre-order as EXPLAIN prints), ``(none)`` where no span of the program was open (between two queries, or
  before the dispatch thread took the query); ``named_share`` is the rest's
  share of the idle time;
- ``busy_by_span``: the device's busy seconds per query, shared out likewise;
- ``launches_by_op``: program launches per query that began under each
  ``op:`` span;
- ``largest_ops``: the slice's largest device operations, each with the
  ``op:`` span most of its time began under;
- ``queries``: per profiled query, ``queryStats`` (``phaseMs``,
  ``operatorMs``, ``xlaCompiles`` ...), ``ingestStats`` (``table_cache_hits``,
  ``h2d_bytes``) and the operators' own attributes (``attempts``,
  ``capacities``, ``tableCacheHit``, ``xlaCompiles``) from its timeline.

Needs the chips the cell asks for (``--platform cpu`` rehearses it, reading
the host's XLA threads as the device). Nothing here is part of the
benchmark: folding these gaps into ``breakdown.idle_gaps`` is a change to
``benchmark/tracereduce.py``, a ``benchmark`` issue's.
"""

import argparse
import json
import os
import re
import shutil
import sys
import time
import urllib.request
from pathlib import Path

ROOT = str(Path(__file__).resolve().parents[1])
sys.path.insert(0, ROOT)

from benchmark import tracereduce  # noqa: E402
from benchmark.harness import load_cell  # noqa: E402

PREFIX = "trino:"
NONE = "(none)"


def innermost_segments(events):
    """``(start, end, name)`` pieces of the time line, each under the span
    that started last among those open (spans of one thread nest; another
    thread's span, a prefetch say, counts while it is the latest)."""
    cuts = sorted({t for _, s, e in events for t in (s, e)})
    pieces = []
    for lo, hi in zip(cuts, cuts[1:]):
        open_ = [(s, -e, n) for n, s, e in events if s <= lo and e >= hi]
        if open_:
            pieces.append((lo, hi, max(open_)[2]))
    return pieces


def share(intervals, pieces, per):
    """Seconds of ``intervals`` (a sorted union) under each piece's name."""
    out = {}
    for lo, hi, name in pieces:
        part = tracereduce.overlap(intervals, [(lo, hi)])
        if part > 0:
            out[name] = out.get(name, 0.0) + part / 1e9 / per
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def enclosing_op(ops, t):
    """The label of the innermost ``op:`` span open at ``t``."""
    best = None
    for label, s, e in ops:
        if s <= t < e and (best is None or s >= best[1]):
            best = (label, s)
    return best[0] if best else NONE


def label_operators(events, timelines):
    """``trino:op:Join`` events as ``op:Join#<node>``: the k-th operator span
    a query opened is the k-th ``op:`` span of its timeline."""
    numbered = []
    for spans in timelines:
        ops = sorted((s for s in spans if s["name"].startswith("op:")),
                     key=lambda s: s["startNs"])
        numbered.extend(f"{s['name']}#{s['attrs'].get('node')}" for s in ops)
    traced = sorted((e for e in events if e[0].startswith("op:")), key=lambda e: e[1])
    if len(traced) != len(numbered):
        return [(n, s, e) for n, s, e in traced]  # a query the profiler cut
    return [(label, s, e) for label, (_, s, e) in zip(numbered, traced)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="scripts/hostgaps.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--queries", type=int, default=2)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--params", default=None,
                    help="JSON: hold the template's parameters at these values")
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1,
                    help="0: no profiler, only the queries' own stats (a long query)")
    ap.add_argument("--platform", default="tpu", choices=("tpu", "cpu"))
    ap.add_argument("--schema", default=None,
                    help="rehearsal: this schema (tiny) in place of the configuration's")
    args = ap.parse_args(argv)

    _, _, cell, config, mix = load_cell(ROOT, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != args.platform or len(devices) != cell["chips"]:
        print(f"hostgaps: cell {cell['name']} needs {cell['chips']} {args.platform} "
              f"device(s), JAX found {len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 2
    if args.platform == "cpu":  # the operations run on host threads there
        tracereduce.DEVICE_PLANE = re.compile(r"^/host:CPU$")
        tracereduce.OP_LINE = re.compile(r"^tf_XLA")

    import trino_tpu  # noqa: F401  (the compile cache, by the program's rule)
    from trino_tpu import client
    from trino_tpu.server.http import TrinoTpuServer

    if args.schema:
        config = {**config, "schema": args.schema}
    schema = f"{config['catalog']}.{config['schema']}"
    held = json.loads(args.params) if args.params else {}
    trace_dir = os.path.join(ROOT, ".cache", "benchmark", "trace-hostgaps")
    server = TrinoTpuServer(port=0).start()
    try:
        conn = client.Connection(
            server.base_uri,
            client.ClientSession(catalog=config["catalog"], schema=config["schema"],
                                 properties=dict(config["session"])),
        )
        warmups = []
        for name, template in mix.templates.items():
            for params in (template.meta["validation"], mix.grids[name][-1]):
                a = time.perf_counter()
                conn.execute(template.sql(schema, params))
                warmups.append(time.perf_counter() - a)
        seen = {q["queryId"] for q in conn.list_queries()}

        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        sent = []
        if args.profile:
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for name, params in mix.schedule(args.seed, 0):
                if len(sent) == args.queries:
                    break
                params = {**params, **held}
                a = time.perf_counter()
                conn.execute(mix.templates[name].sql(schema, params))
                sent.append({"template": name, "params": params,
                             "seconds": time.perf_counter() - a})
        finally:
            if args.profile:
                jax.profiler.stop_trace()
        time.sleep(0.2)  # the last root span closes after the last page
        infos = [q for q in conn.list_queries() if q["queryId"] not in seen]
        timelines = []
        for q in infos:
            with urllib.request.urlopen(
                    f"{server.base_uri}/v1/query/{q['queryId']}/timeline", timeout=10) as r:
                timelines.append(json.loads(r.read().decode())["spans"])
    finally:
        server.stop()

    queries = []
    for q, spans in zip(infos, timelines):
        stats = q.get("queryStats") or {}
        operators = [
            {"op": f"{s['name']}#{s['attrs'].get('node')}", "ms": s["durationMs"],
             **{k: v for k, v in s["attrs"].items() if k != "node"}}
            for s in sorted(spans, key=lambda s: s["startNs"]) if s["name"].startswith("op:")
        ]
        queries.append({
            "sql_tail": q["query"][-60:], "spans": len(spans),
            **{k: stats.get(k) for k in ("elapsedMs", "queuedMs", "phaseMs", "operatorMs",
                                         "xlaCompiles", "xlaCompileMs", "xlaCacheLoads")},
            "ingestStats": q.get("ingestStats"),
            "operators": operators,
        })
    head = {"workload": cell["name"], "device": devices[0].device_kind,
            "queries_sent": len(sent), "sent": sent,
            "warmup_s": warmups}
    if not args.profile:
        print(json.dumps({**head, "queries": queries}), flush=True)
        return 0

    planes = tracereduce.read(tracereduce.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    events = []  # (name without the prefix, start, end) of the program's spans
    for plane in planes:
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(PREFIX):
                    events.append((name[len(PREFIX):], start, start + dur))
    device = next((p for p in planes if tracereduce.DEVICE_PLANE.match(p["name"])), None)
    if device is None or not events:
        print("hostgaps: the trace holds no device plane or no trino: span", file=sys.stderr)
        return 1
    events = ([ev for ev in events if not ev[0].startswith("op:")]
              + label_operators(events, timelines))
    lo = min(s for _, s, _ in events)
    hi = max(e for _, _, e in events)
    n = len(sent)
    roots = sorted((s, e) for name, s, e in events if name == "query")
    if len(roots) == n > 1:
        # the profiler's own start-up lands in the first query it sees
        # (1.3 s of a 3 s Q1): the shares are of the queries after it
        lo, n = roots[0][1], n - 1
        events = [ev for ev in events if ev[1] >= lo]
    dev_ops = tracereduce._events(device, tracereduce.OP_LINE)
    busy = tracereduce.clip(tracereduce.union([(s, s + d) for _, s, d in dev_ops]), lo, hi)
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = e
    if hi > at:
        gaps.append((at, hi))
    ops = [ev for ev in events if ev[0].startswith("op:")]
    pieces = innermost_segments(events)
    covered = tracereduce.union([(a, b) for a, b, _ in pieces])
    uncovered, at = [], lo
    for s, e in covered:
        if s > at:
            uncovered.append((at, s, NONE))
        at = e
    idle = share(gaps, pieces + uncovered, n)
    idle_s = tracereduce.length(gaps) / 1e9 / n

    launches = {}
    for _, start, _ in tracereduce._events(device, tracereduce.PROGRAM_LINE):
        if lo <= start < hi:
            label = enclosing_op(ops, start)
            launches[label] = launches.get(label, 0) + 1
    by_op = {}  # device operation -> {op: span -> ns}
    for name, start, dur in dev_ops:
        if lo <= start < hi:
            per = by_op.setdefault(tracereduce.short_name(name), {})
            label = enclosing_op(ops, start)
            per[label] = per.get(label, 0.0) + dur
    largest = sorted(by_op.items(), key=lambda kv: -sum(kv[1].values()))[:8]

    print(json.dumps({
        **head, "queries_shared_out": n,
        "slice_s": (hi - lo) / 1e9, "busy_s_per_query": tracereduce.length(busy) / 1e9 / n,
        "idle_s_per_query": idle_s,
        "named_share": 1.0 - idle.get(NONE, 0.0) / idle_s if idle_s else None,
        "idle_by_span": idle,
        "busy_by_span": share(busy, pieces + uncovered, n),
        "launches_by_op": {k: v / n for k, v in sorted(launches.items(), key=lambda kv: -kv[1])},
        "largest_ops": [
            {"op": name, "seconds": sum(per.values()) / 1e9,
             "under": max(per, key=per.get), "share_under": max(per.values()) / sum(per.values())}
            for name, per in largest
        ],
        "queries": queries,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
