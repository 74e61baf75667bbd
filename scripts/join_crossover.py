#!/usr/bin/env python
"""The compiled tier's three join kernels against each other, on the device
JAX finds.

    python scripts/join_crossover.py [--shapes q3-slab q3-build dim q5-slab]
        [--strategies sort dense matmul lookup] [--calls 5] [--budget-s 1500]

times ``parallel/distributed.py::_sharded_probe`` (the whole per-shard join:
build, probe, ``verify_equal`` and the output gathers of two probe and two
build payload columns, as ``exec/fragments.py::_exec_join`` calls it),
jitted, at each strategy and shape: the first call (which compiles) and
the median of ``--calls`` calls after it. One 64-bit key lane. ``lookup`` is
``sort`` where the build key is unique, as every shape's is, so the output is
the probe's width whatever the shape's (``_sharded_probe(lookup=True)``: no
expansion, no probe column gathered). The shapes are the static ones
``_join_strategy`` can see:

- ``q3-slab``: a step of TPC-H Q3's slab loop at SF1: lineitem's chunk probes
  the orders that passed the two fragments below (``q3-slab-wide``: the same
  against the 4,194,304 slots the join below really hands up);
- ``q3-build``: the fragment program below it, which makes the slab's build
  side: orders probes the customers of one market segment, ``sort`` expanding
  into 4,194,304 slots against ``lookup`` at orders' 2,097,152 (the join
  ``stream.build`` waits for, ``build_ms``);
- ``dim``: a fact chunk against a 1,024-row dimension in the smallest
  capacity the tracer gives a build side (the star-join shape, and the one
  the history-seeded ``matmul`` promotion was written for);
- ``q5-slab``: a step of TPC-H Q5's slab loop at SF1: lineitem's chunk probes
  the orders of 1994, whose key is unique, at the probe's own width (the
  capacity ``_exec_join`` gives a unique build).

    python scripts/join_crossover.py --sql sort dense [--schema sf1]

runs Q3 at its validation parameters through the served path in the compiled
session with ``join_strategy`` pinned to each value named (``auto`` is the
session's default): cold, then twice warm; the client's seconds,
``phaseMs.build``, ``exchangeStats.joinStrategy``, the ``joins`` attributes of
the query's spans, and at ``sf1`` whether the rows are the published answer.

One JSON line per reading. The readings set what ``_join_strategy`` answers
under ``auto`` (``PERF.md`` section 6, PR 36); nothing here is part of the
benchmark. ``--platform cpu`` rehearses at small sizes and its times mean
nothing.
"""

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import trino_tpu  # noqa: E402,F401  (x64, the compile cache)
from trino_tpu.columnar import bucket_capacity  # noqa: E402
from trino_tpu.ops import join as J  # noqa: E402
from trino_tpu.parallel.distributed import _sharded_probe  # noqa: E402
from trino_tpu.parallel.mesh import make_mesh  # noqa: E402

# name: probe capacity, probe live rows, build capacity, build live rows,
# distinct keys of the domain both sides draw from, output capacity
SHAPES = {
    "q3-slab": (2_097_152, 1_130_000, 2_097_152, 147_000, 1_500_000, 4_194_304),
    # ... at the build capacity the tracer gives it: the join below's output
    "q3-slab-wide": (2_097_152, 1_130_000, 4_194_304, 147_000, 1_500_000, 4_194_304),
    "q3-build": (2_097_152, 1_500_000, 262_144, 30_000, 150_000, 4_194_304),
    "dim": (2_097_152, 2_097_152, 1_024, 1_024, 1_024, 4_194_304),
    "q5-slab": (2_097_152, 1_130_000, 2_097_152, 227_597, 1_500_000, 2_097_152),
}
STARTED = time.perf_counter()


def say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def inputs(shape, seed: int, scale: int):
    """Both sides of one join: distinct build keys drawn from the domain,
    probe keys uniform over it (so a probe row matches with probability
    live build rows / domain), two payload columns a side."""
    probe_cap, probe_live, build_cap, build_live, domain, out_cap = (
        max(16, v // scale) for v in shape
    )
    build_live, probe_live = min(build_live, domain), min(probe_live, probe_cap)
    rng = np.random.default_rng(seed)
    bkey = np.zeros(build_cap, np.int64)
    bkey[:build_live] = 1 + rng.choice(domain, build_live, replace=False)
    pkey = 1 + rng.integers(0, domain, probe_cap)
    side = {}
    for name, key, cap, live in (("probe", pkey, probe_cap, probe_live),
                                 ("build", bkey, build_cap, build_live)):
        sel = np.arange(cap) < live
        valid = jnp.ones(cap, jnp.bool_)
        cols = []
        for _ in range(2):
            cols += [jnp.asarray(rng.integers(0, 10_000_000, cap)), valid]
        side[name] = (cols, [jnp.asarray(key), valid], jnp.asarray(sel))
    return side, out_cap


def kernel(mesh, strategy: str, out_cap: int, build_cap: int):
    lookup = strategy == "lookup"
    strategy = "sort" if lookup else strategy
    # the table's size as ``_exec_join`` sets it: 4 slots a build row
    table_cap = None if strategy == "sort" else bucket_capacity(
        max(1024, 4 * build_cap))

    @jax.jit
    def run(probe, build):
        (pcols, pkeys, psel), (bcols, bkeys, bsel) = probe, build
        ph, _ = J.hash_keys([tuple(pkeys)])
        bh, _ = J.hash_keys([tuple(bkeys)])
        res = _sharded_probe(
            mesh, pcols, pkeys, ph, psel, bcols, bkeys, bh, bsel, out_cap,
            "INNER", 1, strategy=strategy, table_cap=table_cap, lookup=lookup,
        )
        outs, osel, *flags = res
        # what a consumer would read: every output lane at the live rows
        return [jnp.where(osel, o, 0).sum() for o in outs[::2]], osel.sum(), flags

    return run


def time_kernels(args) -> None:
    mesh = make_mesh(1)
    made, answers = {}, {}
    # matmul last: it is dense with another slot base, and a budget that
    # runs out should have read sort against dense at every shape first
    order = sorted(args.strategies, key=lambda s: s == "matmul")
    for strategy in order:
        # (and of matmul's shapes first the one its promotion was written for)
        for name in sorted(args.shapes, key=lambda n: strategy == "matmul" and n != "dim"):
            if time.perf_counter() - STARTED > args.budget_s:
                say(shape=name, strategy=strategy, skipped="budget")
                continue
            if name not in made:
                made[name] = inputs(SHAPES[name], args.seed, args.scale)
            side, out_cap = made[name]
            build_cap = side["build"][2].shape[0]
            width = side["probe"][2].shape[0] if strategy == "lookup" else out_cap
            run = kernel(mesh, strategy, width, build_cap)
            t0 = time.perf_counter()
            out = jax.block_until_ready(run(side["probe"], side["build"]))
            first = time.perf_counter() - t0
            ms = []
            for _ in range(args.calls):
                t0 = time.perf_counter()
                jax.block_until_ready(run(side["probe"], side["build"]))
                ms.append((time.perf_counter() - t0) * 1000.0)
            sums, rows, flags = out
            answer = ([int(s) for s in sums], int(rows))
            say(shape=name, strategy=strategy,
                probe_cap=side["probe"][2].shape[0], build_cap=build_cap,
                build_live=int(side["build"][2].sum()), out_cap=width,
                out_rows=int(rows), flags=[int(f) for f in flags],
                first_s=round(first, 3), ms=statistics.median(ms),
                ms_all=[round(m, 3) for m in ms],
                device=jax.devices()[0].device_kind)
            if answers.setdefault(name, answer) != answer:
                say(shape=name, strategy=strategy, disagree=[answers[name], answer])
                raise SystemExit(1)


def run_sql(args) -> None:
    import urllib.request

    import chip_smoke
    from trino_tpu.benchmarks.tpch import queries
    from trino_tpu.client import ClientSession, Connection
    from trino_tpu.server.http import TrinoTpuServer

    sql = queries(f"tpch.{args.schema}")[3]
    server = TrinoTpuServer(port=0).start()
    try:
        for strategy in args.sql:
            props = {"execution_mode": "distributed", **dict(args.session)}
            if strategy != "auto":
                props["join_strategy"] = strategy
            conn = Connection(server.base_uri, ClientSession(properties=props))
            for run in ("cold", "warm", "warm"):
                rows, info, seconds = chip_smoke.run_query(conn, sql)
                with urllib.request.urlopen(
                    f"{server.base_uri}/v1/query/{info['queryId']}/timeline",
                    timeout=30,
                ) as r:
                    spans = json.loads(r.read().decode())["spans"]
                joins = {
                    s["name"]: s["attrs"]["joins"]
                    for s in spans if (s.get("attrs") or {}).get("joins")
                }
                say(sql="q3", schema=args.schema, join_strategy=strategy, run=run,
                    seconds=seconds,
                    published=rows == chip_smoke.EXPECTED[3]
                    if args.schema == "sf1" else None,
                    first_row=rows[0] if rows else None,
                    build_ms=info["queryStats"]["phaseMs"].get("build"),
                    execute_ms=info["queryStats"]["phaseMs"].get("execute"),
                    traceCount=info["traceCount"],
                    xlaCompiles=info["queryStats"].get("xlaCompiles"),
                    joinStrategy=info["exchangeStats"].get("joinStrategy"),
                    joins=joins, devices=len(jax.devices()),
                    device=jax.devices()[0].device_kind)
    finally:
        server.stop()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shapes", nargs="*", default=["q3-slab", "q3-build", "dim"],
                    choices=list(SHAPES))
    ap.add_argument("--strategies", nargs="*", default=["sort", "dense", "matmul"],
                    choices=["sort", "dense", "matmul", "lookup"])
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=36)
    ap.add_argument("--scale", type=int, default=1,
                    help="divide every shape by this (a rehearsal)")
    ap.add_argument("--budget-s", type=float, default=1e9,
                    help="start no kernel reading after this many seconds")
    ap.add_argument("--sql", nargs="*", default=[],
                    choices=["auto", "sort", "dense", "matmul"])
    ap.add_argument("--schema", default="sf1")
    ap.add_argument("--session", nargs="*", default=[],
                    type=lambda kv: tuple(kv.split("=", 1)),
                    help="more session properties for --sql, as name=value")
    ap.add_argument("--platform", default="tpu", choices=["tpu", "cpu"])
    args = ap.parse_args()

    if jax.devices()[0].platform != args.platform:
        print(f"join_crossover: wants a {args.platform}, JAX found "
              f"{jax.devices()[0].platform!r}", file=sys.stderr)
        return 1
    if args.sql:
        run_sql(args)
    if args.shapes and args.strategies:
        time_kernels(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
