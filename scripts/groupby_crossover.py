#!/usr/bin/env python
"""Where ``group_aggregate``'s domain path stops beating its sort path.

    python scripts/groupby_crossover.py [--rows 2097152] [--slots 12 16 64 256]

Times one ``group_aggregate`` call at Q1's lanes (two int32 dictionary-coded
keys; three narrow 128-bit sums, two wide ones, two more narrow ones for the
averages, a row count: 27 int64 limb lanes), jitted, on the device JAX
finds: the sort path once, the domain path at each slot count, the median
of ``--calls`` calls after one that compiles. A slot count over
``DOMAIN_MAX_SLOTS`` calls the domain path directly, past the gate, which is
the point: the reading is what sets that constant (``PERF.md`` section 6).
One JSON line per reading, the last one the summary; nothing here is part
of the benchmark.

    python scripts/groupby_crossover.py --step-rows 2097152 8388608 16777216 \
        [--groups 1048576]

times instead one step of the compiled session's slab loop
(``StreamingAggregator._step_grouped``: the chunk partial over ``rows`` raw
rows, then the merge of the ``groups``-sized state with the chunk's groups)
at each width, at the lanes of h2o's q5 (one BIGINT key uniform over
``groups`` values, two BIGINT sums, one DECIMAL sum of three limb lanes), the
state full: what a row of a step costs and what the state costs whatever the
rows. The reading that sets ``exec/streaming.py::SLAB_ROWS_PER_GROUP``
(``PERF.md`` section 6, PR 34).
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
from trino_tpu.ops import aggregation as A  # noqa: E402

SPECS = tuple(
    A.AggSpec(k) for k in ("sum128", "sum128", "sum128w", "sum128w",
                           "sum128", "sum128", "sum128", "count_star")
)
MAX_GROUPS = 4096


def inputs(rows: int, domains, seed: int):
    rng = np.random.default_rng(seed)
    keys = tuple(
        (jnp.asarray(rng.integers(0, d, rows).astype(np.int32)), None)
        for d in domains
    )
    sel = jnp.asarray(rng.random(rows) < 0.98)

    def money():
        return jnp.asarray(rng.integers(0, 10_000_000, rows))

    def wide():
        return jnp.stack([jnp.zeros(rows, jnp.int64), money()], axis=1)

    qty, price, disc = money(), money(), money()
    lanes = ((qty, None), (price, None), (wide(), None), (wide(), None),
             (qty, None), (price, None), (disc, None), None)
    return keys, sel, lanes


def timed(fn, args, calls: int):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    ms = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append((time.perf_counter() - t0) * 1000.0)
    return first, ms


def slab_steps(widths, groups: int, calls: int, seed: int, device) -> None:
    """One JSON line a width: a slab step at q5's lanes (module docstring)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from trino_tpu.exec.streaming import StreamingAggregator
    from trino_tpu.parallel.mesh import AXIS, make_mesh

    sagg = object.__new__(StreamingAggregator)  # the step reads these four
    sagg.nkeys, sagg.G, sagg.n, sagg.mesh = 1, groups, 1, make_mesh(1)
    placed = NamedSharding(sagg.mesh, PartitionSpec(AXIS))
    specs = (A.AggSpec("sum"), A.AggSpec("sum"), A.AggSpec("sum128"))
    combine, limbs = ["sum"] * 3, [1, 1, 3]

    @jax.jit
    def step(state, key, v1, v2, v3):
        sel = jnp.ones(key.shape, jnp.bool_)
        out = sagg._step_grouped(
            state, [(key, None)], sel, [(v1, None), (v2, None), (v3, None)],
            specs, combine, limbs, None)
        return {k: v for k, v in out.items() if k != "overflow"}, out["overflow"]

    for rows in sorted(widths):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        chunk = [jax.random.randint(k, (rows,), 1, hi + 1, jnp.int64)
                 for k, hi in zip(ks, (groups, 5, 15, 99_999_999))]
        state = jax.device_put({
            "key_data": [jnp.zeros(groups, jnp.int64)],
            "key_valid": [jnp.zeros(groups, jnp.bool_)],
            "live": jnp.zeros(groups, jnp.bool_),
            "values": [jnp.zeros((groups,) if w == 1 else (groups, w), jnp.int64)
                       for w in limbs],
            "counts": [jnp.zeros(groups, jnp.int64) for _ in limbs],
        }, placed)  # as ``_init_state`` places it: one compilation a width
        t0 = time.perf_counter()
        state, overflow = jax.block_until_ready(step(state, *chunk))
        first = time.perf_counter() - t0
        state, overflow = jax.block_until_ready(step(state, *chunk))
        ms = []
        for _ in range(calls):  # the state is full by now
            t0 = time.perf_counter()
            state, overflow = jax.block_until_ready(step(state, *chunk))
            ms.append((time.perf_counter() - t0) * 1000.0)
        memory = device.memory_stats() or {}
        print(json.dumps({
            "path": "slab_step", "rows": rows, "groups": groups,
            "groups_live": int(jnp.sum(state["live"])), "overflow": int(overflow),
            "first_s": first, "ms": ms, "median_ms": statistics.median(ms),
            "us_per_row": statistics.median(ms) * 1000.0 / rows,
            # the process's high-water mark: widths run in ascending order
            "peak_bytes_in_use": memory.get("peak_bytes_in_use"),
        }), flush=True)
        del chunk, state


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 21)
    ap.add_argument("--slots", type=int, nargs="+", default=[12, 16, 64, 256])
    ap.add_argument("--step-rows", type=int, nargs="+", default=None)
    ap.add_argument("--groups", type=int, default=1 << 20)
    ap.add_argument("--calls", type=int, default=7)
    ap.add_argument("--seed", type=int, default=29)
    args = ap.parse_args()
    device = jax.devices()[0]
    if args.step_rows:
        print(json.dumps({"device": {"platform": device.platform,
                                     "kind": device.device_kind}}), flush=True)
        slab_steps(args.step_rows, args.groups, args.calls, args.seed, device)
        return 0
    out = {"device": {"platform": device.platform, "kind": device.device_kind},
           "rows": args.rows, "max_slots": A.DOMAIN_MAX_SLOTS, "domain_ms": {}}
    for slots in args.slots:
        # two keys without masks: (d0 + 1) * (d1 + 1) slots
        d0 = next(d for d in range(int(slots ** 0.5), 0, -1) if slots % d == 0)
        domains = (d0 - 1, slots // d0 - 1)
        keys, sel, lanes = inputs(args.rows, domains, args.seed)
        assert np.prod([d + 1 for d in domains]) == slots
        if "sort_ms" not in out:
            sort = jax.jit(lambda k, s, ln: A.group_aggregate(
                k, s, ln, SPECS, MAX_GROUPS))
            first, ms = timed(sort, (keys, sel, lanes), args.calls)
            out["sort_ms"] = statistics.median(ms)
            print(json.dumps({"path": "sort", "first_s": first, "ms": ms}),
                  flush=True)
        domain = lambda k, s, ln: A._domain_aggregate(  # noqa: E731
            k, s, ln, SPECS, domains, MAX_GROUPS)
        first, ms = timed(domain, (keys, sel, lanes), args.calls)
        out["domain_ms"][slots] = statistics.median(ms)
        print(json.dumps({"path": "domain", "slots": slots, "first_s": first,
                          "ms": ms}), flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
