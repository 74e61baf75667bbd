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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 21)
    ap.add_argument("--slots", type=int, nargs="+", default=[12, 16, 64, 256])
    ap.add_argument("--calls", type=int, default=7)
    ap.add_argument("--seed", type=int, default=29)
    args = ap.parse_args()
    device = jax.devices()[0]
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
