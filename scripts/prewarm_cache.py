"""Pre-warm the persistent JAX compile cache (ROADMAP tier-1 runtime item).

Traces and compiles the fragment/kernel shapes the test suite hits most —
scan→agg, partitioned join (skewed and plain), streaming group-by — so a
later run that uses the same cache directory skips those compiles. The
directory is the one ``import trino_tpu`` gives every process:
``JAX_COMPILATION_CACHE_DIR`` where it is set, else ``<checkout>/.jax_cache``.

    python scripts/prewarm_cache.py

With ``--history-dir DIR`` (a query-history store written by a prior
serving run, obs/history.py) the corpus is reordered by OBSERVED elapsed
— the slowest fingerprints the store has seen warm first, ``--top N``
bounds how many history-ranked entries run — and a fingerprint →
observed-stats table prints what the history knew about each.

With ``--results`` the corpus is additionally executed with the semantic
result cache enabled and a fingerprint → cached-bytes table prints what
landed in the RESULT tier (see README "Semantic result cache").

The suite's processes follow the same rule, so tests reuse the warmed
entries. Idempotent: re-running only adds missing entries.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--history-dir", default="",
        help="query-history store directory: rank the corpus by observed "
             "elapsed (slowest first) instead of static order",
    )
    ap.add_argument(
        "--top", type=int, default=0,
        help="with --history-dir: only prewarm the N slowest "
             "history-known fingerprints (0 = all, history-known first)",
    )
    ap.add_argument(
        "--results", action="store_true",
        help="also populate the semantic RESULT cache (re-run the corpus "
             "with result_cache=on) and print a fingerprint -> "
             "cached-bytes table",
    )
    args = ap.parse_args()

    import jax
    import numpy as np

    from trino_tpu import types as T  # the import applies the cache rule

    cache_dir = jax.config.jax_compilation_cache_dir
    # write EVERY compile: the suite reads entries regardless of its own
    # write threshold, and CPU-CI compiles are individually fast but
    # collectively the tier-1 tail
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from trino_tpu.columnar import Batch, Column
    from trino_tpu.config import Session
    from trino_tpu.connectors.api import ColumnSchema, TableSchema
    from trino_tpu.testing import LocalQueryRunner

    t0 = time.time()
    runner = LocalQueryRunner()
    mem = runner.catalogs.get("memory")
    rng = np.random.default_rng(0)
    n = 1 << 16
    keys = (rng.zipf(1.2, size=6 * n)[: 6 * n] % 64 + 1)[:n].astype(np.int64)
    vals = rng.integers(0, 1000, n).astype(np.int64)
    mem.create_table(
        "default", "warm_facts",
        TableSchema("warm_facts", (ColumnSchema("k", T.BIGINT),
                                   ColumnSchema("v", T.BIGINT))),
    )
    mem.insert("default", "warm_facts",
               Batch([Column(T.BIGINT, keys), Column(T.BIGINT, vals)], n))
    dk = np.arange(1, 65, dtype=np.int64)
    mem.create_table(
        "default", "warm_dims",
        TableSchema("warm_dims", (ColumnSchema("k", T.BIGINT),
                                  ColumnSchema("name", T.BIGINT))),
    )
    mem.insert("default", "warm_dims",
               Batch([Column(T.BIGINT, dk), Column(T.BIGINT, dk * 10)], 64))

    shapes = [
        # scan -> global agg (single exchange)
        ("select count(*), sum(v) from memory.default.warm_facts", {}),
        # scan -> group-by (hash exchange + final agg)
        ("select k, sum(v) from memory.default.warm_facts group by k", {}),
        # filtered group-by + two literal variants: constant hoisting
        # canonicalizes all three to ONE fingerprint, so the corpus below
        # dedupes them to a single compile (the printed table proves it)
        ("select k, sum(v) from memory.default.warm_facts "
         "where v < 100 group by k", {}),
        ("select k, sum(v) from memory.default.warm_facts "
         "where v < 500 group by k", {}),
        ("select k, sum(v) from memory.default.warm_facts "
         "where v < 900 group by k", {}),
        # partitioned join, skew path on (detect + salt programs)
        ("select sum(f.v * d.name) from memory.default.warm_facts f "
         "join memory.default.warm_dims d on f.k = d.k",
         {"join_distribution_type": "PARTITIONED"}),
        # same join, plain two-tier path
        ("select sum(f.v * d.name) from memory.default.warm_facts f "
         "join memory.default.warm_dims d on f.k = d.k",
         {"join_distribution_type": "PARTITIONED", "skew_handling": False}),
        # TPC-H tiny shapes the suites lean on
        ("select l_returnflag, sum(l_quantity) from tpch.tiny.lineitem "
         "group by l_returnflag", {}),
    ]
    # --history-dir: rank the corpus by what a prior serving run OBSERVED.
    # The store keys on plan fingerprints, not SQL, so each corpus entry's
    # fingerprint is matched against the store; known-slow shapes warm
    # first (they are the compiles worth paying for), unknown shapes keep
    # corpus order after them, and --top N keeps only the N slowest
    # history-known entries plus the unknowns.
    if args.history_dir:
        from trino_tpu.obs.history import QueryHistoryStore

        store = QueryHistoryStore(
            path=os.path.join(args.history_dir, "query_history.json")
        )
        observed = dict(store.entries())
        ranked, unknown = [], []
        for sql, props in shapes:
            try:
                fp, _ = runner.engine.fingerprint(
                    sql,
                    Session(properties={"execution_mode": "distributed",
                                        **props}),
                )
            except Exception:
                fp = None
            ent = observed.get(fp) if fp else None
            (ranked if ent else unknown).append((sql, props, fp, ent))
        ranked.sort(key=lambda r: -float(r[3].get("elapsed_ms") or 0.0))
        if args.top > 0:
            for sql, props, fp, _ent in ranked[args.top:]:
                print(f"below-top skip {fp[:12] if fp else '?':<12} "
                      f"{sql.split(chr(10))[0][:52]}")
            ranked = ranked[: args.top]
        if ranked:
            print(f"history {store.path or '(memory)'}: "
                  f"{len(observed)} fingerprints, "
                  f"{len(ranked)} matched in corpus\n")
            print("fingerprint   count  p50 ms  retries  halvings  "
                  "peak HBM B  query")
            for sql, _props, fp, ent in ranked:
                print(f"{fp[:12]}  {ent.get('count', 0):>5}  "
                      f"{float(ent.get('elapsed_p50_ms') or 0.0):>6.1f}  "
                      f"{ent.get('overflow_retries', 0):>7}  "
                      f"{ent.get('compile_halvings', 0):>8}  "
                      f"{ent.get('peak_hbm_bytes', 0):>10}  "
                      f"{sql.split(chr(10))[0][:40]}")
            print()
        else:
            print(f"history {store.path or '(memory)'}: no corpus entry "
                  "matches a stored fingerprint; static order\n")
        shapes = [(sql, props) for sql, props, _fp, _e in ranked + unknown]

    # one representative per canonical plan shape: literal variants share
    # a fingerprint, so executing the first warms the program cache (and
    # the persistent XLA cache) for every other member of the family
    seen_fps: dict[str, str] = {}
    for sql, props in shapes:
        for mode in ("local", "distributed"):
            s = Session(properties={"execution_mode": mode, **props})
            label = sql.split(chr(10))[0][:60]
            try:
                fp = None
                if mode == "distributed":
                    fp, _params = runner.engine.fingerprint(sql, s)
                    if fp is not None and fp in seen_fps:
                        print(f"dedup  [{mode}] {label} "
                              f"(= {fp[:12]} already warmed)")
                        continue
                runner.engine.execute_statement(sql, s)
                if fp is not None:
                    seen_fps[fp] = label
                print(f"warmed [{mode}] {label}")
            except Exception as e:  # noqa: BLE001 — warm what we can
                print(f"skip   [{mode}] {type(e).__name__}: {e}")
    # fingerprint -> compiled-program table (engine program cache)
    cache = getattr(runner.engine, "_query_cache", {})
    if cache:
        print("\nfingerprint   programs  query")
        for key, entry in cache.items():
            fp = key[0] if isinstance(key, tuple) else str(key)
            print(f"{fp[:12]}  {len(entry.get('programs', {})):>8}  "
                  f"{seen_fps.get(fp, '?')}")
    # --results: re-run the corpus with the semantic result cache on so a
    # serving run that shares this engine (or reads /v1/cache) starts with
    # warm RESULT entries, then print what got cached. Literal variants
    # that share a fingerprint still store separately (the param vector is
    # part of the entry key), so the table can show more rows than the
    # compile table above.
    if args.results:
        for sql, props in shapes:
            s = Session(properties={"execution_mode": "distributed",
                                    "result_cache": True, **props})
            try:
                runner.engine.execute_statement(sql, s)
            except Exception as e:  # noqa: BLE001 — warm what we can
                print(f"skip   [result] {type(e).__name__}: {e}")
        snap = runner.engine.result_cache.snapshot()
        print("\nfingerprint   rows     bytes  maint  query")
        for ent in snap["entries"]:
            fp = ent["fingerprint"] or "?"
            print(f"{fp[:12]}  {ent['rows']:>4}  {ent['nbytes']:>8}  "
                  f"{'yes' if ent['maintainable'] else ' no':>5}  "
                  f"{ent['query'][:48]}")
        print(f"result cache: {len(snap['entries'])} entries, "
              f"{snap['totalBytes']} / {snap['maxBytes']} bytes")
    n_entries = (
        len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    )
    print(
        f"cache dir {cache_dir}: {n_entries} entries, "
        f"{time.time() - t0:.1f}s"
    )


if __name__ == "__main__":
    main()
