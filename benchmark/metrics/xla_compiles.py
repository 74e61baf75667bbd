"""engine, program cache (obs/trace.py: the ``jax.monitoring`` listener): XLA
executables built per query, compiled or loaded from the persistent cache
(``queryStats.xlaCompiles``), whatever executor ran it; a warm window reads 0."""

from benchmark.counters import per_query


def read(run):
    return per_query(run, lambda q: (q.get("queryStats") or {}).get("xlaCompiles"))
