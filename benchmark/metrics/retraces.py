"""engine, program cache: programs traced per query (``traceCount``); a warm
query should trace nothing."""

from benchmark.counters import per_query


def read(run):
    return per_query(run, lambda q: q.get("traceCount"))
