"""engine, program cache: programs traced per query (``traceCount``) in the
cells whose streamed aggregate probes joins: 0 once the slab program and the
fragments below it are found in the store again (the build sides ride as
arguments). The same counter as ``retraces``, whose cells it does not list."""

from benchmark.counters import per_query


def read(run):
    return per_query(run, lambda q: q.get("traceCount"))
