"""parse / analyse / plan, read in the compiled tier (exec/streaming.py: span
``stream.build``): live rows of the build sides the streamed aggregates of a
query make, once a query (``queryStats.buildRows``, which
``obs/trace.py::aggregate_counts`` adds up from the spans' ``rows``). The
join order the planner chose decides it: a build side that is a product of
two dimensions reads in the millions where one table filtered reads its own
rows. A mean over the window's queries the server still lists; ``None`` where
the program has no such counter."""

from benchmark.counters import per_query


def read(run):
    return per_query(run, lambda q: (q.get("queryStats") or {}).get("buildRows"))
