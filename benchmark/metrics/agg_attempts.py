"""executor (the capacity ladder of the group-bys): runs of the query's
grouped aggregates, the surviving ones included (``queryStats.aggAttempts``,
which ``obs/trace.py::aggregate_counts`` adds up from the spans: each pass
over the fragments in the compiled session, each ``op:Aggregate``'s ladder in
the default one); 1 where every group budget held, as a warm query's should."""

from benchmark.counters import per_query


def read(run):
    return per_query(run, lambda q: (q.get("queryStats") or {}).get("aggAttempts"))
