"""executor, compiled tier (exec/streaming.py: span ``stream.build``):
milliseconds a streamed aggregate spends making the build sides of its
probe-spine joins ready, each query anew, before the slab loop is dispatched
(``queryStats.phaseMs.build``): host wall to the build sides' row count, so
with the wait for the fragments below that compute them; inside ``execute_ms``.
A mean over the window's queries the server still lists; ``None`` where the
program has no such span."""

from benchmark.counters import per_query


def read(run):
    return per_query(
        run, lambda q: ((q.get("queryStats") or {}).get("phaseMs") or {}).get("build"))
