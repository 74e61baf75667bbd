"""executor, compiled tier (exec/streaming.py: span ``stream.slab``):
milliseconds from the slab program's lookup in the store to its result
(``queryStats.phaseMs.slab``), host wall with the wait on the device, inside
``execute_ms``; a mean over the window's queries the server still lists."""

from benchmark.counters import per_query


def read(run):
    return per_query(
        run, lambda q: ((q.get("queryStats") or {}).get("phaseMs") or {}).get("slab"))
