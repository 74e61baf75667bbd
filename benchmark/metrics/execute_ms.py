"""executor (exec/local.py: span ``execute_plan``): milliseconds the executor
held the plan (``queryStats.phaseMs.execute``), host wall with its waits on
the device, a mean over the window's queries the server still lists."""

from benchmark.counters import per_query


def read(run):
    return per_query(
        run, lambda q: ((q.get("queryStats") or {}).get("phaseMs") or {}).get("execute"))
