"""client / protocol (engine.py: span ``result.pull``): milliseconds to pull
the answer from the device and type its rows (``queryStats.phaseMs.resultPull``),
a mean over the window's queries the server still lists."""

from benchmark.counters import per_query


def read(run):
    return per_query(
        run, lambda q: ((q.get("queryStats") or {}).get("phaseMs") or {}).get("resultPull"))
