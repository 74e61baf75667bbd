"""admission (server/querymanager.py): milliseconds a query waited between
its creation and its start (``queryStats.queuedMs``), a mean over the
window's queries the server still lists."""

from benchmark.counters import per_query


def read(run):
    return per_query(run, lambda q: (q.get("queryStats") or {}).get("queuedMs"))
