"""ingest (exec/local.py: spans ``op:TableScan`` with ``ingest.decode`` and
``ingest.h2d`` below them): milliseconds in the plan's table scans, their
self time summed (``queryStats.operatorMs.TableScan``), a mean over the
window's queries the server still lists."""

from benchmark.counters import per_query


def read(run):
    return per_query(
        run, lambda q: ((q.get("queryStats") or {}).get("operatorMs") or {}).get("TableScan"))
