"""executor, compiled tier (exec/fragments.py::_exec_join, span ``stream.slab``):
joins of a streamed aggregate's slab step that ran as a lookup: a build key
unique among the build's live rows, at the probe's own width, so output row
``i`` is probe row ``i`` and no probe column is gathered
(``queryStats.lookupJoins``, which ``obs/trace.py::aggregate_counts`` counts
from the ``lookup`` of the ``joins`` of each streamed aggregate's last
``stream.slab`` span). A mean over the window's queries the server still
lists; ``None`` where the program has no such counter."""

from benchmark.counters import per_query


def read(run):
    return per_query(run, lambda q: (q.get("queryStats") or {}).get("lookupJoins"))
