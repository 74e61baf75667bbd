"""executor, compiled tier (exec/streaming.py: span ``stream.slab``): steps
of the slab loop a query, each one chunk partial and one merge into the group
state (``queryStats.slabSteps``, which ``obs/trace.py::aggregate_counts`` takes
from the ``steps`` of each streamed aggregate's last ``stream.slab`` span);
the step's width is the session's on the domain path and grows with the group
budget on the sort path (``slab_step_rows``), so fewer steps merge the state
fewer times. A mean over the window's queries the server still lists."""

from benchmark.counters import per_query


def read(run):
    return per_query(run, lambda q: (q.get("queryStats") or {}).get("slabSteps"))
