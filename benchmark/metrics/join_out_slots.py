"""executor, compiled tier (exec/streaming.py: span ``stream.slab``): output
slots the joins of a streamed aggregate's slab step carry a query, summed
over the step's joins and its steps (``queryStats.joinOutSlots``, which
``obs/trace.py::aggregate_counts`` takes from the ``joins`` of each streamed
aggregate's last ``stream.slab`` span: ``outCap`` times ``steps``). Every
column of both sides of a join is gathered at its output's width, so this is
the width the probe spine carries through the loop. A mean over the window's
queries the server still lists; ``None`` where the program has no such
counter."""

from benchmark.counters import per_query


def read(run):
    return per_query(run, lambda q: (q.get("queryStats") or {}).get("joinOutSlots"))
