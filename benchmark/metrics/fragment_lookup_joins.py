"""executor, compiled tier (exec/fragments.py::_exec_join, spans
``fragment_execute`` and ``fused_execute``): joins of a query's fragment
programs that ran as a lookup: a build side made before the program
(``FragmentedExecutor._unique_builds``) whose key is unique among its live
rows, at the probe's own width, so output row ``i`` is probe row ``i`` and no
probe column is gathered (``queryStats.fragmentLookupJoins``, which
``obs/trace.py::aggregate_counts`` counts from the ``lookup`` of the ``joins``
of each stage's last such span). A mean over the window's queries the server
still lists; ``None`` where the program has no such counter."""

from benchmark.counters import per_query


def read(run):
    return per_query(
        run, lambda q: (q.get("queryStats") or {}).get("fragmentLookupJoins")
    )
