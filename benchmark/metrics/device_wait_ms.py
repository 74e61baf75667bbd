"""executor, compiled tier (exec/fragments.py: span ``device_pull``):
milliseconds a query's host stood in a pull from the device inside
``execute_plan`` (``queryStats.phaseMs.devicePull``): the packed pull of the
root batch with the overflow flags and counters, so the last program's run
and the transfer; inside ``execute_ms``. It is not the whole of the host's
wait for the device: behind a long slab loop the host first stands in an
enqueue the runtime holds back (``PERF.md`` section 5 says in which span, a
cell), and ``stream.build``'s own wait stays in ``build_ms``. A mean over the
window's queries the server still lists; ``None`` where the program has no
such key."""

from benchmark.counters import per_query


def read(run):
    return per_query(
        run, lambda q: ((q.get("queryStats") or {}).get("phaseMs") or {}).get("devicePull"))
