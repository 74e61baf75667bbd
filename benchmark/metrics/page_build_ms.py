"""client / protocol (server/http.py::query_results, round ``ResultPager.page``):
milliseconds a query's pages took to cut (``queryStats.delivery.buildMs``):
the pager slicing the rows, sizing each with ``json.dumps`` against the page's
byte budget, and acking the pages below; 0 on the fixed-row path. Inside
``protocol_ms``, after ``queryStats.elapsedMs`` has stopped. A mean over the
window's queries the server still lists; ``None`` where the program keeps no
such account."""

from benchmark.counters import per_query


def read(run):
    return per_query(
        run, lambda q: ((q.get("queryStats") or {}).get("delivery") or {}).get("buildMs"))
