"""client / protocol (server/http.py::query_results, after ``ResultPager.page``):
responses a query's answer took that carried ``data``
(``queryStats.delivery.pages``; a token asked for again counts once): each is
one HTTP round trip of the client. A mean over the window's
queries the server still lists; ``None`` where the program keeps no such
account."""

from benchmark.counters import per_query


def read(run):
    return per_query(
        run, lambda q: ((q.get("queryStats") or {}).get("delivery") or {}).get("pages"))
