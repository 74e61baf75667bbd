"""client / protocol (server/http.py::_statement_poll, from ``ResultPager.page``'s
return to ``responder.respond``): milliseconds a query's pages took to encode
(``queryStats.delivery.encodeMs``): the ``_json_value`` pass over the rows and
``json.dumps`` of each response body. Inside ``protocol_ms``. A mean over the
window's queries the server still lists; ``None`` where the program keeps no
such account."""

from benchmark.counters import per_query


def read(run):
    return per_query(
        run, lambda q: ((q.get("queryStats") or {}).get("delivery") or {}).get("encodeMs"))
