"""client / protocol (server/http.py: stamped at ``responder.respond``, closed where
the query's next statement request is parsed): milliseconds between the
server handing a response over, the result being ready, and the client asking
for the next (``queryStats.delivery.clientGapMs``): the socket write, the
client reading, parsing and typing the page, its next connection. The time
after the last page is the client's alone and is not in it. Inside
``protocol_ms``. A mean over the window's
queries the server still lists; ``None`` where the program keeps no such
account."""

from benchmark.counters import per_query


def read(run):
    return per_query(
        run, lambda q: ((q.get("queryStats") or {}).get("delivery") or {}).get("clientGapMs"))
