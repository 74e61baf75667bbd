"""fragment programs: compiled programs dispatched per query
(``exchangeStats.dispatchRoundTrips``); only the compiled tier counts them."""

from benchmark.counters import per_query


def read(run):
    return per_query(run, lambda q: (q.get("exchangeStats") or {}).get("dispatchRoundTrips"))
