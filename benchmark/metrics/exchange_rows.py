"""collectives (exec/fragments.py::apply_output_exchange over
parallel/exchange.py): rows a query's hash exchanges sent between the mesh's
devices (``exchangeStats.shuffle_rows``: the ``sent`` counters of the
surviving pass); 0 on one device, where the exchange is the identity."""

from benchmark.counters import per_query


def read(run):
    return per_query(run, lambda q: (q.get("exchangeStats") or {}).get("shuffle_rows"))
