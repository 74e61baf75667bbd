"""executor, compiled tier (exec/fragments.py::_FragmentTracer._join_strategy):
join sites of a query whose kernel is not sort-merge: the values of
``exchangeStats.joinStrategy`` (a site's restart-stable name to ``sort``,
``dense`` or ``matmul``, as the surviving pass traced it, a stored program's
too) that are not ``sort``. 0 where every join of the plan runs
``ops/join.py``; ``None`` where the program lists no join sites (the default
session, which joins in ``LocalExecutor``)."""

from benchmark.counters import per_query


def sites_off_sort(info):
    sites = (info.get("exchangeStats") or {}).get("joinStrategy")
    if sites is None:
        return None
    return sum(1 for kernel in sites.values() if kernel != "sort")


def read(run):
    return per_query(run, sites_off_sort)
