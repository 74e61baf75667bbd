"""ingest: bytes staged host to device per query (``ingestStats.h2d_bytes``);
0 when the device table cache serves the scan."""

from benchmark.counters import per_query


def read(run):
    return per_query(run, lambda q: (q.get("ingestStats") or {}).get("h2d_bytes"))
