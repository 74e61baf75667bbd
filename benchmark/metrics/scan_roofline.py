"""kernels / device programs: the least time the chip's memory could take to
read the query's base-table columns once, over the device's busy seconds per
query in the traced slice (busy share of the slice times this run's seconds
per query). The work is the query's, whatever implements it."""

from benchmark.work import mean_scan_bytes


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    if not trace or not peaks or trace["busy_s"] <= 0:
        return None
    busy_per_query = trace["busy_s"] / trace["window_s"] * run["query_s"]
    least = mean_scan_bytes(run) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / busy_per_query
