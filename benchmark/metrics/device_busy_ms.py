"""kernels / device programs: milliseconds per query in which an operation
ran on the device (busy share of the traced slice times this run's seconds
per query); what a faster kernel shortens, whatever the host does between."""


def read(run):
    trace = run["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    return 1000.0 * trace["busy_s"] / trace["window_s"] * run["query_s"]
