"""executor: compiled programs launched on the device per query, counted in
the trace (one span on the device's "XLA Modules" line for each launch); the
operator-at-a-time executor launches one or more for each operator."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["programs"]:
        return None
    return trace["programs"] / trace["window_s"] * run["query_s"]
