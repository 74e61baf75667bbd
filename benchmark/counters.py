"""Shared by the readers of per-query counters of ``GET /v1/query``."""


def per_query(run, pick):
    """Mean of ``pick(info)`` over the window's queries the server still
    lists; ``None`` where none of them carries the counter."""
    values = [pick(q) for q in run["infos"] if q["state"] == "FINISHED"]
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None
