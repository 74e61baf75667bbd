"""client / protocol: the client's wall time less the server's own
``queryStats.elapsedMs``, a mean over the window's queries that the server
still lists when the window has closed (its history keeps the last 100)."""


def read(run):
    by_sql = {}
    for r in sorted(run["records"], key=lambda r: r["start"]):
        by_sql.setdefault(r["sql"], []).append(r)
    gaps = []
    for info in sorted(run["infos"], key=lambda q: q["createTime"]):
        waiting = by_sql.get(info["query"])
        if not waiting or info["state"] != "FINISHED":
            continue
        rec = waiting.pop(0)
        gaps.append((rec["end"] - rec["start"]) * 1000.0 - info["queryStats"]["elapsedMs"])
    return sum(gaps) / len(gaps) if gaps else None
