"""h2oai db-benchmark, groupby task, basic question q2: ``sum(v1)`` by ``id1, id2`` (K * K groups),
in the order of ``id1, id2`` (the template's one departure from the source)."""

from benchmark.groupby import counts, sums


def answer(tables, params, precision="exact", kept=None):
    x, labels = tables["x"], tables.labels["x"]
    k2 = int(x["id2"].max()) + 1
    pair = x["id1"].astype("int64") * k2 + x["id2"]
    count = counts(pair, (int(x["id1"].max()) + 1) * k2)
    v1 = sums(x["v1"], pair, count, precision)
    rows = [(labels["id1"][g // k2], labels["id2"][g % k2], v1[g])
            for g in range(len(count)) if count[g]]
    return {"rows": rows, "tie_rows": []}
