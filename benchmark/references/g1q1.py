"""h2oai db-benchmark, groupby task, basic question q1: ``sum(v1)`` by ``id1`` (K groups), in the
order of ``id1`` (the template's one departure from the source)."""

from benchmark.groupby import counts, sums


def answer(tables, params, precision="exact", kept=None):
    x, label = tables["x"], tables.labels["x"]["id1"]
    count = counts(x["id1"], int(x["id1"].max()) + 1)
    v1 = sums(x["v1"], x["id1"], count, precision)
    # codes ascend as their texts do: idNNN, zero-padded
    rows = [(label[g], v1[g]) for g in range(len(count)) if count[g]]
    return {"rows": rows, "tie_rows": []}
