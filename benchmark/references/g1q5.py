"""h2oai db-benchmark, groupby task, basic question q5: ``sum(v1), sum(v2), sum(v3)`` by ``id6``
(N/K groups, an integer key with no dictionary), in the order of ``id6``."""

from benchmark.groupby import counts, sums
from benchmark.reference import dec


def answer(tables, params, precision="exact", kept=None):
    x = tables["x"]
    count = counts(x["id6"], int(x["id6"].max()) + 1)
    v1, v2, v3 = (sums(x[c], x["id6"], count, precision) for c in ("v1", "v2", "v3"))
    rows = [(g, v1[g], v2[g], dec(v3[g], 6)) for g in range(len(count)) if count[g]]
    return {"rows": rows, "tie_rows": []}
