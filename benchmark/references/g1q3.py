"""h2oai db-benchmark, groupby task, basic question q3: ``sum(v1), avg(v3)`` by ``id3`` (N/K groups),
in the order of ``id3``. ``v3`` is DECIMAL(9,6), so its average is one too:
the exact sum over the count, rounded half up at scale 6, as Trino rounds
``avg(DECIMAL)``."""

from benchmark.groupby import counts, sums
from benchmark.reference import dec, div_half_up


def answer(tables, params, precision="exact", kept=None):
    x, label = tables["x"], tables.labels["x"]["id3"]
    count = counts(x["id3"], int(x["id3"].max()) + 1)
    v1 = sums(x["v1"], x["id3"], count, precision)
    v3 = sums(x["v3"], x["id3"], count, precision)
    rows = [(label[g], v1[g], dec(div_half_up(v3[g], n), 6))
            for g, n in enumerate(count.tolist()) if n]
    return {"rows": rows, "tie_rows": []}
