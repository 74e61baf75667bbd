"""h2oai db-benchmark, groupby task, basic question q4: ``avg(v1), avg(v2), avg(v3)`` by ``id4`` (K
groups), in the order of ``id4``. ``avg(BIGINT)`` is a DOUBLE in Trino: the
exact sum over the count, rounded once to the nearest double (Python's true
division of integers). ``avg(v3)`` stays DECIMAL(9,6), rounded half up."""

from benchmark.groupby import counts, sums
from benchmark.reference import dec, div_half_up


def answer(tables, params, precision="exact", kept=None):
    x = tables["x"]
    count = counts(x["id4"], int(x["id4"].max()) + 1)
    v1, v2, v3 = (sums(x[c], x["id4"], count, precision) for c in ("v1", "v2", "v3"))
    rows = [(g, v1[g] / n, v2[g] / n, dec(div_half_up(v3[g], n), 6))
            for g, n in enumerate(count.tolist()) if n]
    return {"rows": rows, "tie_rows": []}
