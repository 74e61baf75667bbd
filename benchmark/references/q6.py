"""TPC-H Q6 (2.4.6): revenue change forecast over one year of lines with
DISCOUNT +- 0.01 and quantity under QUANTITY."""

from benchmark.reference import Arithmetic, add_years, days, dec, scaled


def answer(tables, params, precision="exact", kept=None):
    li = tables["lineitem"]
    ar = Arithmetic(precision)
    lo = days(params["DATE"])
    hi = days(add_years(params["DATE"], 1))
    d = scaled(params["DISCOUNT"], 2)
    keep = (
        (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
        & (li["l_discount"] >= d - 1) & (li["l_discount"] <= d + 1)
        & (li["l_quantity"] < scaled(params["QUANTITY"], 2))
    )
    if not keep.any():
        return {"rows": [(None,)], "tie_rows": []}
    revenue = ar.total(
        ar.values(li["l_extendedprice"][keep]) * ar.values(li["l_discount"][keep])
    )
    return {"rows": [(dec(revenue, 4),)], "tie_rows": []}
