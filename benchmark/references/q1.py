"""TPC-H Q1 (2.4.1): pricing summary of lines shipped by 1998-12-01 less
DELTA days, by return flag and line status, in that order.

DELTA only moves the last ship day that counts, so the exact sums are made
once for a set of tables, by group and ship day, and added up through the
days (Python integers): an answer is then a lookup, and a window's 61
answers cost what one did. The float32 control sums the kept lines directly,
answer by answer, as a float32 engine would; ``direct`` with exact
arithmetic is what the tests hold the sums by day to."""

import numpy as np

from benchmark.reference import Arithmetic, days, dec, div_half_up


def _q1_values(li, ar: Arithmetic) -> dict:
    """What Q1 sums, for every line: no parameter changes these."""
    price = ar.values(li["l_extendedprice"])
    disc = ar.values(li["l_discount"])
    disc_price = price * (100 - disc)  # scale 4
    return {
        "qty": ar.values(li["l_quantity"]), "price": price, "disc": disc,
        "disc_price": disc_price,
        "charge": disc_price * (100 + ar.values(li["l_tax"])),  # scale 6
    }


def _group(li):
    return li["l_returnflag"].astype(np.int64) * 2 + li["l_linestatus"]


def direct(li, ar: Arithmetic, n_groups: int, last_day: int, kept: dict):
    """(lines, {name: sums}) by group, over the lines shipped by ``last_day``."""
    keep = li["l_shipdate"] <= last_day
    # a line the filter drops goes to a group of its own, past the real ones
    group = np.where(keep, _group(li), n_groups)
    if "q1" not in kept:
        kept["q1"] = _q1_values(li, ar)
    count = np.bincount(group, minlength=n_groups + 1)
    return count, {name: ar.grouped(v, group, n_groups + 1) for name, v in kept["q1"].items()}


def through_day(li, ar: Arithmetic, n_groups: int, last_day: int, kept: dict):
    """The same, exact, from running sums by group through the ship days."""
    if "q1.through_day" not in kept:
        ship = li["l_shipdate"].astype(np.int64)
        n_days = int(ship.max()) + 1
        cell = _group(li) * n_days + ship

        def running(by_cell):
            return np.cumsum(np.array(by_cell, dtype=object).reshape(n_groups, n_days), axis=1)

        sums = {name: running(ar.grouped(v, cell, n_groups * n_days))
                for name, v in _q1_values(li, ar).items()}
        kept["q1.through_day"] = (
            n_days, running(np.bincount(cell, minlength=n_groups * n_days).tolist()), sums)
    n_days, count, sums = kept["q1.through_day"]
    if last_day < 0:
        return [0] * n_groups, {name: [0] * n_groups for name in sums}
    day = min(last_day, n_days - 1)
    return count[:, day], {name: s[:, day] for name, s in sums.items()}


def answer(tables, params, precision="exact", kept=None):
    li = tables["lineitem"]
    ar = Arithmetic(precision)
    returnflag = tables.labels["lineitem"]["l_returnflag"]
    linestatus = tables.labels["lineitem"]["l_linestatus"]
    n_groups = len(returnflag) * len(linestatus)
    last_day = days("1998-12-01") - int(params["DELTA"])
    kept = {} if kept is None else kept
    count, sums = (through_day if ar.exact else direct)(li, ar, n_groups, last_day, kept)
    rows = []
    for g in range(n_groups):
        n = int(count[g])
        if n == 0:
            continue
        rows.append((
            returnflag[g // 2], linestatus[g % 2],
            dec(sums["qty"][g], 2), dec(sums["price"][g], 2),
            dec(sums["disc_price"][g], 4), dec(sums["charge"][g], 6),
            dec(div_half_up(sums["qty"][g], n), 2),
            dec(div_half_up(sums["price"][g], n), 2),
            dec(div_half_up(sums["disc"][g], n), 2),
            n,
        ))
    rows.sort(key=lambda r: (r[0], r[1]))
    return {"rows": rows, "tie_rows": []}
