"""What decides ``correct``: every answer of the window against the reference.

Four numbers are compared, each with the limit 0 of an exact comparison (the
configurations state exact DECIMAL arithmetic, the query's ORDER BY and every
row delivered; ``PERF.md`` section 2 has the readings they were set from):

- ``answers_missing``: executions of the window that raised or never answered;
- ``answers_wrong``: answers with any value, row count or row order other than
  the reference's;
- ``values_wrong``: values that differ, over all those answers;
- ``widest_gap``: the widest relative gap of a numeric value from the
  reference's, which tells a rounding path (the float32 control reads 1e-7 to
  1e-3) from a wrong join or filter (which reads near 1).

Row order is held to the reference's wherever the query's ORDER BY decides it.
Rows that tie on the ORDER BY columns (``sort_key`` of the template) may come
in any order, and where such a tie straddles a LIMIT any of the tied rows may
fill it: SQL leaves both open.
"""

from __future__ import annotations

from decimal import Decimal

LIMITS = {
    "answers_missing": 0,
    "answers_wrong": 0,
    "values_wrong": 0,
    "widest_gap": 0.0,
}


def _number(v):
    return isinstance(v, (int, float, Decimal)) and not isinstance(v, bool)


def value_gap(got, want) -> float:
    """0.0 where equal; the relative gap of two numbers; 1.0 otherwise."""
    if got is None or want is None:
        return 0.0 if got is want else 1.0
    if _number(got) and _number(want):
        g, w = Decimal(str(got)), Decimal(str(want))
        if g == w:
            return 0.0
        return float(abs(g - w) / max(abs(w), abs(g)))
    return 0.0 if got == want else 1.0


def _row_gaps(got, want) -> list[float]:
    if len(got) != len(want):
        return [1.0] * max(len(got), len(want))
    return [value_gap(g, w) for g, w in zip(got, want)]


def compare_answer(got_rows, ref: dict, sort_key) -> tuple[int, float]:
    """(values that differ, widest gap) of one answer against the reference's
    ``{"rows": [...], "tie_rows": [...]}``."""
    want = ref["rows"]
    pool = list(want) + list(ref["tie_rows"])
    width = len(want[0]) if want else (len(got_rows[0]) if got_rows else 1)
    wrong = abs(len(got_rows) - len(want)) * width
    widest = 1.0 if wrong else 0.0

    def key(row):
        return tuple(row[i] for i in sort_key)

    taken = set()
    for got, exp in zip(got_rows, want):
        got = tuple(got)
        gaps = _row_gaps(got, exp)
        if any(gaps) and sort_key and len(got) == len(exp):
            # another row of the same ORDER BY key may stand here instead
            for other in pool:
                if key(other) == key(exp) and not any(_row_gaps(got, other)):
                    gaps = [0.0] * len(got)
                    break
        if not any(gaps) and got in taken:
            gaps = [1.0] * len(got)  # one row delivered twice
        taken.add(got)
        wrong += sum(1 for g in gaps if g)
        widest = max([widest] + gaps)
    return wrong, widest


def decide(executions, answers, sort_keys) -> dict:
    """``executions``: one ``{"template", "params", "rows" or "error"}`` per
    query of the window. ``answers(template, params)`` is the reference (or,
    in the control's place, what stands in for it)."""
    numbers = dict.fromkeys(LIMITS, 0)
    numbers["widest_gap"] = 0.0
    for ex in executions:
        if ex.get("rows") is None:
            numbers["answers_missing"] += 1
            continue
        ref = answers(ex["template"], ex["params"])
        wrong, widest = compare_answer(ex["rows"], ref, sort_keys[ex["template"]])
        numbers["values_wrong"] += wrong
        numbers["answers_wrong"] += 1 if wrong else 0
        numbers["widest_gap"] = max(numbers["widest_gap"], widest)
    compared = {
        name: {"value": numbers[name], "limit": LIMITS[name]} for name in LIMITS
    }
    correct = bool(executions) and all(
        c["value"] <= c["limit"] for c in compared.values()
    )
    return {"correct": correct, "compared": compared}
