"""The work a query asks of the chip, computed from the cell's shapes.

The least a scan can move is every value of every base-table column the
query reads, once. The width is that of the configuration (``value_bytes``:
the program holds a column value in one 8-byte lane on the device), the row
counts are the configuration's, the columns are the template's ``reads``.
"""

from __future__ import annotations


def scan_bytes(reads: dict, config: dict) -> int:
    return sum(
        config["rows"][table] * len(columns) * config["value_bytes"]
        for table, columns in reads.items()
    )


def mean_scan_bytes(run: dict) -> float:
    """Bytes per query, averaged over the queries the window sent."""
    mix, config = run["mix"], run["config"]
    per_template = {n: scan_bytes(t.meta["reads"], config) for n, t in mix.templates.items()}
    sent = [per_template[r["template"]] for r in run["records"]]
    return sum(sent) / len(sent)
