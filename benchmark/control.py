"""The control of ``correct``: the reference in float32, in the program's place.

    python3 benchmark/control.py --workload <cell> --seed <n> --queries <k>

Takes the first ``k`` executions the cell's streams would send under the
seed, answers each with ``reference.Reference(columns, "float32")`` where
a run has the program's answer, and prints what ``compare.decide`` reads
against the exact reference. It breaks the guarantee of exact DECIMAL
arithmetic, so ``correct`` has to come out false. The benchmark's own runs
never call this; ``PERF.md`` section 2 has its readings at the cells' size.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, harness, reference  # noqa: E402


def control_verdict(root: str, workload: str, seed: int, queries: int, cache: bool = False) -> dict:
    _, data_root, _, config, mix = harness.load_cell(root, workload)
    columns = harness.reference_columns(root, data_root, config, mix, cache)
    refs = {p: reference.Reference(columns, p, data_root) for p in ("exact", "float32")}

    def answer(name, params, precision):
        return refs[precision].answer(mix.templates[name].meta["reference"], params)

    executions = []
    for i in range(len(mix.streams)):
        for name, params in itertools.islice(mix.schedule(seed, i), queries):
            rows = answer(name, params, "float32")["rows"]
            executions.append({"template": name, "params": params, "rows": rows})
    sort_keys = {n: t.meta["sort_key"] for n, t in mix.templates.items()}
    verdict = compare.decide(executions, lambda n, p: answer(n, p, "exact"), sort_keys)
    verdict["answers"] = len(executions)
    return verdict


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", type=int, default=100)
    args = ap.parse_args(argv)
    verdict = control_verdict(ROOT, args.workload, args.seed, args.queries, cache=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed, **verdict}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
