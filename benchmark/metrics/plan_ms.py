"""parse / analyse / plan (engine.py: spans ``parse``, ``plan``, ``optimize``,
``canonicalize``): milliseconds from the SQL text to the plan the executor is
given (``queryStats.phaseMs``), a mean over the window's queries the server
still lists. Nothing to read where the program has no such spans."""

from benchmark.counters import per_query

PHASES = ("parse", "plan", "optimize", "canonicalize")


def read(run):
    def pick(q):
        phases = (q.get("queryStats") or {}).get("phaseMs")
        if not phases or any(p not in phases for p in PHASES):
            return None
        return sum(phases[p] for p in PHASES)

    return per_query(run, pick)
