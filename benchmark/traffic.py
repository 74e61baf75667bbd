"""The one traffic generator: a mix is a data file, this reads it.

``traffic/<mix>.json`` states the loop (``closed``: each stream sends its next
query when the last one has answered, after ``think_s``), the streams, each
with the templates it cycles through in order, and for each template the
values its substitution parameters take (TPC-H clause 2.4.x.3). A template is
``templates/<t>.sql``, with ``{SCHEMA}`` and ``{PARAMETER}`` fields, beside
``templates/<t>.json``, which names its reference function, its validation
parameters and the columns it reads.

A parameter's rule is one of ``{"values": [...]}``, ``{"range": [lo, hi]}``
(whole numbers, both ends in, optional ``"step"``) and ``{"dates": [first,
last]}`` (every day, both ends in). Every execution draws uniformly as qgen
does, but without replacement: a stream walks a shuffle of the template's
whole grid of parameter values and then a fresh shuffle, so every seed sends
the same set of queries in another order and no seed changes the work.
Shuffles come from ``random.Random`` seeded with a string of the run's seed,
the stream and the template: any whole number is a seed.
"""

from __future__ import annotations

import datetime
import itertools
import json
import os
import random
from typing import Iterator


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def values_of(rule: dict) -> list:
    if "values" in rule:
        return list(rule["values"])
    if "range" in rule:
        lo, hi = rule["range"]
        return list(range(int(lo), int(hi) + 1, int(rule.get("step", 1))))
    if "dates" in rule:
        first, last = (datetime.date.fromisoformat(d) for d in rule["dates"])
        return [
            (first + datetime.timedelta(days=i)).isoformat()
            for i in range((last - first).days + 1)
        ]
    raise ValueError(f"a parameter rule needs values, range or dates: {rule!r}")


class Template:
    def __init__(self, data_root: str, name: str):
        base = os.path.join(data_root, "templates", name)
        with open(base + ".sql", encoding="utf-8") as f:
            self.text = f.read()
        self.meta = load_json(base + ".json")

    def sql(self, schema: str, params: dict) -> str:
        return self.text.format(SCHEMA=schema, **params).strip()


class Mix:
    def __init__(self, data_root: str, name: str):
        self.spec = load_json(os.path.join(data_root, "traffic", name + ".json"))
        if self.spec.get("loop") != "closed":
            raise ValueError(f"mix {name}: only closed loops are generated")
        self.think_s = float(self.spec.get("think_s", 0))
        self.streams = [list(s["templates"]) for s in self.spec["streams"]]
        names = sorted({t for s in self.streams for t in s})
        self.templates = {t: Template(data_root, t) for t in names}
        self.grids = {}
        for t in names:
            rules = self.spec["parameters"][t]
            keys = sorted(rules)
            self.grids[t] = [
                dict(zip(keys, combo))
                for combo in itertools.product(*(values_of(rules[k]) for k in keys))
            ]

    def _draws(self, seed: int, stream: int, template: str) -> Iterator[dict]:
        for cycle in itertools.count():
            grid = list(self.grids[template])
            random.Random(f"{seed}/{stream}/{template}/{cycle}").shuffle(grid)
            yield from grid

    def schedule(self, seed: int, stream: int) -> Iterator[tuple[str, dict]]:
        """The endless sequence of (template, parameters) of one stream."""
        order = self.streams[stream]
        draws = {t: self._draws(seed, stream, t) for t in set(order)}
        for t in itertools.cycle(order):
            yield t, next(draws[t])
