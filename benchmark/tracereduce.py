"""From a profiler trace to busy seconds, idle gaps and the largest operations.

``read`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
lists (plane -> line -> ``(name, start_ns, duration_ns)``) with nothing but
JAX's own ``ProfileData``; ``reduce`` works on those lists alone, so the test
feeds it a small recorded trace (``tests/data/trace_small.json``).

The traced slice is the stretch from the first to the last of the
benchmark's own spans (``bench:*``, written with ``TraceAnnotation`` by the
client streams, so they sit on the profiler's clock). Busy time is the union
of the intervals in which an operation ran on a device, clipped to the slice
and averaged over the devices. Each idle gap of the first device is shared
out over what the client was doing in it: ``submit`` (POST /v1/statement in
flight), ``poll`` (GET nextUri in flight), ``client`` (inside ``execute``
between requests: decoding pages, typing rows) or ``between_queries``. Where
the profiler stopped inside a query, its requests are not in the trace, and
its idle time reads as ``client``.

The operations of a device are listed as the trace nests them: a ``while``
holds the operations of its body, so the largest operations' seconds can add
up to more than the busy time.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: the line of a device plane that holds operations; the others ("Steps", "XLA
#: Modules", ...) hold spans that cover them and would read as always busy
OP_LINE = re.compile(r"^XLA Ops$")
#: the line that holds one span for each launch of a compiled program
PROGRAM_LINE = re.compile(r"^XLA Modules$")
SPAN_PREFIX = "bench:"
INNER_SPANS = ("submit", "poll")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = [
            {"name": line.name,
             "events": [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]}
            for line in plane.lines
        ]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a, b) -> float:
    """Total length of the intersection of two unions of intervals."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def _events(plane: dict, line_name) -> list[tuple[str, float, float]]:
    lines = [ln for ln in plane["lines"] if line_name.match(ln["name"])]
    return [ev for ln in lines for ev in ln["events"] if ev[2] > 0]


def short_name(op: str) -> str:
    """``%fusion.12 = u32[...] fusion(...), kind=kCustom`` -> ``%fusion.12``:
    the trace names an operation by its whole HLO line."""
    return op.split(" = ", 1)[0].strip()[:120]


def reduce(planes: list[dict]) -> dict | None:
    """``None`` where no operation ran on a device inside the slice."""
    spans: dict[str, list[tuple[float, float]]] = {}
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    spans.setdefault(name[len(SPAN_PREFIX):], []).append((start, start + dur))
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    ops = [_events(p, OP_LINE) for p in devices]
    if not any(ops):
        return None
    if spans:
        lo = min(s for iv in spans.values() for s, _ in iv)
        hi = max(e for iv in spans.values() for _, e in iv)
    else:
        lo = min(s for dev in ops for _, s, _ in dev)
        hi = max(s + d for dev in ops for _, s, d in dev)
    busy = [clip(union([(s, s + d) for _, s, d in dev]), lo, hi) for dev in ops]
    busy_ns = sum(length(b) for b in busy) / len(busy)
    if busy_ns <= 0:
        return None

    by_name: dict[str, float] = {}
    for dev in ops:
        for name, start, dur in dev:
            part = min(start + dur, hi) - max(start, lo)
            if part > 0:
                name = short_name(name)
                by_name[name] = by_name.get(name, 0.0) + part / len(ops)
    programs = sum(
        1 for p in devices for _, start, dur in _events(p, PROGRAM_LINE)
        if start + dur > lo and start < hi
    ) / len(devices)

    gaps, at = [], lo
    for s, e in busy[0]:
        if s > at:
            gaps.append((at, s))
        at = e
    if hi > at:
        gaps.append((at, hi))
    # a query in flight when the profiler stopped left its ``begin`` mark but
    # no ``execute`` span: it runs to the end of the slice
    whole = sorted(spans.get("execute", []))
    cut = [(s, hi) for s, _ in spans.get("begin", [])
           if not any(a <= s <= b for a, b in whole)]
    execute = union(whole + cut)
    inner = {k: union(spans.get(k, [])) for k in INNER_SPANS}
    idle: dict[str, float] = {}
    for kind, iv in inner.items():
        idle[kind] = overlap(gaps, iv)
    in_execute = overlap(gaps, execute)
    idle["client"] = max(0.0, in_execute - sum(idle[k] for k in INNER_SPANS))
    idle["between_queries"] = max(0.0, length(gaps) - in_execute)

    def top(d):
        rows = sorted(((k, v / 1e9) for k, v in d.items() if v > 0), key=lambda r: -r[1])
        return [[k, v] for k, v in rows[:10]]

    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": len(devices),
        "queries": len(spans.get("execute", [])),
        "programs": programs,
        "longest_gap_s": max((e - s for s, e in gaps), default=0.0) / 1e9,
        "breakdown": {"device_ops": top(by_name), "idle_gaps": top(idle)},
    }
