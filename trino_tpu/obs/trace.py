"""Lightweight structured span tracer.

Spans model the life of a query: the trace id IS the query id, the
root span is the query itself, and children cover planning,
optimization, fragmentation, per-stage scheduling, per-task attempts,
exchange transfers, program trace/compile, and device→host pulls.

Design constraints (per the hot-path rule in the issue):

- **No-op when dark.** ``Tracer.start_span`` returns a shared
  ``_NoopSpan`` singleton when no sink is registered — zero
  allocations, no clock reads, nothing to garbage-collect. Servers
  register an :class:`InMemorySpanSink`; a bare engine run traces
  nothing.
- **One clock pair.** A span keeps ``time.monotonic_ns()`` stamps
  (``startNs``/``endNs`` in ``to_json``, unrounded; the epoch is kept only
  for display), so :func:`self_times` needs the timeline alone.
- **On the profiler's clock.** A span entered and left on one thread
  (``with``) is also a ``jax.profiler.TraceAnnotation`` named
  ``trino:<name>``: a flag test while no profiler session runs, an event
  beside the device's operations while one does. A span opened with
  ``start_span()`` and finished elsewhere is not bridged.
- **Compilations are counted where they happen.** One ``jax.monitoring``
  listener adds every backend compilation (and persistent-cache load) to
  the span current on the compiling thread: ``xlaCompiles``,
  ``xlaCompileMs``, ``xlaCacheLoads``; :func:`compile_counts` sums a trace.
- **Threads don't inherit context.** The ambient "current span" lives
  in a ``threading.local`` stack, so spans started on the same thread
  nest automatically, but work handed to another thread (query
  dispatch, exchange pulls) or another process (worker tasks over
  HTTP) must carry an explicit ``(trace_id, parent_span_id)`` pair —
  see :func:`format_trace_header` / :func:`parse_trace_header` for the
  ``X-Trino-Trace`` wire form.
"""

from __future__ import annotations

import itertools
import threading
import time
import uuid
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from jax import monitoring as _monitoring
from jax.profiler import TraceAnnotation

TRACE_HEADER = "X-Trino-Trace"
#: prefix of the spans' names in a profiler trace (``benchmark/tracereduce.py``
#: gathers ``bench:`` names only, so it reads what it read before)
ANNOTATION_PREFIX = "trino:"
# jax.monitoring duration events: the first fires once per executable built
# (eager primitive or jit, compiled or loaded from the persistent cache),
# the second only for a load; neither fires on a warm call
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_ids = itertools.count(1)
# span ids must stay unique across the whole cluster: a timeline is the
# UNION of every node's span dump for one trace, and coordinator and
# worker processes each count from 1
_PROC = uuid.uuid4().hex[:6]


def _next_id(prefix: str) -> str:
    return f"{prefix}{_PROC}-{next(_ids)}"


@dataclass
class Span:
    """One timed unit of work inside a trace."""

    trace_id: str
    span_id: str
    parent_id: Optional[str]
    name: str
    start_epoch: float
    attrs: Dict[str, Any] = field(default_factory=dict)
    duration_ms: Optional[float] = None
    status: str = "OK"
    start_ns: int = 0  # time.monotonic_ns()
    end_ns: Optional[int] = None
    _tracer: Optional["Tracer"] = None
    _done: bool = False
    _annotation: Any = None

    def set(self, key: str, value: Any) -> None:
        self.attrs[key] = value

    def add(self, key: str, amount: float = 1) -> None:
        """Count into an attribute (``attempts``, ``xlaCompiles``)."""
        self.attrs[key] = self.attrs.get(key, 0) + amount

    def finish(self, status: str = "OK", **attrs: Any) -> None:
        """Close the span and hand it to the sinks. Idempotent."""
        if self._done:
            return
        self._done = True
        if self.end_ns is None:
            self.end_ns = time.monotonic_ns()
        if self.duration_ms is None:
            self.duration_ms = (self.end_ns - self.start_ns) / 1e6
        self.status = status
        if attrs:
            self.attrs.update(attrs)
        if self._tracer is not None:
            self._tracer._record(self)

    def drop(self) -> None:
        """Close the span and keep it from the sinks: what it was opened
        round turned out not to be its kind of work."""
        self._done = True

    def context(self) -> Tuple[str, str]:
        return (self.trace_id, self.span_id)

    def to_json(self) -> Dict[str, Any]:
        return {
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "name": self.name,
            "startMs": round(self.start_epoch * 1000.0, 1),
            "startNs": self.start_ns,
            "endNs": self.end_ns,
            "durationMs": round(self.duration_ms, 3)
            if self.duration_ms is not None
            else None,
            "status": self.status,
            "attrs": self.attrs,
        }

    # context-manager form: ``with tracer.span("plan"): ...``
    def __enter__(self) -> "Span":
        if self._tracer is not None:
            self._tracer._push(self)
        self._annotation = TraceAnnotation(ANNOTATION_PREFIX + self.name)
        self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._annotation is not None:
            # stamped first: a thread's first event takes the profiler
            # a while to close, after the event's own end
            if not self._done:
                self.end_ns = time.monotonic_ns()
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if self._tracer is not None:
            self._tracer._pop(self)
        if exc is not None and not self._done:
            self.finish(status="ERROR", error=f"{type(exc).__name__}: {exc}")
        else:
            self.finish()
        return False


class _NoopSpan:
    """Shared do-nothing span returned when no sink is registered."""

    __slots__ = ()
    trace_id = None
    span_id = None
    parent_id = None

    def set(self, key: str, value: Any) -> None:
        pass

    def add(self, key: str, amount: float = 1) -> None:
        pass

    def finish(self, status: str = "OK", **attrs: Any) -> None:
        pass

    def drop(self) -> None:
        pass

    def context(self) -> None:
        return None

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


NOOP_SPAN = _NoopSpan()


class Tracer:
    """Process-global span factory fanning finished spans out to sinks."""

    def __init__(self) -> None:
        self._sinks: List[Any] = []
        self._tls = threading.local()
        self._lock = threading.Lock()

    # -- sink management ------------------------------------------------
    @property
    def enabled(self) -> bool:
        return bool(self._sinks)

    def add_sink(self, sink: Any) -> None:
        with self._lock:
            if sink not in self._sinks:
                self._sinks.append(sink)

    def remove_sink(self, sink: Any) -> None:
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)

    # -- ambient current-span stack (per thread) ------------------------
    def _stack(self) -> List[Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current(self) -> Optional[Span]:
        st = getattr(self._tls, "stack", None)
        return st[-1] if st else None

    def context(self) -> Optional[Tuple[str, str]]:
        """(trace_id, span_id) of the ambient span, for cross-thread/HTTP
        handoff; None when dark or outside any span."""
        cur = self.current()
        return cur.context() if cur is not None else None

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()
        elif span in st:  # defensive: unbalanced exit
            st.remove(span)

    # -- span creation --------------------------------------------------
    def start_span(
        self,
        name: str,
        trace_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ):
        """Create a live span. Parentage: explicit ``parent_id`` wins,
        else the ambient current span on this thread, else root."""
        if not self._sinks:
            return NOOP_SPAN
        if parent_id is None:
            cur = self.current()
            if cur is not None:
                parent_id = cur.span_id
                if trace_id is None:
                    trace_id = cur.trace_id
        if trace_id is None:
            trace_id = _next_id("t")
        return Span(
            trace_id=trace_id,
            span_id=_next_id("s"),
            parent_id=parent_id,
            name=name,
            start_epoch=time.time(),
            attrs=dict(attrs) if attrs else {},
            start_ns=time.monotonic_ns(),
            _tracer=self,
        )

    def span(
        self,
        name: str,
        trace_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ):
        """``with tracer.span("optimize"): ...`` — starts, activates as
        the ambient span, and finishes on exit (ERROR on exception)."""
        return self.start_span(name, trace_id, parent_id, attrs)

    def activate(self, span):
        """Re-enter an existing span as the ambient span on THIS thread
        (e.g. the per-query dispatch thread adopting the root span that
        the HTTP handler thread created). Does not finish it on exit."""
        return _Activation(self, span)

    def record(
        self,
        name: str,
        duration_ms: float,
        attrs: Optional[Dict[str, Any]] = None,
        trace_id: Optional[str] = None,
        parent_id: Optional[str] = None,
        status: str = "OK",
    ) -> None:
        """Emit an already-measured span retroactively (e.g. compile time
        known only after the fact). No-op when dark."""
        if not self._sinks:
            return
        if parent_id is None:
            cur = self.current()
            if cur is not None:
                parent_id = cur.span_id
                if trace_id is None:
                    trace_id = cur.trace_id
        if trace_id is None:
            trace_id = _next_id("t")
        end_ns = time.monotonic_ns()
        span = Span(
            trace_id=trace_id,
            span_id=_next_id("s"),
            parent_id=parent_id,
            name=name,
            start_epoch=time.time() - duration_ms / 1000.0,
            attrs=dict(attrs) if attrs else {},
            duration_ms=duration_ms,
            start_ns=end_ns - int(duration_ms * 1e6),
            end_ns=end_ns,
            _tracer=self,
        )
        span._done = True
        span.status = status
        self._record(span)

    def _record(self, span: Span) -> None:
        for sink in list(self._sinks):
            try:
                sink.record(span)
            except Exception:  # noqa: BLE001 — observability must not fail queries
                pass


class _Activation:
    __slots__ = ("_tracer", "_span", "_annotation")

    def __init__(self, tracer: Tracer, span) -> None:
        self._tracer = tracer
        self._span = span
        self._annotation = None

    def __enter__(self):
        if isinstance(self._span, Span):
            self._tracer._push(self._span)
            # the adopting thread's share of the span, on the profiler's clock
            self._annotation = TraceAnnotation(
                ANNOTATION_PREFIX + self._span.name
            )
            self._annotation.__enter__()
        return self._span

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if isinstance(self._span, Span):
            self._tracer._pop(self._span)
        return False


class InMemorySpanSink:
    """Bounded per-trace span store backing ``/v1/query/{id}/timeline``."""

    def __init__(self, max_traces: int = 256, max_spans_per_trace: int = 4096):
        self.max_traces = max_traces
        self.max_spans_per_trace = max_spans_per_trace
        self._traces: "OrderedDict[str, List[Dict[str, Any]]]" = OrderedDict()
        self._lock = threading.Lock()

    def record(self, span: Span) -> None:
        with self._lock:
            spans = self._traces.get(span.trace_id)
            if spans is None:
                spans = self._traces[span.trace_id] = []
                while len(self._traces) > self.max_traces:
                    self._traces.popitem(last=False)
            if len(spans) < self.max_spans_per_trace:
                spans.append(span.to_json())

    def spans_for(self, trace_id: str) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._traces.get(trace_id, ()))

    def trace_ids(self) -> List[str]:
        with self._lock:
            return list(self._traces)

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()


# -- cross-process propagation (X-Trino-Trace header) -------------------

def format_trace_header(ctx: Optional[Tuple[str, str]]) -> Optional[str]:
    """``(trace_id, span_id)`` → ``"{trace_id};{span_id}"``."""
    if not ctx or not ctx[0]:
        return None
    return f"{ctx[0]};{ctx[1]}"


def parse_trace_header(value: Optional[str]) -> Optional[Tuple[str, str]]:
    if not value or ";" not in value:
        return None
    trace_id, _, span_id = value.partition(";")
    if not trace_id or not span_id:
        return None
    return (trace_id.strip(), span_id.strip())


# -- the timeline reduced (choosing-metrics §4: self time) ---------------

def self_times(
    spans: Iterable[Dict[str, Any]],
    keep: Optional[Callable[[str], bool]] = None,
) -> Dict[str, float]:
    """``spanId`` -> self milliseconds for ``to_json`` spans of one trace:
    a span's duration less the union of its children's intervals (clipped
    to its own), whatever thread a child ran on. Spans without both clock
    stamps are left out.

    With ``keep`` (a predicate on the name) the other spans are elided:
    their time stays with the nearest kept ancestor, and their kept
    descendants count as that ancestor's children."""
    spans = [
        s for s in spans
        if s.get("startNs") is not None and s.get("endNs") is not None
    ]
    by_id = {s["spanId"]: s for s in spans}
    kept = [s for s in spans if keep is None or keep(s["name"])]
    kept_ids = {s["spanId"] for s in kept}
    children: Dict[str, List[Tuple[int, int]]] = {}
    for s in kept:
        parent = s.get("parentId")
        while parent in by_id and parent not in kept_ids:
            parent = by_id[parent].get("parentId")
        if parent in by_id:
            children.setdefault(parent, []).append((s["startNs"], s["endNs"]))
    out: Dict[str, float] = {}
    for s in kept:
        lo, hi = s["startNs"], s["endNs"]
        covered, at = 0, lo
        for a, b in sorted(children.get(s["spanId"], ())):
            a, b = max(a, at), min(b, hi)
            if b > a:
                covered += b - a
                at = b
        out[s["spanId"]] = (hi - lo - covered) / 1e6
    return out


#: span name -> the ``phaseMs`` key that sums its durations. ``build`` (a
#: streamed aggregate's build sides made ready, with the wait for the
#: fragments that compute them) and ``slab`` (the compiled tier's streamed
#: slab program, lookup to result) lie inside ``execute``, one after the
#: other; the others follow one another
PHASE_OF_SPAN = {
    "parse": "parse", "plan": "plan", "optimize": "optimize",
    "canonicalize": "canonicalize", "execute_plan": "execute",
    "result.pull": "resultPull", "stream.build": "build",
    "stream.slab": "slab", "device_pull": "devicePull",
}
OPERATOR_PREFIX = "op:"


def query_phases(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """One query's timeline as ``queryStats`` serves it: ``phaseMs`` (span
    durations by phase), ``operatorMs`` (self times of the ``op:`` spans,
    summed by node type: host wall in the operator, waits on the device and
    the ingest spans below a scan included) and the compile counts."""
    spans = list(spans)
    phases = dict.fromkeys(PHASE_OF_SPAN.values(), 0.0)
    for s in spans:
        key = PHASE_OF_SPAN.get(s["name"])
        if key is not None and s.get("durationMs") is not None:
            phases[key] += s["durationMs"]
    operators: Dict[str, float] = {}
    own = self_times(spans, keep=lambda n: n.startswith(OPERATOR_PREFIX))
    for s in spans:
        if s["spanId"] in own:
            kind = s["name"][len(OPERATOR_PREFIX):]
            operators[kind] = operators.get(kind, 0.0) + own[s["spanId"]]
    return {
        "phaseMs": {k: round(v, 3) for k, v in phases.items()},
        "operatorMs": {k: round(v, 3) for k, v in operators.items()},
        **compile_counts(spans),
        **aggregate_counts(spans),
    }


def aggregate_counts(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """What the capacity ladder of the group-bys cost a query, and what the
    query delivered. ``aggAttempts``: runs of its grouped aggregates, the
    surviving ones included (the default session counts each ``op:Aggregate``
    up its own ladder; the compiled session each pass over the fragments,
    which runs every aggregate of the plan once): 1 an aggregate or a pass
    where every budget held. ``groupBudgetGrowths``: budgets that were
    outgrown and grown. ``resultRows``: rows ``result.pull`` typed for the
    client. ``slabSteps``: steps of the slab loops whose answer the query
    kept, each streamed aggregate's last ``stream.slab`` span (the passes
    before it outgrew a budget, the attempts before it a width the compiler
    refused). ``joinOutSlots``: over the same spans, each join's ``outCap``
    of the slab step times the span's ``steps``: the width the probe spine
    carries through the loop. ``lookupJoins``: over the same spans, the
    joins with ``lookup`` true, which ran as a lookup of a unique build key
    with no probe column gathered. ``fragmentLookupJoins``: the same of the
    joins of the fragment programs of the surviving pass, each stage's last
    ``fragment_execute`` or ``fused_execute`` span, a build side made before
    the program (``FragmentedExecutor._unique_builds``). ``buildRows``: live
    rows of the build sides each streamed aggregate made last
    (``stream.build``'s ``rows``).
    ``meshDevices``: devices of the mesh the compiled session ran the plan
    on (``execute_plan``'s attribute). The first two are absent where no
    grouped aggregate ran, ``slabSteps`` where nothing streamed through a
    slab program, ``joinOutSlots`` and ``lookupJoins`` where no such loop
    joined (``lookupJoins`` too where its joins carry no ``lookup``),
    ``fragmentLookupJoins`` where no fragment program ran, ``buildRows``
    where nothing streamed past a build side, ``meshDevices`` in the
    default session."""
    attempts = growths = rows = devices = 0
    # site -> (start, steps, output slots of a step, lookup joins), the
    # latest loop
    last: Dict[Any, Tuple[int, int, Optional[int], Optional[int]]] = {}
    built: Dict[Any, Tuple[int, int]] = {}  # site -> (start, rows), the latest
    # (span, stage) -> (start, its joins), the latest
    staged: Dict[Any, Tuple[int, Any]] = {}
    for s in spans:
        attrs = s.get("attrs") or {}
        attempts += attrs.get("aggAttempts", 0)
        growths += attrs.get("groupBudgetGrowths", 0)
        devices = max(devices, attrs.get("meshDevices", 0))
        start, site = s.get("startNs") or 0, attrs.get("site")
        if s["name"] == "result.pull":
            rows += attrs.get("rows", 0)
        elif s["name"] == "stream.slab" and "steps" in attrs:
            joins = attrs.get("joins") or ()
            slots, lookups = (
                sum(j[key] for j in joins)
                if joins and all(key in j for j in joins) else None
                for key in ("outCap", "lookup")
            )
            if site not in last or start >= last[site][0]:
                last[site] = (start, attrs["steps"], slots, lookups)
        elif s["name"] == "stream.build" and "rows" in attrs:
            if site not in built or start >= built[site][0]:
                built[site] = (start, attrs["rows"])
        elif s["name"] in ("fragment_execute", "fused_execute"):
            stage = (s["name"], attrs.get("stage"))
            if stage not in staged or start >= staged[stage][0]:
                staged[stage] = (start, attrs.get("joins"))
    out: Dict[str, Any] = {"resultRows": rows}
    if attempts:
        out.update(aggAttempts=attempts, groupBudgetGrowths=growths)
    if last:
        out["slabSteps"] = sum(steps for _, steps, _, _ in last.values())
        joined = [steps * slots for _, steps, slots, _ in last.values() if slots]
        if joined:
            out["joinOutSlots"] = sum(joined)
        looked = [n for _, _, _, n in last.values() if n is not None]
        if looked:
            out["lookupJoins"] = sum(looked)
    if staged:
        out["fragmentLookupJoins"] = sum(
            bool(j.get("lookup")) for _, js in staged.values() for j in js or ()
        )
    if built:
        out["buildRows"] = sum(n for _, n in built.values())
    if devices:
        out["meshDevices"] = devices
    return out


def compile_counts(spans: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """A trace's XLA compilations: each span carries what was compiled
    while it was the innermost one on its thread, so the sum is the
    query's."""
    n = loads = 0
    ms = 0.0
    for s in spans:
        attrs = s.get("attrs") or {}
        n += attrs.get("xlaCompiles", 0)
        ms += attrs.get("xlaCompileMs", 0.0)
        loads += attrs.get("xlaCacheLoads", 0)
    return {
        "xlaCompiles": n, "xlaCompileMs": round(ms, 3), "xlaCacheLoads": loads,
    }


_TRACER = Tracer()


def _on_duration_event(event: str, duration_secs: float, **_kw: Any) -> None:
    if event not in (_COMPILE_EVENT, _CACHE_LOAD_EVENT):
        return
    cur = _TRACER.current()
    if cur is None:
        return
    if event == _COMPILE_EVENT:
        cur.add("xlaCompiles")
        cur.add("xlaCompileMs", duration_secs * 1000.0)
    else:
        cur.add("xlaCacheLoads")


_monitoring.register_event_duration_secs_listener(_on_duration_event)


def get_tracer() -> Tracer:
    return _TRACER
