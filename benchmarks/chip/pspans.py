"""Reduction of the program's own spans (``paio.*``, written by
``repro.telemetry.spans``) in a profiler trace (``.xplane.pb``).

Each host thread is a line of the trace's host plane, and its spans are kept
per line: a span's self time is its duration less that of the spans nested in
it on the same line. Device idle time is attributed only by the spans of the
line that dispatches to the device, the one that holds the benchmark's
``window`` span (``xtrace.WINDOW_SPAN``; both jobs dispatch from the thread
that opens it). The other lines (the control plane's tick, the checkpoint
writer) give self time only.

Device time goes to the span that launched it: each program run's operations
(its ``XLA Modules`` event on the device, by ``run_id``) are credited to the
innermost span of the dispatching line that was open when the host launched
that run. The run is enqueued (``DoEnqueueProgram``, by ``run_id``) on
whichever runtime thread got to it, often after the call that launched it
returned; the launch is the start of the event at the other end of the flow
(``_c`` ← ``_p``) that the enqueue's enclosing event consumes, which runs
inside the launching call. The device timeline is put on the host's clock by
``xtrace``'s offset.

Nothing here imports the program.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks.chip import xtrace

PREFIX = "paio."
LAUNCH = "DoEnqueueProgram"

#: (plane name, line index): one host thread
LineKey = Tuple[str, int]


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str
    span_id: Optional[int]


@dataclasses.dataclass
class Spans:
    #: each host line's ``paio.*`` spans, by start
    lines: Dict[LineKey, List[Span]]
    #: the line that holds the window span; None where the trace has none
    dispatch: Optional[LineKey]
    #: run_id -> host time the run was launched
    launches: Dict[int, float]
    #: device plane -> its program runs as (start_ns, end_ns, run_id), device clock
    runs: Dict[str, List[Tuple[float, float, int]]]


def _stat(event, key: str):
    for k, v in event.stats:
        if k == key:
            return int(v)
    return None


def load(path: str) -> Spans:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    lines: Dict[LineKey, List[Span]] = {}
    dispatch: Optional[LineKey] = None
    enqueues: List[Tuple[float, int, LineKey]] = []
    producers: Dict[int, float] = {}
    consumers: Dict[LineKey, List[Tuple[float, float, int]]] = {}
    runs: Dict[str, List[Tuple[float, float, int]]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == xtrace.MODULES_LINE:
                    for e in line.events:
                        run = _stat(e, "run_id")
                        if run is not None:
                            runs.setdefault(plane.name, []).append((e.start_ns, e.start_ns + e.duration_ns, run))
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                key = (plane.name, i)
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        lines.setdefault(key, []).append(Span(e.start_ns, e.start_ns + e.duration_ns, e.name, _stat(e, "span_id")))
                        continue
                    if e.name == xtrace.WINDOW_SPAN:
                        dispatch = key
                    elif e.name == LAUNCH and _stat(e, "run_id") is not None:
                        enqueues.append((e.start_ns, _stat(e, "run_id"), key))
                    flow_out, flow_in = _stat(e, "_p"), _stat(e, "_c")
                    if flow_out is not None:
                        producers.setdefault(flow_out, e.start_ns)
                    if flow_in is not None:
                        consumers.setdefault(key, []).append((e.start_ns, e.start_ns + e.duration_ns, flow_in))
    for spans in lines.values():
        spans.sort(key=lambda s: (s.start, -s.end))
    for r in runs.values():
        r.sort()
    for c in consumers.values():
        c.sort()
    launches: Dict[int, float] = {}
    for t, run, key in enqueues:
        launches.setdefault(run, _launch_time(t, consumers.get(key, []), producers))
    return Spans(lines, dispatch, launches, runs)


def _launch_time(t: float, consumers: List[Tuple[float, float, int]], producers: Dict[int, float]) -> float:
    """The start of the flow's producer that the innermost flow-consuming
    event around an enqueue at ``t`` (on its line, sorted by start) consumes;
    ``t`` itself where there is none."""
    k = bisect.bisect_right(consumers, (t, float("inf"), 0)) - 1
    while k >= 0 and t - consumers[k][0] < 1e9:  # an enclosing event began within the last second
        start, end, flow = consumers[k]
        if end > t and flow in producers:
            return producers[flow]
        k -= 1
    return t


def self_times(spans: Sequence[Span]) -> List[float]:
    """Each span's duration less the spans directly nested in it (one line's
    spans, sorted by start, properly nested)."""
    own = [s.end - s.start for s in spans]
    stack: List[int] = []
    for i, s in enumerate(spans):
        while stack and spans[stack[-1]].end <= s.start:
            stack.pop()
        if stack and s.end <= spans[stack[-1]].end:
            own[stack[-1]] -= s.end - s.start
        stack.append(i)
    return own


def segments(spans: Sequence[Span]) -> List[Tuple[float, float, Span]]:
    """One line's spans (sorted by start, properly nested) as disjoint pieces,
    each with the innermost span open over it; time under no span is left
    out."""
    out: List[Tuple[float, float, Span]] = []
    stack: List[Span] = []
    cursor = float("-inf")
    for s in spans:
        while stack and stack[-1].end <= s.start:
            top = stack.pop()
            if top.end > cursor:
                out.append((cursor, top.end, top))
                cursor = top.end
        if stack and s.start > cursor:
            out.append((cursor, s.start, stack[-1]))
        cursor = max(cursor, s.start)
        stack.append(s)
    while stack:
        top = stack.pop()
        if top.end > cursor:
            out.append((cursor, top.end, top))
            cursor = top.end
    return out


def attribute_ops(trace: xtrace.Trace, sp: Spans) -> List[Tuple[float, float, str, Optional[Span]]]:
    """Every device operation on the host's clock, with the dispatching
    line's span that launched its run (None where no span was open or the
    run's launch is not in the trace)."""
    pieces = segments(sp.lines.get(sp.dispatch, [])) if sp.dispatch is not None else []
    piece_starts = [p[0] for p in pieces]

    def innermost_at(t: float) -> Optional[Span]:
        k = bisect.bisect_right(piece_starts, t) - 1
        return pieces[k][2] if k >= 0 and t < pieces[k][1] else None

    out = []
    for device, ops in trace.device.items():
        runs = sp.runs.get(device, [])
        starts = [r[0] for r in runs]
        by_run: Dict[int, Optional[Span]] = {}
        for s, e, name in ops:
            k = bisect.bisect_right(starts, s) - 1
            owner = None
            if k >= 0 and s < runs[k][1]:
                run = runs[k][2]
                if run not in by_run:
                    launched = sp.launches.get(run)
                    by_run[run] = innermost_at(launched) if launched is not None else None
                owner = by_run[run]
            out.append((s + trace.offset_ns, e + trace.offset_ns, name, owner))
    return out


def reduce(trace: xtrace.Trace, sp: Spans) -> dict:
    """Per span name: count, total and self seconds over every line; and, on
    the dispatching line within the window, the device's idle seconds inside
    each name's spans (``idle_in``: its children's time included;
    ``idle_self``: only where it is the innermost span) and the device's busy
    seconds that each name launched (``busy_by``, innermost at launch; the
    union of its operations' intervals).
    Times are averaged over devices, as in ``xtrace.reduce``."""
    table: Dict[str, Dict[str, float]] = {}
    for spans in sp.lines.values():
        for s, own in zip(spans, self_times(spans)):
            row = table.setdefault(s.name, {"count": 0, "total_s": 0.0, "self_s": 0.0})
            row["count"] += 1
            row["total_s"] += (s.end - s.start) * 1e-9
            row["self_s"] += own * 1e-9
    out = {"spans": table, "idle_in": {}, "idle_self": {}, "busy_by": {}, "in_window": {}}
    windows = [(s, e) for s, e, n in trace.host if n == xtrace.WINDOW_SPAN]
    devices = [d for d, ops in trace.device.items() if ops]
    if sp.dispatch is None or not windows or not devices:
        return out
    lo, hi = windows[0]
    spans = [s for s in sp.lines.get(sp.dispatch, []) if s.end > lo and s.start < hi]
    shift = trace.offset_ns
    n = len(devices)
    busy = {
        d: xtrace.merge([(max(s + shift, lo), min(e + shift, hi)) for s, e, _ in trace.device[d] if e + shift > lo and s + shift < hi])
        for d in devices
    }
    ends = {d: [e for _, e in busy[d]] for d in devices}

    def idle(a: float, b: float) -> float:
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            return 0.0
        return sum((b - a) - xtrace._busy_within(busy[d], a, b, bisect.bisect_right(ends[d], a))[0] for d in devices) * 1e-9 / n

    # inclusive: each span with the spans nested in it (one line's spans of
    # one name never nest, so adding them counts no time twice)
    for s in spans:
        out["in_window"][s.name] = out["in_window"].get(s.name, 0) + 1
        out["idle_in"][s.name] = out["idle_in"].get(s.name, 0.0) + idle(s.start, s.end)
    for a, b, s in segments(spans):
        out["idle_self"][s.name] = out["idle_self"].get(s.name, 0.0) + idle(a, b)
    for d in devices:
        launched: Dict[str, List[Tuple[float, float]]] = {}
        for s, e, _, owner in attribute_ops(xtrace.Trace({d: trace.device[d]}, trace.host, shift), sp):
            a, b = max(s, lo), min(e, hi)
            if b > a:
                launched.setdefault(owner.name if owner is not None else "none", []).append((a, b))
        # the union of each name's ops: a loop op holds its body's ops
        for k, v in launched.items():
            out["busy_by"][k] = out["busy_by"].get(k, 0.0) + sum(b - a for a, b in xtrace.merge(v)) * 1e-9 / n
    return out
