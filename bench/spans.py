"""The program's own spans in a traced run, reduced to per-layer numbers.

The served path writes a profiler span at each of its boundaries
(``src/repro/launch/spans.py``): ``proxy.request``, ``proxy.submit``,
``serving.admit``, ``serving.queued``, ``serving.encode``,
``serving.handoff``, ``serving.dispatch``, ``serving.await``,
``serving.resolve``, and the stages' idle waits ``serving.encode_idle``
and ``serving.scan_idle``. They arrive in ``Trace.host`` on the clock
of the device operations, by name and interval only.

A program without these spans gives no such host spans: every reduction
here then returns ``None``.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

from bench import trace

# The scan thread is always in one of these (or between two of them):
# together they say why the device had nothing to run.
SCAN_SPANS = ("serving.dispatch", "serving.await", "serving.resolve",
              "serving.scan_idle")
ENCODE_SPANS = ("serving.encode", "serving.encode_idle")
AWAIT_PARTS = ("before_first_op", "between_ops", "after_last_op", "no_op")
# The served encoder's program (as ``metrics/encode_ms.py`` finds it): its
# ops may run inside a search's await without being the search's.
ENCODER = re.compile(r"(^|[^A-Za-z0-9])_encode([^A-Za-z0-9]|$)")


def ended_in_window(ctx, name: str) -> List[trace.Op]:
    """The host spans called ``name`` that end inside the window."""
    lo, hi = ctx.window
    return [h for h in ctx.trace.host if h.name == name and lo <= h.end <= hi]


def mean_ms(ctx, name: str) -> Optional[float]:
    """Mean duration in ms of the spans ``name`` that end in the window."""
    spans = ended_in_window(ctx, name)
    if not spans:
        return None
    return 1e3 * sum(h.end - h.start for h in spans) / len(spans)


def self_mean_ms(ctx, name: str, child: str) -> Optional[float]:
    """Mean self time in ms of the spans ``name`` that end in the window:
    each one's duration less the part that ``child`` spans inside it
    cover. Children are matched by interval, so this holds where one
    thread opens the ``name`` spans at a time (an open loop's generator)."""
    spans = ended_in_window(ctx, name)
    if not spans:
        return None
    kids = [(h.start, h.end) for h in ctx.trace.host if h.name == child]
    own = sum(trace.length(trace.subtract([(h.start, h.end)], kids))
              for h in spans)
    return 1e3 * own / len(spans)


def _overlap(a: List[trace.Interval], b: List[trace.Interval]) -> float:
    return trace.length(a) - trace.length(trace.subtract(a, b))


def idle_split(ctx, top: int = 5) -> Optional[Dict]:
    """Device-idle time with a request in flight, put down to the program
    span the scan thread was in (``SCAN_SPANS``, or ``-`` between them),
    and, while it waited for input, the span the encode thread was in.
    The idle time inside ``serving.await`` spans is split again: before
    the first op of a program other than the encoder's inside the span
    (the dispatched search had not started), between such ops, after the
    last (the device was done and the host not yet back), or in an
    await with no such op. Seconds
    averaged over chips, as ``device_idle`` averages them; also the
    ``top`` longest idle stretches with every program span over each.
    """
    lo, hi = ctx.window
    inflight = trace.union(trace.clip(ctx.inflight, lo, hi))
    if not inflight or not ctx.busy:
        return None
    by_name: Dict[str, List[trace.Interval]] = {}
    for h in ctx.trace.host:
        if h.name.startswith(("serving.", "proxy.")):
            by_name.setdefault(h.name, []).append((h.start, h.end))
    if not by_name:
        return None
    scan = {n: trace.union(by_name.get(n, [])) for n in SCAN_SPANS}
    waiting = scan["serving.scan_idle"]
    split: Dict[str, float] = {}
    while_waiting: Dict[str, float] = {}
    in_await = dict.fromkeys(AWAIT_PARTS, 0.0)
    idle_s, stretches = 0.0, []
    chips = len(ctx.busy)
    searched: Dict[int, List[trace.Interval]] = {}
    for o in ctx.trace.ops:
        if not ENCODER.search(o.module):
            searched.setdefault(o.device, []).append((o.start, o.end))
    for dev, busy in ctx.busy.items():
        search_busy = trace.union(searched.get(dev, []))
        idle = trace.subtract(inflight, busy)
        idle_s += trace.length(idle) / chips
        stretches += idle
        named = 0.0
        for n, spans in scan.items():
            t = _overlap(idle, spans)
            split[n] = split.get(n, 0.0) + t / chips
            named += t
        split["-"] = split.get("-", 0.0) + (trace.length(idle) - named) / chips
        idle_waiting = [(s, e) for a, b in idle for s, e in
                        trace.clip(waiting, a, b)]
        named = 0.0
        for n in ENCODE_SPANS:
            t = _overlap(idle_waiting, trace.union(by_name.get(n, [])))
            while_waiting[n] = while_waiting.get(n, 0.0) + t / chips
            named += t
        while_waiting["-"] = (while_waiting.get("-", 0.0)
                              + (trace.length(idle_waiting) - named) / chips)
        for s, e in by_name.get("serving.await", []):
            own = trace.clip(idle, s, e)
            if not own:
                continue
            ran = trace.clip(search_busy, s, e)
            if not ran:
                in_await["no_op"] += trace.length(own) / chips
                continue
            before = trace.length(trace.clip(own, s, ran[0][0]))
            after = trace.length(trace.clip(own, ran[-1][1], e))
            in_await["before_first_op"] += before / chips
            in_await["after_last_op"] += after / chips
            in_await["between_ops"] += (trace.length(own) - before
                                        - after) / chips
    longest = []
    for s, e in sorted(stretches, key=lambda g: g[0] - g[1])[:top]:
        over = {n: _overlap([(s, e)], trace.union(iv))
                for n, iv in by_name.items()}
        longest.append({"s": e - s, "at": s - lo,
                        "spans": {n: t for n, t in sorted(
                            over.items(), key=lambda kv: -kv[1]) if t > 0}})
    return {"idle_in_flight_s": idle_s, "scan_thread": split,
            "encode_thread_while_scan_waits": while_waiting,
            "await": in_await, "longest": longest}
