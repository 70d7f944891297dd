"""Profiler spans at the boundaries of the served path, and the stage
totals read at the same boundaries.

Each span is a ``jax.profiler.TraceAnnotation``: it lands in the
profiler's own trace, on the clock of the device operations, and only
while a profiler session runs. The session is the only switch; with none
a span costs one ``is_enabled`` check. Nothing is logged or exported
here: the profiler holds the spans and writes them out when the trace
stops. The spans of one request carry the router's sequence number as
the argument ``req``.

A span may open on one thread and close on another (a request waiting in
a queue between two stages); the profiler records it on the closing
thread with its true duration. One that is never closed would record a
wrong end when it is garbage-collected, so every path that ends a
crossing span closes it once.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

_tracing = TraceAnnotation.is_enabled

# The stages of one replica, as ``ServingPipeline.stats()["stages"]``
# reports them; each is timed at the boundaries of one span.
STAGES = (
    "admission_wait",   # serving.queued: admitted -> encode stage takes it
    "encode",           # serving.encode: the encode_fn call
    "handoff",          # serving.handoff: encoded -> scan stage dispatches
    "dispatch",         # serving.dispatch: the search_fn call
    "await",            # serving.await: block_until_ready of the result
    "resolve",          # serving.resolve: ticket resolve + done callbacks
    "scan_input_wait",  # serving.scan_idle: scan stage blocked on input
)


class Span:
    """One open span; ``close`` ends it and returns its seconds.

    The seconds come from ``time.perf_counter`` read where the span opens
    and closes, so the stage totals and the trace time the same stretch.
    """

    __slots__ = ("_t0", "_tm")

    def __init__(self, name: str, req: Optional[int] = None):
        self._tm = None
        if _tracing():
            self._tm = (TraceAnnotation(name) if req is None
                        else TraceAnnotation(name, req=req))
            self._tm.__enter__()
        self._t0 = time.perf_counter()

    def close(self) -> float:
        t = time.perf_counter() - self._t0
        tm, self._tm = self._tm, None
        if tm is not None:
            tm.__exit__(None, None, None)
        return t


class StageTimes:
    """Seconds and count of each stage of one replica, since the last
    ``reset`` (the pipeline's stats generation)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def add(self, stage: str, seconds: float) -> None:
        with self._lock:
            self._s[stage] += seconds
            self._n[stage] += 1

    def reset(self) -> None:
        with self._lock:
            self._s: Dict[str, float] = dict.fromkeys(STAGES, 0.0)
            self._n: Dict[str, int] = dict.fromkeys(STAGES, 0)

    def snapshot(self) -> Dict[str, dict]:
        """{stage: {"seconds": total, "count": n}}."""
        with self._lock:
            return {k: {"seconds": self._s[k], "count": self._n[k]}
                    for k in STAGES}
