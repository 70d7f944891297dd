"""Run one cell traced, as ``run.py --trace 1`` does, and say where the
device's idle time with a request in flight went, by program span.

    python3 bench/idle_split.py --workload <cell> --seed <n> --seconds <s>

Prints the run's result line, then JSON lines: ``{"idle_split": ...}``
from ``spans.idle_split`` (the scan thread's span over each idle stretch,
the encode thread's while the scan thread waited for input, the longest
stretches with every program span over them), null for a program that
writes no spans; ``{"spans": ...}``, the count and mean ms of each
program span that ended in the window; ``{"other_host": ...}``, the other
host events (the client's, the runtime's) over each longest stretch; and
``{"gc": ...}``, the garbage collections in the window with their
pauses. A measuring aid beside the benchmark: `run.py` never calls it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

# ruff: noqa: E402
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, spans, trace

    if jax.devices()[0].platform != "tpu":
        harness.log("idle_split: JAX found no TPU; nothing was run")
        return 2
    harness.log(f"[compile-cache] {harness.enable_compile_cache()}")
    # The run's own per-layer context, kept as the harness builds it.
    kept = []
    build = harness.layer_context

    def keep(*a, **kw):
        kept.append(build(*a, **kw))
        return kept[-1]

    harness.layer_context = keep
    collections, started = [], []

    def on_gc(phase, info):
        if phase == "start":
            started[:] = [time.perf_counter()]
        elif started:
            collections.append((started[0], time.perf_counter() - started[0],
                                info["generation"]))

    gc.callbacks.append(on_gc)
    harness.run(args.workload, args.seed, args.seconds, True,
                t_start=T_START)
    gc.callbacks.remove(on_gc)
    ctx = kept[0]
    names = sorted({h.name for h in ctx.trace.host
                    if h.name.startswith(("serving.", "proxy."))})
    means = {n: [len(spans.ended_in_window(ctx, n)), spans.mean_ms(ctx, n)]
             for n in names}
    split = spans.idle_split(ctx)
    print(json.dumps({"idle_split": split}), flush=True)
    print(json.dumps({"spans": means or None}), flush=True)
    # The longest idle stretches with a request in flight, with the other
    # host events (the client's, the runtime's, the Python tracer's
    # frames, named "$file:line function"): the runtime's events that
    # hold the whole stretch, innermost first, and every event that
    # starts inside it, longest first.
    lo, hi = ctx.window
    stretches = sorted(trace.subtract(
        trace.clip(ctx.inflight, lo, hi),
        [iv for b in ctx.busy.values() for iv in b]),
        key=lambda g: g[0] - g[1])[:5]
    other = []
    for s, e in stretches:
        rest = [h for h in ctx.trace.host
                if not h.name.startswith(("serving.", "proxy."))]
        held = sorted((h for h in rest if h.start <= s and h.end >= e
                       and not h.name.startswith("$")),
                      key=lambda h: h.end - h.start)[:8]
        began = sorted((h for h in rest if s < h.start < e),
                       key=lambda h: h.start - h.end)[:8]
        other.append({"s": e - s, "at": s - lo,
                      "held": [[h.name, h.end - h.start] for h in held],
                      "began": [[h.name, h.start - s, h.end - h.start]
                                for h in began]})
    print(json.dumps({"other_host": other}), flush=True)
    # Collections inside the window, on the host clock it opened at.
    t0 = lo - ctx.trace.offset
    inside = [(t - t0, d, g) for t, d, g in collections
              if 0.0 <= t - t0 <= hi - lo]
    print(json.dumps({"gc": {
        "count_by_generation": {g: sum(1 for c in inside if c[2] == g)
                                for g in (0, 1, 2)},
        "longest": [{"at": a, "s": d, "generation": g} for a, d, g in
                    sorted(inside, key=lambda c: -c[1])[:5]]}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
