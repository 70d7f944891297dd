"""Share of the traced window in which a request was in flight and no
operation ran on the device, in %, averaged over the chips.

A request is in flight from its submit to its answer in the client's
hand. Plain idle time would rise in an open loop when the device got
faster; time with work waiting and the device idle does not.
"""

from bench import trace


def read(ctx):
    lo, hi = ctx.window
    inflight = trace.union(trace.clip(ctx.inflight, lo, hi))
    if not inflight or not ctx.busy:
        return None
    idle = [trace.length(trace.subtract(inflight, b))
            for b in ctx.busy.values()]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)
