"""Device time of the query encoder per request, in ms: the operations
of the served encode program (``binarize_lib.make_encode_fn``, jitted as
``_encode``), summed over chips."""

import re

PROGRAM = re.compile(r"(^|[^A-Za-z0-9])_encode([^A-Za-z0-9]|$)")


def read(ctx):
    lo, hi = ctx.window
    t = sum(min(o.end, hi) - max(o.start, lo) for o in ctx.trace.ops
            if PROGRAM.search(o.module) and min(o.end, hi) > max(o.start, lo))
    if t <= 0 or ctx.n_requests == 0:
        return None
    return 1e3 * t / ctx.n_requests
