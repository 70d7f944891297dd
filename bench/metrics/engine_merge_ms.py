"""Device time of the sharded engine's selection merge per request, in
ms, averaged over chips: in each execution of a program that exchanges
the leaves' top-k, the operations from the first all-gather on (the
exchange, the merge top-k and the id gather). The leaves' scans hold no
collective, so the first one marks where the merge begins, whichever
collective the compiler chose for the exchange."""

COLLECTIVES = ("all-gather", "all_gather", "allgather", "all-reduce",
               "collective-permute", "all-to-all")


def read(ctx):
    lo, hi = ctx.window
    per_dev = {}
    execs = {}
    for o in ctx.trace.ops:
        if lo <= o.start < hi:
            execs.setdefault((o.device, o.run), []).append(o)
    for (dev, _), ops in execs.items():
        ops.sort(key=lambda o: o.start)
        merge, on = 0.0, False
        for o in ops:
            on = on or any(c in o.name.lower() for c in COLLECTIVES)
            if on:
                merge += o.end - o.start
        per_dev[dev] = per_dev.get(dev, 0.0) + merge
    t = sum(per_dev.values()) / max(1, len(per_dev))
    if t <= 0 or ctx.n_requests == 0:
        return None
    return 1e3 * t / ctx.n_requests
