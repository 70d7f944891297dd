"""Find the knee of an open-loop cell: the highest rate served without a
growing backlog.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 30 40 50

One set-up, then one window a rate, each behind a fresh router. For each
rate it prints one JSON line: latency percentiles, and the growth of the
backlog (mean latency of the last fifth of the requests minus that of
the first fifth). An open-loop mix's fixed rate (``traffic/<mix>.json``
``rate_per_s``) is set at about 0.8x the knee this finds; the
benchmark's runs never sweep.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# ruff: noqa: E402
import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, registry, traffic
    from repro.launch import proxy, serving

    if jax.devices()[0].platform != "tpu":
        harness.log("sweep: JAX found no TPU; nothing was run")
        return 2
    harness.enable_compile_cache()
    cell = registry.cell(args.workload)
    with tempfile.TemporaryDirectory(prefix="bench-snapshot-") as spill_dir:
        served = harness.setup(cell, args.seed, "pallas", spill_dir)
        warm = [served.pool[:cell.mix["batch"]]]
        serving.warmup_replicas([(served.encode, served.search)], warm)
        gc.collect()
        gc.freeze()  # as a run does at the end of its set-up
        for rate in args.rates:
            router = proxy.QueryRouter(
                proxy.ReplicaSet([(served.encode, served.search)]))
            reqs, late, _ = traffic.run_open(router, served.pool, cell.mix,
                                             rate, args.seconds, args.seed)
            router.close()
            served.codes_of.clear()
            lat = np.array([r.latency for r in reqs if r.done is not None])
            fifth = max(1, len(lat) // 5)
            print(json.dumps({
                "rate_per_s": rate, "requests": len(reqs),
                "answered": int(len(lat)),
                "p50_ms": 1e3 * float(np.percentile(lat, 50)),
                "p95_ms": 1e3 * harness.percentile(list(lat), 95),
                "max_ms": 1e3 * float(lat.max()),
                "backlog_growth_ms": 1e3 * float(lat[-fifth:].mean()
                                                 - lat[:fifth].mean()),
                "generator_late_ms": 1e3 * late}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
