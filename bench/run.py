"""The benchmark's one command: run one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on, with the chips it finds: it exits
non-zero, and prints no result, when JAX finds no TPU or fewer chips than
the cell asks for. Its last line on standard output is the result object;
its last lines on standard error are the numbers compared with the
reference, each beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

# ruff: noqa: E402
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, registry

    cell = registry.cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log(f"bench: JAX found no TPU (platform "
                    f"{devices[0].platform!r}); nothing was run")
        return 2
    if len(devices) < cell.chips:
        harness.log(f"bench: {args.workload} needs {cell.chips} chips, JAX "
                    f"found {len(devices)}; nothing was run")
        return 2
    harness.log(f"[device] platform={devices[0].platform} "
                f"kind={devices[0].device_kind} count={len(devices)}")
    harness.log(f"[compile-cache] {harness.enable_compile_cache()}")
    try:
        harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                    t_start=T_START)
    except harness.UntraceableSearch as e:
        harness.log(f"bench: {e}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
