"""The lower-precision control: the reference in the program's place.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed it draws the cell's weights, corpus and requests as a run
does, answers the requests that a run's check compares with the
reference computed one precision step below the configuration's (the
encoder's float32 matmuls at ``high``, three bfloat16 passes, instead of
``highest``; scores rounded to bfloat16 before ranking, instead of
float32), and prints the numbers the check compares, one JSON line a
seed. The reference answers as the index family promises: its own
``refs/<family>.py`` where it has one, else the whole corpus's top-k.
Every limit sits below what the control reads: the control has to come
out not correct. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# ruff: noqa: E402
import numpy as np


def control_numbers(name: str, seed: int, overrides=None) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import harness, reference, registry, traffic

    cell = registry.cell(name)
    cell.config.update(overrides or {})
    cfg = cell.config
    b = cfg["binarizer"]
    params, state, corpus = harness.deployment(cfg)
    pool = corpus.queries(cell.mix["pool"], seed)
    n = max(1, cfg["check_queries"] // cell.mix["batch"])
    reqs = [traffic.Request(queries=pool[r], done=0.0)
            for r in traffic.payload_rows(seed, n, cell.mix["batch"],
                                          pool.shape[0])]
    picked = harness.sample(cell, reqs, seed)
    queries = np.concatenate([r.queries for r in picked])
    codes = np.asarray(reference.encode(params, state, jnp.asarray(queries),
                                        precision="high"))
    family = registry.reference(cell.family)
    if family is not None:
        scores, ids = family.expected(cfg, codes, corpus, round_bf16=True)
    else:
        none = -np.ones((codes.shape[0], cfg["k"]), np.int64)
        scores, ids, _ = reference.exact_search(
            codes, none, corpus.chunks(), n_levels=b["n_levels"], k=cfg["k"],
            n_docs=cfg["n_docs"], round_bf16=True)
    numbers, _, parts = harness.compare(cell, (params, state), corpus,
                                        queries, codes, scores, ids)
    limits = cfg["limits"]
    return {"seed": seed, "numbers": numbers, "parts": parts,
            "fails": sorted(k for k in numbers if numbers[k] > limits[k]),
            "device": jax.devices()[0].device_kind}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        t = time.perf_counter()
        out = control_numbers(args.workload, seed)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
