"""Time the flat scan at each query and document tile, on the chip, at
web-flat's corpus.

    python benchmarks/scan_tile_sweep.py
        [--sweep "8:8/512,1024,2048,4096 128:128/512,1024,2048,4096"]
        [--reps 10] [--seed 7]

Draws the web-flat cells' corpus (``bench/configs/web-flat.json``: its
``corpus_seed``, 2^23 nibble-packed documents) and binarizer on the
device as the benchmark does, builds the served ``FlatSDC`` and, for
each request size Q, query tile ``block_q`` and document tile
``block_n``, times ``FlatSDC.search`` (the served
``sdc_search_backend``) with those tiles set. ``--sweep`` lists
``Q:block_q,.../block_n,...`` groups; without ``/block_n,...`` a group
runs at the program's document tile (``defaults.BLOCK_N``). For
each triple it prints one JSON line: the stored corpus's shape, the
request's wall time (median of ``--reps`` calls, each to
``block_until_ready``), the kernel's device time a request from a
profiler trace of the same calls (the ``sdc_topk_q<block_q>`` ops), the
device time of every other op a request (the ``copy`` in front of the
kernel among them), the grid steps a request
(``ceil(Q / block_q) * N / block_n``), and the kernel's microseconds a
step and a 512-document tile. A markdown table follows on standard
error. TPU only: elsewhere it exits 2 and prints nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# ruff: noqa: E402
import numpy as np

CONFIG = os.path.join(ROOT, "bench", "configs", "web-flat.json")
DEFAULT_SWEEP = "8:8/512,1024,2048,4096 128:128/512,1024,2048,4096"


def parse_sweep(text: str) -> list[tuple[int, int, int | None]]:
    """``"128:8,128/512,1024 8:8"`` -> [(128, 8, 512), (128, 8, 1024),
    (128, 128, 512), (128, 128, 1024), (8, 8, None)]; None is the
    program's document tile."""
    out = []
    for part in text.split():
        q, tiles = part.split(":")
        q_tiles, _, n_tiles = tiles.partition("/")
        block_ns = [int(n) for n in n_tiles.split(",")] if n_tiles else [None]
        out += [(int(q), int(t), n) for t in q_tiles.split(",")
                for n in block_ns]
    return out


def time_tile(index, q_codes, k: int, block_q: int, block_n: int, reps: int):
    """(wall seconds of each call, kernel seconds of all calls or None,
    seconds of every other device op of all calls or None)."""
    import jax

    from bench import trace

    def call():
        return jax.block_until_ready(
            index.search(q_codes, k, block_q=block_q, block_n=block_n))

    call()  # compile and warm
    wall = []
    for _ in range(reps):
        t = time.perf_counter()
        call()
        wall.append(time.perf_counter() - t)
    with tempfile.TemporaryDirectory() as log_dir:
        with trace.capture(log_dir):
            for _ in range(reps):
                call()
        tr = trace.load(log_dir)
    if not tr.ops:
        return wall, None, None
    by_name = trace.time_by_name(tr.ops, float("-inf"), float("inf"))
    kernel = sum(v for n, v in by_name.items()
                 if n.split(".")[0] == f"sdc_topk_q{block_q}")
    return wall, kernel, sum(by_name.values()) - kernel


def sweep(index, queries, triples, *, k: int, reps: int):
    from repro.kernels.sdc.defaults import BLOCK_N

    n_docs = index.inv_norm.shape[0]
    rows = []
    for q, bq, bn in triples:
        bn = BLOCK_N if bn is None else bn
        try:
            wall, kernel, other = time_tile(index, queries[:q], k, bq, bn,
                                            reps)
        except Exception as e:  # noqa: BLE001 — a tile the chip refuses
            print(json.dumps({"q": q, "block_q": bq, "block_n": bn,
                              "error": str(e)[:500]}), flush=True)
            continue
        steps = -(-q // bq) * -(-n_docs // bn)
        kernel_ms = None if kernel is None else 1e3 * kernel / reps
        us_step = None if kernel_ms is None else 1e3 * kernel_ms / steps
        rows.append({
            "q": q, "block_q": bq, "block_n": bn, "n_docs": n_docs,
            "corpus_shape": list(index.codes.shape), "reps": reps,
            "wall_ms": 1e3 * float(np.median(wall)),
            "kernel_ms": kernel_ms,
            "other_ops_ms": None if other is None else 1e3 * other / reps,
            "corpus_passes": -(-q // bq),
            "grid_steps": steps,
            "us_per_step": us_step,
            "us_per_512_docs": None if us_step is None else us_step * 512 / bn,
        })
        print(json.dumps(rows[-1]), flush=True)
    return rows


def table(rows) -> str:
    def f(x, fmt):
        return "not measured" if x is None else format(x, fmt)

    out = ["| corpus | Q | block_q | block_n | grid steps | wall ms a request "
           "| kernel ms a request | other ops ms | us a step "
           "| us a 512 docs |",
           "| --- | --- | --- | --- | --- | --- | --- | --- | --- | --- |"]
    for r in rows:
        out.append(
            f"| {r['corpus_shape']} | {r['q']} | {r['block_q']} | "
            f"{r['block_n']} | {r['grid_steps']:,} | {r['wall_ms']:.3f} | "
            f"{f(r['kernel_ms'], '.3f')} | {f(r['other_ops_ms'], '.3f')} | "
            f"{f(r['us_per_step'], '.4f')} | "
            f"{f(r['us_per_512_docs'], '.4f')} |")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sweep", default=DEFAULT_SWEEP,
                    help="request sizes and tiles, 'Q:t1,t2/n1,n2 Q:t3'")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=7, help="draws the queries")
    args = ap.parse_args(argv)

    import jax

    from bench import harness, reference
    from repro.index.flat import FlatSDC

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"scan_tile_sweep: JAX found no TPU (platform "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        return 2
    print(f"[device] platform={dev.platform} kind={dev.device_kind}",
          file=sys.stderr)
    harness.enable_compile_cache()
    with open(CONFIG) as fh:
        cfg = json.load(fh)
    triples = parse_sweep(args.sweep)
    t = time.perf_counter()
    params, state, corpus = harness.deployment(cfg)
    index = FlatSDC.build(corpus.all_codes(), cfg["binarizer"]["n_levels"],
                          packed=cfg["index"]["params"]["packed"],
                          backend="pallas")
    pool = corpus.queries(max(q for q, _, _ in triples), args.seed)
    queries = reference.encode(
        params, state, jax.numpy.asarray(pool),
        precision=cfg["binarizer"]["matmul_precision"])
    jax.block_until_ready((index.codes, queries))
    print(f"[setup] {corpus.n_docs} docs in {time.perf_counter() - t:.1f} s",
          file=sys.stderr)
    rows = sweep(index, queries, triples, k=cfg["k"], reps=args.reps)
    print(table(rows), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
