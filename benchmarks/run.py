"""Benchmark harness: one entry per paper table/figure + roofline.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only tableN]
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer training steps (CI mode)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    steps = 120 if args.fast else 400
    from benchmarks import (
        bits_sweep,
        fig6_ann_integration,
        roofline,
        table1_recall_public,
        table2_recall_industrial,
        table3_training_pipelines,
        table4_backward_compat,
        table5_search_latency,
        table67_system_ab,
    )

    suites = {
        "table1": lambda: table1_recall_public.run(steps=steps),
        "table2": lambda: table2_recall_industrial.run(steps=steps),
        "table3": lambda: table3_training_pipelines.run(steps=max(steps // 3, 60)),
        "table4": lambda: table4_backward_compat.run(steps=max(steps // 2, 100)),
        "table5": table5_search_latency.run,
        # machine-readable scan perf (BENCH_sdc_scan.json) without the
        # rest of table5 — cheap enough for every CI run. --fast shrinks
        # the corpus to CI-smoke size (the byte-ratio gate that
        # scripts/check_bench_gate.py enforces is size-independent).
        "bench_sdc_scan": lambda: table5_search_latency.emit_sdc_scan_json(
            **(dict(n_docs=4096, queries=8) if args.fast else {})
        ),
        # graph-search trajectory (BENCH_hnsw_scan.json): hops, candidates
        # scored, ms, recall vs the flat scan.
        "bench_hnsw_scan": lambda: fig6_ann_integration.emit_hnsw_scan_json(
            **(dict(n_docs=1500, queries=8) if args.fast else {})
        ),
        # steady-state serving throughput (BENCH_serving.json): sequential
        # encode+scan loop vs the double-buffered ServingPipeline vs the
        # replicated router tier. The CI gate holds overlapped QPS >=
        # sequential and replicated >= 0.9x overlapped on the smoke
        # corpus; extra interleaved trials keep the best-of/median-paired
        # ratios immune to shared-runner noise (each smoke trial is
        # ~1s). The replica gate compares N>1 vs the replicas=1 tier run
        # of the same trial — the identical code path, so the ratio
        # survives this host's 2x noisy-neighbour swings (comparing
        # against the plain overlapped pipeline does not: its different
        # thread structure de-pairs the noise).
        "bench_serving_pipeline": lambda:
            table5_search_latency.emit_serving_json(
                **(dict(n_docs=4096, batch=32, n_batches=40, trials=6)
                   if args.fast else {})
            ),
        "fig6": lambda: fig6_ann_integration.run(steps=max(steps // 2, 100)),
        "table67": lambda: table67_system_ab.run(steps=max(steps // 2, 100)),
        "bits_sweep": lambda: bits_sweep.run(steps=max(steps // 2, 100)),
        "roofline": roofline.run,
    }
    if args.only:
        suites = {args.only: suites[args.only]}

    failures = []
    for name, fn in suites.items():
        t0 = time.time()
        print(f"\n===== {name} =====", flush=True)
        try:
            fn()
            print(f"===== {name} done in {time.time()-t0:.1f}s =====", flush=True)
        except Exception:  # noqa: BLE001 — report all suites
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED suites: {failures}")
        sys.exit(1)
    print("\nall benchmark suites completed.")


if __name__ == "__main__":
    main()
