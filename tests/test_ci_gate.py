"""The CI bench gate (scripts/check_bench_gate.py) must actually gate:
green on a healthy packed/unpacked byte ratio, red on a regressed one, on
a missing packed row, and on an empty report (deliberate-failure coverage
demanded by the CI satellite — a gate that cannot fail is decoration)."""

import json
import os
import subprocess
import sys

GATE = os.path.join(
    os.path.dirname(__file__), "..", "scripts", "check_bench_gate.py"
)


def _rows(ratio: float):
    return [
        {"variant": "flat", "packed": False, "bytes_scanned": 100_000},
        {"variant": "flat", "packed": True,
         "bytes_scanned": int(100_000 * ratio)},
        {"variant": "ivf", "packed": False, "bytes_scanned": 50_000},
        {"variant": "ivf", "packed": True,
         "bytes_scanned": int(50_000 * ratio)},
    ]


def _run_gate(tmp_path, bench: dict, *extra):
    path = tmp_path / "bench.json"
    path.write_text(json.dumps(bench))
    return subprocess.run(
        [sys.executable, GATE, str(path), *extra],
        capture_output=True, text=True, timeout=60,
    )


def test_gate_passes_healthy_ratio(tmp_path):
    out = _run_gate(tmp_path, {"rows": _rows(0.53)})
    assert out.returncode == 0, out.stderr


def test_gate_fails_regressed_ratio(tmp_path):
    out = _run_gate(tmp_path, {"rows": _rows(0.60)})
    assert out.returncode != 0
    assert "FAIL" in out.stdout


def test_gate_threshold_is_configurable(tmp_path):
    out = _run_gate(tmp_path, {"rows": _rows(0.60)},
                    "--max-packed-ratio", "0.65")
    assert out.returncode == 0, out.stderr


def test_gate_fails_on_missing_packed_row(tmp_path):
    rows = [r for r in _rows(0.5) if not r["packed"]]
    out = _run_gate(tmp_path, {"rows": rows})
    assert out.returncode != 0
    assert "MISSING-PAIR" in out.stdout


def test_gate_fails_on_empty_report(tmp_path):
    out = _run_gate(tmp_path, {"rows": []})
    assert out.returncode != 0


# -- bi-granular + bits-per-dimension sections (scan bench) ------------------


def _bigranular_section(levels=4):
    def row(c, ratio):
        return {"coarse_levels": c, "k_coarse": 40, "packed": True,
                "ms": 1.0, "recall_rerank": 0.95, "recall_coarse": 0.7,
                "coarse_bytes_scanned": int(100_000 * ratio),
                "fine_bytes_scanned": 5_000,
                "full_bytes_scanned": 100_000}
    return [row(levels // 2, 0.53), row(levels - 1, 0.78)]


def _bits_sweep_section():
    return [
        {"n_levels": n, "packed": packed, "ms": 1.0, "recall": 0.5,
         "bytes_scanned": 66_000 if packed else 132_000,
         "index_bytes": 20_000 * n}
        for n in (1, 2, 4) for packed in (False, True)
    ]


def _autotune_section():
    def row(kind, dq, dn, tq, tn, ratio, source):
        return {"kind": kind, "backend": "interpret",
                "block_q_default": dq, "block_n_default": dn,
                "block_q": tq, "block_n": tn, "source": source,
                "default_ms": None if ratio is None else 10.0,
                "tuned_ms": None if ratio is None else 10.0 * ratio,
                "ms_ratio_tuned_vs_default": ratio}
    return [row("scan", 128, 512, 32, 1024, 0.7, "tuned"),
            row("gather", 1, 0, 1, 0, 1.0, "fixed-geometry"),
            row("rerank", 1, 1, 1, 8, 0.5, "tuned")]


def _probe_budget_section(nlist=64, nprobe=8):
    def row(budget, rw, rf, **extra):
        return {"probe_budget": budget,
                "avg_probes_per_query": budget / nlist,
                "recall_weighted": rw, "recall_flat": rf, **extra}
    return [row(nlist // 2, 0.7, 0.5),
            row(nlist + nlist // 2, 0.9, 0.85),
            row(nprobe * nlist, 0.99, 0.99, bit_identical=True)]


def _scan_bench(**overrides):
    bench = {"bench": "sdc_scan", "levels": 4, "nlist": 64, "nprobe": 8,
             "rows": _rows(0.53),
             "bigranular": _bigranular_section(),
             "bits_sweep": _bits_sweep_section(),
             "autotune": _autotune_section(),
             "probe_budget": _probe_budget_section()}
    bench.update(overrides)
    return bench


def test_gate_passes_full_scan_bench(tmp_path):
    out = _run_gate(tmp_path, _scan_bench())
    assert out.returncode == 0, out.stdout + out.stderr


def test_gate_requires_a_bigranular_section(tmp_path):
    """A scan report without the coarse+rerank sweep (emitter regression)
    must not pass green; plain row-only reports without the sdc_scan
    bench tag (e.g. hnsw_scan) stay exempt."""
    out = _run_gate(tmp_path, _scan_bench(bigranular=[]))
    assert out.returncode != 0
    assert "no 'bigranular' section" in out.stderr


def test_gate_fails_on_malformed_bigranular_row(tmp_path):
    bench = _scan_bench()
    del bench["bigranular"][0]["recall_rerank"]
    del bench["bigranular"][0]["coarse_bytes_scanned"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "missing keys" in out.stderr
    assert "recall_rerank" in out.stderr
    assert "coarse_bytes_scanned" in out.stderr


def test_gate_fails_when_rerank_loses_recall(tmp_path):
    """The fine rerank refines the coarse scan; a row where rerank recall
    drops below the coarse-only recall means the rerank is broken."""
    bench = _scan_bench()
    bench["bigranular"][0]["recall_rerank"] = 0.6
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "below" in out.stderr and "coarse-only recall" in out.stderr


def test_gate_fails_on_oversized_coarse_tier(tmp_path):
    """At coarse_levels = levels // 2 the hot tier must hold <= 0.6x the
    full-level bytes — the acceptance point of the tiered layout."""
    bench = _scan_bench()
    bench["bigranular"][0]["coarse_bytes_scanned"] = 70_000
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "coarse tier too large" in out.stderr


def test_gate_coarse_ratio_is_configurable(tmp_path):
    bench = _scan_bench()
    bench["bigranular"][0]["coarse_bytes_scanned"] = 70_000
    out = _run_gate(tmp_path, bench, "--max-coarse-ratio", "0.75")
    assert out.returncode == 0, out.stdout + out.stderr


def test_gate_fails_without_the_half_levels_row(tmp_path):
    """The sweep must COVER the gated operating point: dropping the
    coarse_levels = levels // 2 row must not dodge the byte check."""
    bench = _scan_bench()
    bench["bigranular"] = bench["bigranular"][1:]  # only levels-1 row
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "no row at coarse_levels=2" in out.stderr


def test_gate_requires_a_bits_sweep_section(tmp_path):
    out = _run_gate(tmp_path, _scan_bench(bits_sweep=[]))
    assert out.returncode != 0
    assert "no 'bits_sweep' section" in out.stderr


def test_gate_fails_on_malformed_bits_sweep_row(tmp_path):
    bench = _scan_bench()
    del bench["bits_sweep"][0]["index_bytes"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "missing keys" in out.stderr and "index_bytes" in out.stderr


def test_gate_fails_on_bits_sweep_missing_packed_row(tmp_path):
    bench = _scan_bench()
    bench["bits_sweep"] = [r for r in bench["bits_sweep"]
                           if not (r["n_levels"] == 2 and r["packed"])]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "n_levels=2 has no packed row" in out.stderr


def test_gate_fails_on_bits_sweep_packed_ratio(tmp_path):
    bench = _scan_bench()
    for r in bench["bits_sweep"]:
        if r["n_levels"] == 4 and r["packed"]:
            r["bytes_scanned"] = 80_000  # 0.606x unpacked > 0.55
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "packed scan bytes ratio" in out.stderr


def test_gate_fails_on_nonmonotone_index_bytes(tmp_path):
    """Serialized bytes per doc must GROW with the level count — a
    sweep where more levels serialize smaller is measuring the wrong
    thing (or the layout silently dropped levels)."""
    bench = _scan_bench()
    for r in bench["bits_sweep"]:
        if r["n_levels"] == 4:
            r["index_bytes"] = 10_000  # below the 2-level rows
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "not monotone" in out.stderr


# -- autotune + probe-budget sections (scan bench) ---------------------------


def test_gate_requires_an_autotune_section(tmp_path):
    """A scan report without the block-plan autotuner record (emitter
    regression) must not pass green."""
    out = _run_gate(tmp_path, _scan_bench(autotune=[]))
    assert out.returncode != 0
    assert "no 'autotune' section" in out.stderr


def test_gate_fails_on_malformed_autotune_row(tmp_path):
    bench = _scan_bench()
    del bench["autotune"][0]["block_q"]
    del bench["autotune"][0]["source"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "missing keys" in out.stderr
    assert "block_q" in out.stderr and "source" in out.stderr


def test_gate_fails_when_tuned_plan_loses_to_default(tmp_path):
    """The sweep times the default as a candidate on the same operands,
    so an honest tuner can never lose — a ratio above 1 means the tuner
    shipped a plan it never beat the default with."""
    bench = _scan_bench()
    bench["autotune"][0]["ms_ratio_tuned_vs_default"] = 1.3
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "LOST to the default" in out.stderr


def test_gate_fails_on_swept_kind_without_timings(tmp_path):
    """Only un-sweepable kinds may skip timings; a swept kind with a
    null ratio is a tuner that cannot show its work."""
    bench = _scan_bench()
    bench["autotune"][0]["ms_ratio_tuned_vs_default"] = None
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "no tuned-vs-default timing ratio" in out.stderr


def test_gate_fails_on_missing_kernel_kind(tmp_path):
    bench = _scan_bench()
    bench["autotune"] = [r for r in bench["autotune"]
                         if r["kind"] != "rerank"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "missing kernel kind" in out.stderr and "rerank" in out.stderr


def test_gate_autotune_ratio_is_configurable(tmp_path):
    bench = _scan_bench()
    bench["autotune"][0]["ms_ratio_tuned_vs_default"] = 1.3
    out = _run_gate(tmp_path, bench, "--max-autotune-ratio", "1.5")
    assert out.returncode == 0, out.stdout + out.stderr


def test_gate_requires_a_probe_budget_section(tmp_path):
    out = _run_gate(tmp_path, _scan_bench(probe_budget=[]))
    assert out.returncode != 0
    assert "no 'probe_budget' section" in out.stderr


def test_gate_fails_on_malformed_probe_budget_row(tmp_path):
    bench = _scan_bench()
    del bench["probe_budget"][0]["recall_weighted"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "missing keys" in out.stderr and "recall_weighted" in out.stderr


def test_gate_fails_when_weighted_loses_to_flat(tmp_path):
    """Occupancy-weighted allocation must never cost recall at equal
    budget — losing to the flat comparator means the surplus slots went
    to the wrong lists."""
    bench = _scan_bench()
    bench["probe_budget"][0]["recall_weighted"] = 0.4  # flat is 0.5
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "below" in out.stderr and "flat recall" in out.stderr


def test_gate_fails_when_parity_row_is_not_bit_identical(tmp_path):
    """budget == nprobe * nlist must reproduce flat nprobe bit-for-bit
    (same jit program); anything else means the budget path diverged."""
    bench = _scan_bench()
    bench["probe_budget"][-1]["bit_identical"] = False
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "not bit-identical to the flat-nprobe search" in out.stderr


def test_gate_fails_without_the_parity_row(tmp_path):
    """The sweep must COVER the bit-identity operating point: dropping
    the exact-multiple budget row must not dodge the parity check."""
    bench = _scan_bench()
    bench["probe_budget"] = bench["probe_budget"][:-1]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "no parity row at budget=512" in out.stderr


def test_gate_understands_hnsw_schema(tmp_path):
    """BENCH_hnsw_scan rows carry table_bytes and no variant key; the
    gate must pair them by the bench name and apply the same invariant."""
    def bench(ratio):
        return {"bench": "hnsw_scan", "rows": [
            {"packed": False, "table_bytes": 200_000},
            {"packed": True, "table_bytes": int(200_000 * ratio)},
        ]}

    assert _run_gate(tmp_path, bench(0.53)).returncode == 0
    out = _run_gate(tmp_path, bench(0.60))
    assert out.returncode != 0
    assert "hnsw_scan" in out.stdout


def _replicated_row(replicas=2, paired_ratio=0.95, **overrides):
    row = {
        "mode": "replicated", "replicas": replicas, "router": "round-robin",
        "qps": 950.0, "qps_ratio_vs_single": paired_ratio,
        "ms_per_batch": 1.0, "latency_p50_ms": 5.0, "latency_p99_ms": 9.0,
        "scan_input_wait_frac": 0.1, "shed": 0, "failovers": 0,
        "per_replica": [
            {"replica": i, "requests": 10, "queries": 100, "shed": 0,
             "scan_input_wait_frac": 0.1, "generation": 0}
            for i in range(replicas)
        ],
    }
    row.update(overrides)
    return row


def _swap_row(**overrides):
    row = {
        "mode": "swap", "replicas": 2, "index_kind": "flat",
        "swapped_replicas": 2, "swap_s": 0.5, "queries_during_swap": 128,
        "lost": 0, "reordered": 0, "bit_identical": True, "revivals": 1,
    }
    row.update(overrides)
    return row


def _chaos_row(**overrides):
    row = {
        "mode": "chaos", "replicas": 2, "index_kind": "flat",
        "submitted": 40, "lost": 0, "reordered": 0, "bit_identical": True,
        "deadline_violations": 2, "watchdog_stalls": 1, "failovers": 4,
        "revivals": 1, "time_to_recover_s": 0.1,
        "shed_without_degradation": 30, "shed_with_degradation": 3,
        "degraded_frac": 0.9,
    }
    row.update(overrides)
    return row


def _upgrade_row(**overrides):
    row = {
        "mode": "upgrade", "replicas": 2, "index_kind": "flat",
        "from_version": "v1", "to_version": "v2",
        "swapped_replicas": 2, "swap_s": 0.1, "queries_during_swap": 128,
        "submitted": 20, "lost": 0, "reordered": 0, "bit_identical": True,
        "compat_dispatches": 8, "recall_v1": 0.9, "recall_v2": 0.8,
        "recall_floor": 0.55, "final_versions": ["v2", "v2"],
    }
    row.update(overrides)
    return row


def _bigranular_swap_row(**overrides):
    row = _swap_row(mode="bigranular_swap", reranked=True)
    row.update(overrides)
    return row


def _autoscale_row(**overrides):
    row = {
        "mode": "autoscale", "index_kind": "flat",
        "replicas_min": 1, "replicas_max": 3, "fixed_replicas": 1,
        "steady_state_replicas": 1, "submitted": 500,
        "lost": 0, "reordered": 0, "bit_identical": True,
        "shed_fixed": 200, "shed_autoscaled": 120,
        "shed_rate_fixed": 0.4, "shed_rate_autoscaled": 0.24,
        "scale_ups": 2, "scale_downs": 2,
        "max_replicas_seen": 3, "min_replicas_seen": 1,
    }
    row.update(overrides)
    return row


def _serving_bench(ratio: float, paired_ratio: float = 0.95):
    return {"bench": "serving", "rows": [
        {"mode": "sequential", "qps": 1000.0},
        {"mode": "overlapped", "qps": 1000.0 * ratio},
        _replicated_row(replicas=1, paired_ratio=1.0),
        _replicated_row(paired_ratio=paired_ratio),
        _swap_row(),
        _chaos_row(),
        _upgrade_row(),
        _bigranular_swap_row(),
        _autoscale_row(),
    ]}


def test_serving_gate_passes_when_overlapped_wins(tmp_path):
    out = _run_gate(tmp_path, _serving_bench(1.15))
    assert out.returncode == 0, out.stderr


def test_serving_gate_fails_when_pipeline_loses_throughput(tmp_path):
    out = _run_gate(tmp_path, _serving_bench(0.9))
    assert out.returncode != 0
    assert "FAIL" in out.stdout


def test_serving_gate_ratio_is_configurable(tmp_path):
    out = _run_gate(tmp_path, _serving_bench(0.9),
                    "--min-serving-ratio", "0.85")
    assert out.returncode == 0, out.stderr


def test_serving_gate_fails_on_missing_mode_row(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"] = bench["rows"][:1]  # no overlapped row
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0


# -- replica sweep (proxy tier) ---------------------------------------------


def test_serving_gate_requires_a_replicated_row(tmp_path):
    """The replica sweep is part of the schema now: a BENCH_serving.json
    without it (e.g. an emitter regression) must not pass green."""
    bench = _serving_bench(1.2)
    bench["rows"] = bench["rows"][:2]  # sequential + overlapped only
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "no 'replicated' rows" in out.stderr


def test_serving_gate_fails_on_missing_replicated_keys(tmp_path):
    bench = _serving_bench(1.2)
    del bench["rows"][3]["latency_p99_ms"]
    del bench["rows"][3]["shed"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "missing keys" in out.stderr
    assert "latency_p99_ms" in out.stderr and "shed" in out.stderr


def test_serving_gate_fails_on_missing_failover_count(tmp_path):
    bench = _serving_bench(1.2)
    del bench["rows"][3]["failovers"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "failovers" in out.stderr


def test_serving_gate_fails_on_incomplete_per_replica_entry(tmp_path):
    bench = _serving_bench(1.2)
    del bench["rows"][3]["per_replica"][1]["scan_input_wait_frac"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "per_replica[1]" in out.stderr


def test_serving_gate_fails_on_wrong_typed_per_replica(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][3]["per_replica"] = {}  # present but unparseable
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "expected a list" in out.stderr


def test_serving_gate_fails_on_per_replica_count_mismatch(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][3]["per_replica"].pop()  # 1 entry for replicas=2
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "per_replica has 1 entries" in out.stderr


def test_serving_gate_fails_below_replica_floor(tmp_path):
    out = _run_gate(tmp_path, _serving_bench(1.2, paired_ratio=0.8))
    assert out.returncode != 0
    assert "replicated tier lost throughput" in out.stderr


def test_serving_gate_replica_floor_is_configurable(tmp_path):
    out = _run_gate(tmp_path, _serving_bench(1.2, paired_ratio=0.8),
                    "--min-replica-ratio", "0.75")
    assert out.returncode == 0, out.stderr


# -- live index lifecycle (swap row) ----------------------------------------


def test_serving_gate_requires_a_swap_row(tmp_path):
    """The rolling-swap exercise is part of the schema now: a report
    without it (lifecycle emitter regression) must not pass green."""
    bench = _serving_bench(1.2)
    bench["rows"] = bench["rows"][:4]  # drop the swap row
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "no 'swap' row" in out.stderr


def test_serving_gate_fails_on_malformed_swap_row(tmp_path):
    bench = _serving_bench(1.2)
    del bench["rows"][4]["lost"]
    del bench["rows"][4]["revivals"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "missing keys" in out.stderr
    assert "lost" in out.stderr and "revivals" in out.stderr


def test_serving_gate_fails_on_lost_results_during_swap(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][4] = _swap_row(lost=2)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "lost 2 result(s)" in out.stderr


def test_serving_gate_fails_on_reordered_results_during_swap(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][4] = _swap_row(reordered=1)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "reordered 1 result(s)" in out.stderr


def test_serving_gate_fails_when_swap_breaks_bit_identity(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][4] = _swap_row(bit_identical=False)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "not bit-identical" in out.stderr


def test_serving_gate_fails_on_incomplete_rolling_swap(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][4] = _swap_row(swapped_replicas=1)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "swapped only 1/2" in out.stderr


def test_serving_gate_fails_without_a_revival(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][4] = _swap_row(revivals=0)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "no canary revival" in out.stderr


def test_serving_gate_fails_on_missing_generation(tmp_path):
    """A per-replica row without the stats generation (revival/swap
    bookkeeping) is an incomplete report."""
    bench = _serving_bench(1.2)
    del bench["rows"][3]["per_replica"][0]["generation"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "generation" in out.stderr


# -- chaos drill (fault injection row) ---------------------------------------


def test_serving_gate_requires_a_chaos_row(tmp_path):
    """The fault-injection drill is part of the schema now: a report
    without it (emitter regression) must not pass green."""
    bench = _serving_bench(1.2)
    bench["rows"] = bench["rows"][:5]  # drop the chaos row
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "no 'chaos' row" in out.stderr


def test_serving_gate_fails_on_lost_results_under_chaos(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][5] = _chaos_row(lost=3)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "lost 3 result(s)" in out.stderr


def test_serving_gate_fails_on_missing_deadline_accounting(tmp_path):
    """deadline_violations must be PRESENT even at zero — a report that
    cannot count deadline misses is an accounting hole, not a pass."""
    bench = _serving_bench(1.2)
    del bench["rows"][5]["deadline_violations"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "missing keys" in out.stderr
    assert "deadline_violations" in out.stderr


def test_serving_gate_fails_when_watchdog_missed_the_stall(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][5] = _chaos_row(watchdog_stalls=0)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "watchdog never detected" in out.stderr


def test_serving_gate_fails_when_degradation_does_not_help(tmp_path):
    """The A/B at equal load must show strictly fewer sheds with the
    effort knob enabled; equal counts mean the knob is not wired in."""
    bench = _serving_bench(1.2)
    bench["rows"][5] = _chaos_row(shed_with_degradation=30,
                                  shed_without_degradation=30)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "did not reduce shedding" in out.stderr


# -- live embedding-version migration (upgrade row) ---------------------------


def test_serving_gate_requires_an_upgrade_row(tmp_path):
    """The live v1 -> v2 migration is part of the schema now: a report
    without it (emitter regression) must not pass green."""
    bench = _serving_bench(1.2)
    bench["rows"] = bench["rows"][:6]  # drop the upgrade row
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "no 'upgrade' row" in out.stderr


def test_serving_gate_fails_on_malformed_upgrade_row(tmp_path):
    bench = _serving_bench(1.2)
    del bench["rows"][6]["recall_floor"]
    del bench["rows"][6]["compat_dispatches"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "missing keys" in out.stderr
    assert "recall_floor" in out.stderr and "compat_dispatches" in out.stderr


def test_serving_gate_fails_on_lost_results_during_upgrade(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][6] = _upgrade_row(lost=2)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "lost 2 result(s) during the version migration" in out.stderr


def test_serving_gate_fails_on_reordered_results_during_upgrade(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][6] = _upgrade_row(reordered=1)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "reordered 1 result(s)" in out.stderr


def test_serving_gate_fails_when_upgrade_breaks_bit_identity(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][6] = _upgrade_row(bit_identical=False)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "not bit-identical" in out.stderr


def test_serving_gate_fails_below_upgrade_recall_floor(tmp_path):
    """Per-version recall across the migration window is a QUALITY gate:
    degrading by version must not degrade below the row's own floor."""
    bench = _serving_bench(1.2)
    bench["rows"][6] = _upgrade_row(recall_v2=0.4)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "recall_v2=0.4000 below the recall floor" in out.stderr


def test_serving_gate_upgrade_floor_cannot_be_zeroed_out(tmp_path):
    """An emitter shipping recall_floor=0 must not self-certify: the
    gate floors it at --min-upgrade-recall (default 0.5) — which stays
    configurable for deliberately tiny smoke corpora."""
    bench = _serving_bench(1.2)
    bench["rows"][6] = _upgrade_row(recall_floor=0.0, recall_v1=0.1,
                                    recall_v2=0.1)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "below the recall floor 0.5" in out.stderr
    out = _run_gate(tmp_path, bench, "--min-upgrade-recall", "0.05")
    assert out.returncode == 0, out.stdout + out.stderr


def test_serving_gate_fails_without_a_compat_dispatch(tmp_path):
    """A 'migration' whose stream never took the cross-version hop
    proves nothing about the compat path — hard fail."""
    bench = _serving_bench(1.2)
    bench["rows"][6] = _upgrade_row(compat_dispatches=0)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "no compat dispatch" in out.stderr


def test_serving_gate_fails_on_incomplete_version_migration(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][6] = _upgrade_row(swapped_replicas=1)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "migrated only 1/2" in out.stderr


def test_serving_gate_fails_when_a_replica_misses_the_target_version(
        tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][6] = _upgrade_row(final_versions=["v2", "v1"])
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "final replica versions" in out.stderr


# -- tiered serving drill (bigranular_swap row) -------------------------------


def test_serving_gate_requires_a_bigranular_swap_row(tmp_path):
    """The tiered (coarse+rerank) rolling-swap drill is part of the
    schema now: a report without it must not pass green."""
    bench = _serving_bench(1.2)
    bench["rows"] = bench["rows"][:7]  # drop the bigranular_swap row
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "no 'bigranular_swap' row" in out.stderr


def test_serving_gate_fails_without_rerank_provenance(tmp_path):
    """bit-identical results alone do not prove the tier served the
    bi-granular path — a silent fallback to the flat index would also
    be bit-identical. Every ticket must carry reranked provenance."""
    bench = _serving_bench(1.2)
    bench["rows"][7] = _bigranular_swap_row(reranked=False)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "did not serve every query through the bi-granular rerank" \
        in out.stderr


def test_serving_gate_fails_on_lost_results_during_bigranular_swap(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][7] = _bigranular_swap_row(lost=2)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "lost 2 result(s) during the rolling swap" in out.stderr


def test_serving_gate_fails_when_bigranular_swap_breaks_bit_identity(
        tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][7] = _bigranular_swap_row(bit_identical=False)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "not bit-identical" in out.stderr


# -- shed-pressure autoscaler drill (autoscale row) ---------------------------


def test_serving_gate_requires_an_autoscale_row(tmp_path):
    """The autoscaler drill is part of the schema now: a report without
    it (emitter regression) must not pass green."""
    bench = _serving_bench(1.2)
    bench["rows"] = bench["rows"][:8]  # drop the autoscale row
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "no 'autoscale' row" in out.stderr


def test_serving_gate_fails_on_malformed_autoscale_row(tmp_path):
    bench = _serving_bench(1.2)
    del bench["rows"][8]["shed_rate_autoscaled"]
    del bench["rows"][8]["max_replicas_seen"]
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "missing keys" in out.stderr
    assert "shed_rate_autoscaled" in out.stderr
    assert "max_replicas_seen" in out.stderr


def test_serving_gate_fails_when_autoscaling_does_not_reduce_shed(tmp_path):
    """The row's reason to exist: strictly fewer sheds than the fixed
    tier on the same trace. Equal shed rates also fail — scaling up has
    to buy something."""
    bench = _serving_bench(1.2)
    bench["rows"][8] = _autoscale_row(shed_rate_autoscaled=0.4,
                                      shed_rate_fixed=0.4)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "did not reduce shedding" in out.stderr


def test_serving_gate_fails_on_lost_results_during_autoscale(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][8] = _autoscale_row(lost=3)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "lost 3 result(s)" in out.stderr


def test_serving_gate_fails_on_reordered_results_during_autoscale(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][8] = _autoscale_row(reordered=1)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "reordered 1 result(s)" in out.stderr


def test_serving_gate_fails_when_replicas_leave_spec_bounds(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][8] = _autoscale_row(max_replicas_seen=4)  # spec max is 3
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "left the TierSpec bounds" in out.stderr

    bench["rows"][8] = _autoscale_row(min_replicas_seen=0)  # spec min is 1
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "left the TierSpec bounds" in out.stderr


def test_serving_gate_fails_on_unequal_steady_state_comparison(tmp_path):
    """A tier that never settles back to the fixed tier's size is not a
    fair shed comparison — more steady-state replicas would win on
    capacity alone."""
    bench = _serving_bench(1.2)
    bench["rows"][8] = _autoscale_row(steady_state_replicas=2)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "equal steady-state capacity" in out.stderr


def test_serving_gate_fails_when_autoscaler_never_scaled_up(tmp_path):
    bench = _serving_bench(1.2)
    bench["rows"][8] = _autoscale_row(scale_ups=0)
    out = _run_gate(tmp_path, bench)
    assert out.returncode != 0
    assert "no scale-up" in out.stderr


# -- docs lint (scripts/check_docs_links.py) ---------------------------------

DOCS_LINT = os.path.join(
    os.path.dirname(__file__), "..", "scripts", "check_docs_links.py"
)


def _run_docs_lint(repo):
    return subprocess.run(
        [sys.executable, DOCS_LINT, str(repo)],
        capture_output=True, text=True, timeout=60,
    )


def _docs_lint_repo(tmp_path, readme="# hi\n[ok](docs/GOOD.md)\n",
                    launch_src='"""documented."""\nX = 1\n'):
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "GOOD.md").write_text("# good\n")
    (tmp_path / "README.md").write_text(readme)
    launch = tmp_path / "src" / "repro" / "launch"
    launch.mkdir(parents=True)
    (launch / "mod.py").write_text(launch_src)
    return tmp_path


def test_docs_lint_passes_healthy_repo(tmp_path):
    repo = _docs_lint_repo(tmp_path)
    out = _run_docs_lint(repo)
    assert out.returncode == 0, out.stderr


def test_docs_lint_fails_on_broken_relative_link(tmp_path):
    repo = _docs_lint_repo(tmp_path, readme="[dead](docs/MISSING.md)\n")
    out = _run_docs_lint(repo)
    assert out.returncode != 0
    assert "broken link" in out.stderr and "MISSING.md" in out.stderr


def test_docs_lint_ignores_external_links_and_code_blocks(tmp_path):
    repo = _docs_lint_repo(
        tmp_path,
        readme=("[ext](https://example.com/x) [anchor](#sec)\n"
                "```\n[fake](not/a/file.md)\n```\n"
                "inline `[q](also/fake.md)` span\n"),
    )
    out = _run_docs_lint(repo)
    assert out.returncode == 0, out.stderr


def test_docs_lint_fails_on_undocumented_launch_module(tmp_path):
    repo = _docs_lint_repo(tmp_path, launch_src="X = 1\n")
    out = _run_docs_lint(repo)
    assert out.returncode != 0
    assert "missing module docstring" in out.stderr


def test_docs_lint_passes_this_repo(tmp_path):
    """The real README/docs/launch tree must satisfy its own lint."""
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    out = _run_docs_lint(repo)
    assert out.returncode == 0, out.stderr


def test_gate_accepts_real_emitter_output(tmp_path, monkeypatch):
    """End-to-end: the actual tiny-corpus emitter satisfies the gate."""
    repo_root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    if repo_root not in sys.path:  # bare `pytest` does not add the cwd
        sys.path.insert(0, repo_root)
    from benchmarks.table5_search_latency import emit_sdc_scan_json

    # keep the emitter's autotune sweep out of the user's real tune cache
    monkeypatch.setenv("REPRO_BEBR_CACHE", str(tmp_path / "tune-cache"))
    path = tmp_path / "BENCH_sdc_scan.json"
    emit_sdc_scan_json(path=str(path), n_docs=1024, queries=4)
    out = subprocess.run(
        [sys.executable, GATE, str(path)],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr


def test_serving_gate_accepts_real_emitter_schema(tmp_path):
    """End-to-end: the serving emitter's replica sweep satisfies the
    SCHEMA half of the gate (the QPS floors are waived — a micro corpus
    in a loaded test process is not a throughput measurement)."""
    repo_root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from benchmarks.table5_search_latency import emit_serving_json

    path = tmp_path / "BENCH_serving.json"
    # the upgrade row trains its own mini-world (phi_v1 + the bc-trained
    # phi_v2), so this end-to-end run includes a real training loop
    emit_serving_json(path=str(path), n_docs=512, batch=8, n_batches=6,
                      trials=2)
    out = subprocess.run(
        [sys.executable, GATE, str(path),
         "--min-serving-ratio", "0", "--min-replica-ratio", "0"],
        capture_output=True, text=True, timeout=180,
    )
    assert out.returncode == 0, out.stdout + out.stderr
