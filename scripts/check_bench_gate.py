#!/usr/bin/env python
"""CI bench gate: fail when the packed-scan byte invariant regresses.

ROADMAP invariant: int4 nibble-packed code streaming must keep scan bytes
at <= 0.55x the unpacked scan for every engine variant (0.5x codes + small
per-doc metadata that packing cannot shrink). A PR that silently widens
the packed layout, forgets to pack a new scan path, or inflates per-doc
metadata shows up here as a ratio creep past the threshold.

    python scripts/check_bench_gate.py BENCH_sdc_scan.json \
        [--max-packed-ratio 0.55]

Reads the ``rows`` emitted by ``benchmarks/run.py --only bench_sdc_scan``
(each row: variant, packed, bytes_scanned), pairs packed/unpacked rows per
variant, and exits non-zero if any ratio exceeds the threshold — or if a
variant is missing one side of the pair (a gate that can't see the packed
row must not pass green).

Also understands ``BENCH_hnsw_scan.json`` (rows keyed by ``packed`` only,
bytes in ``table_bytes`` — the device footprint of the neighbor-block
tables), so the graph-search tables are held to the same invariant.

``BENCH_serving.json`` (rows keyed by ``mode``) is gated differently:
the double-buffered pipeline must not lose throughput to the sequential
encode+scan loop it replaced — overlapped QPS >= --min-serving-ratio x
sequential QPS (default 1.0). Both rows must be present; the emitter
reports best-of-N interleaved runs, so the ratio is not noise-driven.

The replica sweep ("replicated" rows, added with launch/proxy.py) is
held to a schema AND a floor: every replicated row must carry the full
routing telemetry (replicas, router, qps, latency percentiles, shed/
failover counts, and a per-replica breakdown — missing keys are a hard
failure, because a report the proxy dashboards cannot parse must not
pass green), at least one replicated row must exist, and every N>1
row's BEST paired-trial QPS ratio vs the replicas=1 tier run (same
trial, same code path — a genuine tier cost fails every paired trial,
while the host's noise phases move even identical-code paired medians
by +-30%) must be >= --min-replica-ratio (default 0.9: on a
shared-core CI host replication cannot scale, but the router must not
COST meaningful throughput either). The per-run median rides along in
the row for the perf record.

The live index lifecycle ("swap" row, added with launch/lifecycle.py)
is gated on CORRECTNESS, not speed: the emitter performs a rolling
per-replica index swap under continuous traffic plus an injected
transient fault + canary revival, and the gate hard-fails when any
result was lost or reordered, when results were not bit-identical to
the sequential loop, when the rolling swap did not cover every replica,
or when no revival was recorded. Per-replica rows must also carry the
stats ``generation`` (bumped on every swap/revival so a revived
replica's counters are not conflated with its previous run).

The chaos drill ("chaos" row, added with launch/faults.py) extends the
same correctness treatment to the robustness machinery: a stuck
(non-raising) scan must be caught by the watchdog and survived with
zero lost results, per-query deadline misses must be *counted*
(``deadline_violations`` present — an accounting hole is a hard fail
even at zero misses), a revival must follow the stall clearing, and
the degradation A/B at equal overload must shed strictly fewer
requests with the effort knob enabled than without it.

The live embedding-version migration ("upgrade" row, added with the
version-aware serving tier) gates the compat-encoded upgrade path: the
emitter runs mixed v1/v2 traffic through a 2-replica tier while a
rolling swap migrates it from the v1 to the v2 index, with the
CompatibilityMatrix covering the cross-version window. The gate
hard-fails when any result was lost or reordered, when answers were not
bit-identical to the sequential reference for their
(query_version, served_by_version) pair, when per-version recall over
the migration window drops below the row's embedded ``recall_floor``
(itself floored by --min-upgrade-recall so an emitter cannot pass by
shipping a zero floor), when no compat dispatch was recorded (a
"migration" that never exercised the cross-version hop proves nothing),
when the swap did not cover every replica, or when any replica does not
finish on the target version.

The bi-granular sweep ("bigranular" section of BENCH_sdc_scan.json,
added with the coarse-scan + fine-rerank mode) is gated on the memory
hierarchy actually paying off: every row must carry the full schema
(coarse_levels, k_coarse, recall_rerank/recall_coarse, and the
coarse/fine/full byte totals), rerank recall must never fall below the
coarse-only recall it refines, and at ``coarse_levels = levels // 2``
the hot coarse tier's bytes must be <= --max-coarse-ratio x the
full-level bytes (default 0.6: half the levels plus the per-doc
metadata packing cannot shrink). A section that is missing, empty, or
missing its half-levels row hard-fails — a tiered mode the bench
cannot see must not pass green.

The bits-per-dimension sweep ("bits_sweep" section, same file) is
gated on schema and byte monotonicity only — recall at a given level
count is a modelling choice, not an invariant: every row carries
n_levels/packed/ms/recall/bytes_scanned/index_bytes, the serialized
``index_bytes`` must grow monotonically with n_levels within each
packed state, and each level's packed scan must hold the same
--max-packed-ratio byte invariant as the main rows.

The block-plan autotuner record ("autotune" section of
BENCH_sdc_scan.json, added with the adaptive query execution PR) is
gated on the tuner never LOSING to the shipped defaults: one row per
kernel kind (scan / gather / rerank) with the default and tuned launch
geometry plus the sweep's own paired timings (the default plan is timed
as a candidate on the same operands as every challenger, so the ratio
is noise-immune by construction). Every kind must be present, and
``ms_ratio_tuned_vs_default`` must be <= --max-autotune-ratio (default
1.0). A swept kind with no timings (ratio null) hard-fails —
un-sweepable kinds (gather's corpus-fixed geometry) must report the
default plan with ratio exactly 1.0 instead.

The probe-budget sweep ("probe_budget" section, same file) gates the
occupancy-weighted IVF probe allocation: per global budget B, recall@k
for the weighted allocation and for the flat comparator (equal weights,
same budget machinery, same total scan work). Weighted recall must
never fall below flat recall at equal budget (both are deterministic
seeded scans, so ties pass and the check cannot flake), and the sweep
must include the exact-multiple parity row ``B = nprobe * nlist`` with
``bit_identical`` true — at exact multiples the per-centroid thresholds
are uniform and the budgeted search must reproduce the flat-nprobe
search bit-for-bit (ids AND scores, weighted and flat alike).

The tiered serving drill ("bigranular_swap" row of BENCH_serving.json)
re-runs the rolling-swap correctness record with a coarse+rerank
lifecycle builder serving the tier: the same lost/reordered/
bit-identity/revival checks apply, plus ``reranked`` must be true —
every ticket must have carried rerank provenance, proving the tier
actually served the bi-granular path (not a silent fallback to the
flat index).
"""

from __future__ import annotations

import argparse
import json
import sys


def _row_bytes(row: dict):
    return row.get("bytes_scanned", row.get("table_bytes"))


# Replica-sweep schema: a replicated row that cannot be parsed into the
# proxy-level report (QPS, latency, shed, per-replica breakdown) must
# fail the gate, not silently pass with holes.
REPLICATED_ROW_KEYS = (
    "replicas", "router", "qps", "qps_ratio_vs_single", "ms_per_batch",
    "latency_p50_ms", "latency_p99_ms", "scan_input_wait_frac",
    "shed", "failovers", "per_replica",
)
PER_REPLICA_KEYS = ("replica", "requests", "queries", "shed",
                    "scan_input_wait_frac", "generation")

# Live index lifecycle row (added with launch/lifecycle.py): a rolling
# per-replica swap under continuous traffic plus a canary revival. The
# row is not a throughput measurement — it is a CORRECTNESS record, so
# the gate hard-fails on any lost or reordered result, any non-bit-
# identical answer, an incomplete rolling swap, or a missing revival.
SWAP_ROW_KEYS = (
    "replicas", "index_kind", "swapped_replicas", "swap_s",
    "queries_during_swap", "lost", "reordered", "bit_identical", "revivals",
)

# Chaos drill row (added with launch/faults.py): a stuck (non-raising)
# scan under traffic + per-query deadlines + the degradation A/B. Like
# the swap row it is a CORRECTNESS record: lost results, a missing
# deadline accounting, an undetected stall, a missing revival, or a
# degradation run that sheds MORE than its baseline all hard-fail.
CHAOS_ROW_KEYS = (
    "replicas", "lost", "reordered", "bit_identical",
    "deadline_violations", "watchdog_stalls", "failovers", "revivals",
    "time_to_recover_s", "shed_without_degradation",
    "shed_with_degradation", "degraded_frac",
)

# Live embedding-version migration row (added with the version-aware
# serving tier): mixed v1/v2 traffic over a rolling v1 -> v2 index swap,
# cross-version requests served through the CompatibilityMatrix. A
# CORRECTNESS record like the swap/chaos rows, plus a QUALITY floor:
# per-version recall across the migration window must hold the row's
# own recall_floor (which --min-upgrade-recall keeps honest).
UPGRADE_ROW_KEYS = (
    "replicas", "index_kind", "from_version", "to_version",
    "swapped_replicas", "swap_s", "queries_during_swap",
    "lost", "reordered", "bit_identical", "compat_dispatches",
    "recall_v1", "recall_v2", "recall_floor", "final_versions",
)

# Bi-granular sweep row (BENCH_sdc_scan.json "bigranular" section,
# added with the coarse-scan + fine-rerank mode): the tiered layout's
# quality/traffic record. recall_rerank must refine (>=) recall_coarse
# and the hot coarse tier must actually be small.
BIGRANULAR_ROW_KEYS = (
    "coarse_levels", "k_coarse", "packed", "ms",
    "recall_rerank", "recall_coarse",
    "coarse_bytes_scanned", "fine_bytes_scanned", "full_bytes_scanned",
)

# Bits-per-dimension sweep row (BENCH_sdc_scan.json "bits_sweep"
# section): schema + byte monotonicity only — recall is recorded, not
# gated (the level count is a quality/cost knob, not an invariant).
BITS_SWEEP_ROW_KEYS = (
    "n_levels", "packed", "ms", "recall", "bytes_scanned", "index_bytes",
)

# Block-plan autotuner row (BENCH_sdc_scan.json "autotune" section):
# one row per kernel kind. The timings come from the tuner's own sweep
# (default timed as a candidate alongside every challenger), so the
# gated ratio is paired-by-construction. default_ms/tuned_ms are
# nullable (un-sweepable kinds), so they are not in the hard-key set —
# a swept kind with a null RATIO still fails below.
AUTOTUNE_ROW_KEYS = (
    "kind", "backend", "block_q_default", "block_n_default",
    "block_q", "block_n", "source",
)
AUTOTUNE_KINDS = ("scan", "gather", "rerank")

# Shed-pressure autoscaler row (added with launch/autoscale.py): the
# same bursty open-loop trace replayed against a fixed single-replica
# tier and an autoscaled tier that is allowed to grow to
# replicas_max but must settle back to the fixed tier's size. A
# CORRECTNESS record (zero lost/reordered, bit-identical answers)
# plus the autoscaler's reason to exist: it must shed strictly less
# than the fixed tier at equal steady-state capacity, and its
# replica count must never leave the TierSpec bounds.
AUTOSCALE_ROW_KEYS = (
    "index_kind", "replicas_min", "replicas_max", "fixed_replicas",
    "steady_state_replicas", "submitted", "lost", "reordered",
    "bit_identical", "shed_fixed", "shed_autoscaled",
    "shed_rate_fixed", "shed_rate_autoscaled",
    "scale_ups", "scale_downs", "max_replicas_seen", "min_replicas_seen",
)

# Probe-budget sweep row (BENCH_sdc_scan.json "probe_budget" section):
# occupancy-weighted vs flat allocation at equal global budget. The
# parity row (budget == nprobe * nlist) additionally carries
# ``bit_identical``.
PROBE_BUDGET_ROW_KEYS = (
    "probe_budget", "avg_probes_per_query", "recall_weighted", "recall_flat",
)


def _check_upgrade_row(row: dict, label: str, min_recall: float) -> int:
    errors = 0
    missing = [k for k in UPGRADE_ROW_KEYS if k not in row or row[k] is None]
    if missing:
        print(f"serving gate: {label} missing keys {missing}",
              file=sys.stderr)
        return errors + 1  # can't judge an incomplete row further
    if row["lost"] != 0:
        print(f"serving gate: {label} lost {row['lost']} result(s) during "
              "the version migration", file=sys.stderr)
        errors += 1
    if row["reordered"] != 0:
        print(f"serving gate: {label} reordered {row['reordered']} "
              "result(s) during the version migration", file=sys.stderr)
        errors += 1
    if row["bit_identical"] is not True:
        print(f"serving gate: {label} answers not bit-identical to the "
              "sequential reference for their (query_version, "
              "served_by_version) pair", file=sys.stderr)
        errors += 1
    if row["swapped_replicas"] != row["replicas"]:
        print(f"serving gate: {label} migrated only "
              f"{row['swapped_replicas']}/{row['replicas']} replicas",
              file=sys.stderr)
        errors += 1
    if row["compat_dispatches"] < 1:
        print(f"serving gate: {label} recorded no compat dispatch — the "
              "cross-version hop was never exercised", file=sys.stderr)
        errors += 1
    floor = max(float(row["recall_floor"]), min_recall)
    for key in ("recall_v1", "recall_v2"):
        if row[key] < floor:
            print(f"serving gate: {label} {key}={row[key]:.4f} below the "
                  f"recall floor {floor}", file=sys.stderr)
            errors += 1
    bad = [v for v in row["final_versions"] if v != row["to_version"]]
    if bad or len(row["final_versions"]) != row["replicas"]:
        print(f"serving gate: {label} final replica versions "
              f"{row['final_versions']} != {row['replicas']} x "
              f"'{row['to_version']}'", file=sys.stderr)
        errors += 1
    return errors


def _check_autoscale_row(row: dict, label: str) -> int:
    errors = 0
    missing = [k for k in AUTOSCALE_ROW_KEYS if k not in row or row[k] is None]
    if missing:
        print(f"serving gate: {label} missing keys {missing}",
              file=sys.stderr)
        return errors + 1  # can't judge an incomplete row further
    if row["lost"] != 0:
        print(f"serving gate: {label} lost {row['lost']} result(s) across "
              "the scale-up/scale-down churn", file=sys.stderr)
        errors += 1
    if row["reordered"] != 0:
        print(f"serving gate: {label} reordered {row['reordered']} "
              "result(s) across the scale-up/scale-down churn",
              file=sys.stderr)
        errors += 1
    if row["bit_identical"] is not True:
        print(f"serving gate: {label} answered results not bit-identical "
              "to the sequential loop", file=sys.stderr)
        errors += 1
    if row["steady_state_replicas"] != row["fixed_replicas"]:
        print(f"serving gate: {label} settled at "
              f"{row['steady_state_replicas']} replica(s), not the fixed "
              f"tier's {row['fixed_replicas']} — the shed comparison is "
              "only fair at equal steady-state capacity", file=sys.stderr)
        errors += 1
    if row["shed_rate_autoscaled"] >= row["shed_rate_fixed"]:
        print(f"serving gate: {label} autoscaling did not reduce shedding "
              f"(shed rate {row['shed_rate_autoscaled']:.4f} autoscaled vs "
              f"{row['shed_rate_fixed']:.4f} fixed on the same trace)",
              file=sys.stderr)
        errors += 1
    if row["scale_ups"] < 1:
        print(f"serving gate: {label} recorded no scale-up — the burst "
              "never triggered the control loop", file=sys.stderr)
        errors += 1
    if not (row["replicas_min"] <= row["min_replicas_seen"]
            <= row["max_replicas_seen"] <= row["replicas_max"]):
        print(f"serving gate: {label} replica count left the TierSpec "
              f"bounds: saw [{row['min_replicas_seen']}, "
              f"{row['max_replicas_seen']}] outside "
              f"[{row['replicas_min']}, {row['replicas_max']}]",
              file=sys.stderr)
        errors += 1
    return errors


def _check_chaos_row(row: dict, label: str) -> int:
    errors = 0
    missing = [k for k in CHAOS_ROW_KEYS if k not in row or row[k] is None]
    if missing:
        print(f"serving gate: {label} missing keys {missing}",
              file=sys.stderr)
        return errors + 1  # can't judge an incomplete row further
    if row["lost"] != 0:
        print(f"serving gate: {label} lost {row['lost']} result(s) — every "
              "request must resolve or be accounted (shed/deadline)",
              file=sys.stderr)
        errors += 1
    if row["reordered"] != 0:
        print(f"serving gate: {label} reordered {row['reordered']} "
              "result(s) across the stall failover", file=sys.stderr)
        errors += 1
    if row["bit_identical"] is not True:
        print(f"serving gate: {label} answered results not bit-identical "
              "to the sequential loop", file=sys.stderr)
        errors += 1
    if row["watchdog_stalls"] < 1:
        print(f"serving gate: {label} watchdog never detected the injected "
              "stuck scan", file=sys.stderr)
        errors += 1
    if row["revivals"] < 1:
        print(f"serving gate: {label} recorded no revival after the stall "
              "cleared", file=sys.stderr)
        errors += 1
    if row["shed_with_degradation"] >= row["shed_without_degradation"]:
        print(f"serving gate: {label} degradation did not reduce shedding "
              f"({row['shed_with_degradation']} with vs "
              f"{row['shed_without_degradation']} without at equal load)",
              file=sys.stderr)
        errors += 1
    return errors


def _check_swap_row(row: dict, label: str) -> int:
    errors = 0
    missing = [k for k in SWAP_ROW_KEYS if k not in row or row[k] is None]
    if missing:
        print(f"serving gate: {label} missing keys {missing}",
              file=sys.stderr)
        return errors + 1  # can't judge an incomplete row further
    if row["lost"] != 0:
        print(f"serving gate: {label} lost {row['lost']} result(s) during "
              "the rolling swap", file=sys.stderr)
        errors += 1
    if row["reordered"] != 0:
        print(f"serving gate: {label} reordered {row['reordered']} "
              "result(s) during the rolling swap", file=sys.stderr)
        errors += 1
    if row["bit_identical"] is not True:
        print(f"serving gate: {label} results not bit-identical to the "
              "sequential loop across the swap", file=sys.stderr)
        errors += 1
    if row["swapped_replicas"] != row["replicas"]:
        print(f"serving gate: {label} swapped only "
              f"{row['swapped_replicas']}/{row['replicas']} replicas",
              file=sys.stderr)
        errors += 1
    if row["revivals"] < 1:
        print(f"serving gate: {label} recorded no canary revival "
              "(re-probe must revive the injected transient fault)",
              file=sys.stderr)
        errors += 1
    return errors


def _check_replicated_schema(row: dict, label: str) -> int:
    """Hard-fail on any missing key in a replicated row (returns #errors)."""
    errors = 0
    missing = [k for k in REPLICATED_ROW_KEYS
               if k not in row or row[k] is None]
    if missing:
        print(f"serving gate: {label} missing keys {missing}",
              file=sys.stderr)
        errors += 1
    per = row.get("per_replica")
    if per is not None and not isinstance(per, list):
        # present-but-unparseable must fail, same as missing
        print(f"serving gate: {label} per_replica is "
              f"{type(per).__name__}, expected a list", file=sys.stderr)
        errors += 1
    elif isinstance(per, list):
        if isinstance(row.get("replicas"), int) and len(per) != row["replicas"]:
            print(f"serving gate: {label} per_replica has {len(per)} "
                  f"entries for replicas={row['replicas']}", file=sys.stderr)
            errors += 1
        for i, pr in enumerate(per):
            pr_missing = [k for k in PER_REPLICA_KEYS
                          if k not in pr or pr[k] is None]
            if pr_missing:
                print(f"serving gate: {label} per_replica[{i}] missing "
                      f"keys {pr_missing}", file=sys.stderr)
                errors += 1
    return errors


def check_serving(bench: dict, min_ratio: float,
                  min_replica_ratio: float,
                  min_upgrade_recall: float = 0.5) -> int:
    """Overlapped QPS >= min_ratio x sequential, replicated QPS >=
    min_replica_ratio x overlapped, replica-sweep schema complete,
    swap/chaos/upgrade correctness rows present and clean."""
    rows = bench.get("rows", [])
    qps = {r.get("mode"): r.get("qps") for r in rows
           if r.get("mode") in ("sequential", "overlapped")}
    seq, ovl = qps.get("sequential"), qps.get("overlapped")
    print("mode,replicas,qps")
    for r in rows:
        if "qps" not in r:
            continue  # lifecycle rows carry swap metrics, not throughput
        print(f"{r.get('mode')},{r.get('replicas', 1)},{r.get('qps')}")
    if seq is None or ovl is None:
        print("serving gate: need both a 'sequential' and an 'overlapped' "
              "row with qps", file=sys.stderr)
        return 1
    if seq <= 0:
        print(f"serving gate: bad sequential qps {seq}", file=sys.stderr)
        return 1
    failures = 0
    # Prefer the emitter's best paired-trial ratio (each trial runs the
    # two modes adjacently, so host-noise phases cancel; a genuinely
    # slower pipeline fails every trial); fall back to the best-of qps
    # ratio for reports that predate it.
    ovl_row = next(r for r in rows if r.get("mode") == "overlapped")
    ratio = ovl_row.get("qps_ratio_vs_sequential")
    if ratio is None:
        ratio = ovl / seq
    ok = ratio >= min_ratio
    print(f"overlapped/sequential,{ratio:.4f},limit>={min_ratio},"
          f"{'ok' if ok else 'FAIL'}")
    if not ok:
        print(f"serving gate: overlapped pipeline lost throughput "
              f"(ratio {ratio:.4f} < {min_ratio})", file=sys.stderr)
        failures += 1

    replicated = [r for r in rows if r.get("mode") == "replicated"]
    if not replicated:
        print("serving gate: no 'replicated' rows — the replica sweep "
              "must be emitted (launch/proxy.py tier)", file=sys.stderr)
        return 1
    swap_rows = [r for r in rows if r.get("mode") == "swap"]
    if not swap_rows:
        print("serving gate: no 'swap' row — the live index lifecycle "
              "(rolling swap + canary revival, launch/lifecycle.py) must "
              "be exercised and emitted", file=sys.stderr)
        return 1
    for r in swap_rows:
        label = f"swap row (index_kind={r.get('index_kind')})"
        failures += _check_swap_row(r, label)
        if "lost" in r:
            print(f"swap({r.get('index_kind')}),lost={r.get('lost')},"
                  f"reordered={r.get('reordered')},"
                  f"bit_identical={r.get('bit_identical')},"
                  f"revivals={r.get('revivals')}")
    chaos_rows = [r for r in rows if r.get("mode") == "chaos"]
    if not chaos_rows:
        print("serving gate: no 'chaos' row — the fault-injection drill "
              "(stuck scan + deadlines + degradation, launch/faults.py) "
              "must be exercised and emitted", file=sys.stderr)
        return 1
    for r in chaos_rows:
        failures += _check_chaos_row(r, "chaos row")
        if "lost" in r:
            print(f"chaos,lost={r.get('lost')},"
                  f"deadline_violations={r.get('deadline_violations')},"
                  f"stalls={r.get('watchdog_stalls')},"
                  f"revivals={r.get('revivals')},"
                  f"shed={r.get('shed_without_degradation')}->"
                  f"{r.get('shed_with_degradation')}")
    upgrade_rows = [r for r in rows if r.get("mode") == "upgrade"]
    if not upgrade_rows:
        print("serving gate: no 'upgrade' row — the live embedding-version "
              "migration (compat-gated rolling v1 -> v2 swap, version-aware "
              "serving tier) must be exercised and emitted", file=sys.stderr)
        return 1
    for r in upgrade_rows:
        label = (f"upgrade row ({r.get('from_version')} -> "
                 f"{r.get('to_version')})")
        failures += _check_upgrade_row(r, label, min_upgrade_recall)
        if "lost" in r:
            print(f"upgrade,lost={r.get('lost')},"
                  f"reordered={r.get('reordered')},"
                  f"bit_identical={r.get('bit_identical')},"
                  f"compat_dispatches={r.get('compat_dispatches')},"
                  f"recall_v1={r.get('recall_v1')},"
                  f"recall_v2={r.get('recall_v2')},"
                  f"final={r.get('final_versions')}")
    bg_rows = [r for r in rows if r.get("mode") == "bigranular_swap"]
    if not bg_rows:
        print("serving gate: no 'bigranular_swap' row — the tiered "
              "(coarse-scan + fine-rerank) serving drill must be exercised "
              "and emitted", file=sys.stderr)
        return 1
    for r in bg_rows:
        label = f"bigranular_swap row (index_kind={r.get('index_kind')})"
        failures += _check_swap_row(r, label)
        # the same correctness record as the plain swap, PLUS proof the
        # tier actually served the rerank path: every resolved ticket
        # must have carried reranked provenance.
        if r.get("reranked") is not True:
            print(f"serving gate: {label} reranked={r.get('reranked')} — "
                  "the tier did not serve every query through the "
                  "bi-granular rerank path", file=sys.stderr)
            failures += 1
        if "lost" in r:
            print(f"bigranular_swap,lost={r.get('lost')},"
                  f"reordered={r.get('reordered')},"
                  f"bit_identical={r.get('bit_identical')},"
                  f"reranked={r.get('reranked')}")
    autoscale_rows = [r for r in rows if r.get("mode") == "autoscale"]
    if not autoscale_rows:
        print("serving gate: no 'autoscale' row — the shed-pressure "
              "autoscaler drill (bursty trace, autoscaled vs fixed tier, "
              "launch/autoscale.py) must be exercised and emitted",
              file=sys.stderr)
        return 1
    for r in autoscale_rows:
        label = f"autoscale row (index_kind={r.get('index_kind')})"
        failures += _check_autoscale_row(r, label)
        if "lost" in r:
            print(f"autoscale,lost={r.get('lost')},"
                  f"reordered={r.get('reordered')},"
                  f"bit_identical={r.get('bit_identical')},"
                  f"shed_rate={r.get('shed_rate_fixed')}->"
                  f"{r.get('shed_rate_autoscaled')},"
                  f"replicas_seen=[{r.get('min_replicas_seen')},"
                  f"{r.get('max_replicas_seen')}],"
                  f"steady={r.get('steady_state_replicas')}")
    for r in replicated:
        label = f"replicated row (replicas={r.get('replicas')})"
        failures += _check_replicated_schema(r, label)
        if r.get("replicas") == 1:
            continue  # the baseline row gates nothing (ratio vs itself)
        # The gated ratio is the emitter's BEST per-interleaved-trial
        # ratio vs the replicas=1 run (same trial, same code path, so
        # host noise cancels; a genuine tier cost fails every paired
        # trial). The per-run median rides along in the row for the
        # perf record.
        rratio = r.get("qps_ratio_vs_single")
        if rratio is None:
            continue  # already counted by the schema check
        rok = rratio >= min_replica_ratio
        print(f"replicated(x{r.get('replicas')})/replicated(x1),{rratio:.4f},"
              f"limit>={min_replica_ratio},{'ok' if rok else 'FAIL'}")
        if not rok:
            print(f"serving gate: replicated tier lost throughput "
                  f"(paired-trial ratio {rratio:.4f} < {min_replica_ratio})",
                  file=sys.stderr)
            failures += 1
    return 1 if failures else 0


def check_bigranular(bench: dict, max_coarse_ratio: float) -> int:
    """Gate the coarse-scan + fine-rerank sweep (returns #failures).

    Three invariants per row: full schema, rerank recall >= the
    coarse-only recall it refines, and (at coarse_levels = levels // 2,
    the acceptance point) coarse bytes <= max_coarse_ratio x full-level
    bytes. The half-levels row must EXIST — a sweep that skips the
    gated operating point must not pass green.
    """
    section = bench.get("bigranular")
    if not section:
        print("bench gate: no 'bigranular' section — the coarse-scan + "
              "fine-rerank sweep must be emitted", file=sys.stderr)
        return 1
    levels = bench.get("levels")
    half = max(1, levels // 2) if isinstance(levels, int) else None
    failures = 0
    saw_half = False
    print("bigranular: coarse_levels,k_coarse,recall_rerank,recall_coarse,"
          "coarse_ratio,status")
    for i, r in enumerate(section):
        missing = [k for k in BIGRANULAR_ROW_KEYS
                   if k not in r or r[k] is None]
        if missing:
            print(f"bench gate: bigranular[{i}] missing keys {missing}",
                  file=sys.stderr)
            failures += 1
            continue
        errs = []
        if r["recall_rerank"] < r["recall_coarse"]:
            errs.append(f"rerank recall {r['recall_rerank']:.4f} below "
                        f"coarse-only recall {r['recall_coarse']:.4f}")
        full = r["full_bytes_scanned"]
        ratio = r["coarse_bytes_scanned"] / full if full > 0 else None
        if ratio is None:
            errs.append("bad full_bytes_scanned")
        elif half is not None and r["coarse_levels"] == half:
            saw_half = True
            if ratio > max_coarse_ratio:
                errs.append(f"coarse tier too large: {ratio:.4f} of "
                            f"full-level bytes > {max_coarse_ratio} at "
                            f"coarse_levels={half}")
        print(f"{r['coarse_levels']},{r['k_coarse']},"
              f"{r['recall_rerank']:.4f},{r['recall_coarse']:.4f},"
              f"{'?' if ratio is None else f'{ratio:.4f}'},"
              f"{'FAIL' if errs else 'ok'}")
        for e in errs:
            print(f"bench gate: bigranular[{i}] {e}", file=sys.stderr)
        failures += len(errs)
    if half is not None and not saw_half:
        print(f"bench gate: bigranular sweep has no row at "
              f"coarse_levels={half} (= levels // 2), the gated operating "
              "point", file=sys.stderr)
        failures += 1
    return failures


def check_bits_sweep(bench: dict, max_ratio: float) -> int:
    """Gate the bits-per-dimension sweep (returns #failures): schema,
    packed-byte invariant per level, and serialized index_bytes
    monotone nondecreasing in n_levels within each packed state."""
    section = bench.get("bits_sweep")
    if not section:
        print("bench gate: no 'bits_sweep' section — the bits-per-"
              "dimension sweep must be emitted", file=sys.stderr)
        return 1
    failures = 0
    by_state: dict = {}
    for i, r in enumerate(section):
        missing = [k for k in BITS_SWEEP_ROW_KEYS
                   if k not in r or r[k] is None]
        if missing:
            print(f"bench gate: bits_sweep[{i}] missing keys {missing}",
                  file=sys.stderr)
            failures += 1
            continue
        by_state.setdefault(bool(r["packed"]), {})[int(r["n_levels"])] = r
    print("bits_sweep: n_levels,packed_bytes,unpacked_bytes,ratio,status")
    for n in sorted(by_state.get(False, {})):
        pair = by_state.get(True, {}).get(n)
        if pair is None:
            print(f"bench gate: bits_sweep n_levels={n} has no packed row",
                  file=sys.stderr)
            failures += 1
            continue
        p, u = pair["bytes_scanned"], by_state[False][n]["bytes_scanned"]
        if u <= 0:
            print(f"bench gate: bits_sweep n_levels={n} bad bytes",
                  file=sys.stderr)
            failures += 1
            continue
        ratio = p / u
        ok = ratio <= max_ratio
        print(f"{n},{p},{u},{ratio:.4f},{'ok' if ok else 'FAIL'}")
        if not ok:
            print(f"bench gate: bits_sweep n_levels={n} packed scan bytes "
                  f"ratio {ratio:.4f} > {max_ratio}", file=sys.stderr)
            failures += 1
    for packed, rows in sorted(by_state.items()):
        ns = sorted(rows)
        for a, b in zip(ns, ns[1:]):
            if rows[b]["index_bytes"] < rows[a]["index_bytes"]:
                print(f"bench gate: bits_sweep index_bytes not monotone in "
                      f"n_levels (packed={packed}): {rows[b]['index_bytes']} "
                      f"at {b} levels < {rows[a]['index_bytes']} at {a}",
                      file=sys.stderr)
                failures += 1
    return failures


def check_autotune(bench: dict, max_autotune_ratio: float) -> int:
    """Gate the block-plan autotuner record (returns #failures): schema,
    every kernel kind present, and the tuned plan never losing to the
    default in the tuner's own paired sweep (ratio <= max ratio; a
    swept kind with no ratio is a hard fail — a tuner that cannot show
    its timings must not pass green)."""
    section = bench.get("autotune")
    if not section:
        print("bench gate: no 'autotune' section — the block-plan "
              "autotuner record must be emitted", file=sys.stderr)
        return 1
    failures = 0
    seen = set()
    print("autotune: kind,default,tuned,ratio,limit,status")
    for i, r in enumerate(section):
        missing = [k for k in AUTOTUNE_ROW_KEYS if k not in r or r[k] is None]
        if missing:
            print(f"bench gate: autotune[{i}] missing keys {missing}",
                  file=sys.stderr)
            failures += 1
            continue
        seen.add(r["kind"])
        ratio = r.get("ms_ratio_tuned_vs_default")
        if ratio is None:
            print(f"bench gate: autotune[{i}] (kind={r['kind']}) has no "
                  "tuned-vs-default timing ratio — the sweep must time the "
                  "default as a candidate", file=sys.stderr)
            failures += 1
            continue
        ok = ratio <= max_autotune_ratio + 1e-9
        print(f"{r['kind']},({r['block_q_default']},{r['block_n_default']}),"
              f"({r['block_q']},{r['block_n']}),{ratio:.4f},"
              f"<={max_autotune_ratio},{'ok' if ok else 'FAIL'}")
        if not ok:
            print(f"bench gate: autotune kind={r['kind']} tuned plan LOST "
                  f"to the default in its own paired sweep (ratio "
                  f"{ratio:.4f} > {max_autotune_ratio})", file=sys.stderr)
            failures += 1
    absent = [k for k in AUTOTUNE_KINDS if k not in seen]
    if absent:
        print(f"bench gate: autotune section missing kernel kind(s) "
              f"{absent}", file=sys.stderr)
        failures += 1
    return failures


def check_probe_budget(bench: dict) -> int:
    """Gate the occupancy-weighted probe-budget sweep (returns
    #failures): schema, weighted recall >= flat recall at every budget,
    and the exact-multiple parity row present with bit_identical true."""
    section = bench.get("probe_budget")
    if not section:
        print("bench gate: no 'probe_budget' section — the occupancy-"
              "weighted probe allocation sweep must be emitted",
              file=sys.stderr)
        return 1
    nlist, nprobe = bench.get("nlist"), bench.get("nprobe")
    parity = (nprobe * nlist
              if isinstance(nlist, int) and isinstance(nprobe, int) else None)
    failures = 0
    saw_parity = False
    print("probe_budget: budget,recall_weighted,recall_flat,status")
    for i, r in enumerate(section):
        missing = [k for k in PROBE_BUDGET_ROW_KEYS
                   if k not in r or r[k] is None]
        if missing:
            print(f"bench gate: probe_budget[{i}] missing keys {missing}",
                  file=sys.stderr)
            failures += 1
            continue
        errs = []
        if r["recall_weighted"] < r["recall_flat"] - 1e-9:
            errs.append(f"weighted recall {r['recall_weighted']:.4f} below "
                        f"flat recall {r['recall_flat']:.4f} at equal "
                        f"budget {r['probe_budget']}")
        if parity is not None and r["probe_budget"] == parity:
            saw_parity = True
            if r.get("bit_identical") is not True:
                errs.append(f"parity row (budget={parity} = nprobe*nlist) "
                            "not bit-identical to the flat-nprobe search")
        print(f"{r['probe_budget']},{r['recall_weighted']:.4f},"
              f"{r['recall_flat']:.4f},{'FAIL' if errs else 'ok'}")
        for e in errs:
            print(f"bench gate: probe_budget[{i}] {e}", file=sys.stderr)
        failures += len(errs)
    if parity is not None and not saw_parity:
        print(f"bench gate: probe_budget sweep has no parity row at "
              f"budget={parity} (= nprobe * nlist), the bit-identity "
              "operating point", file=sys.stderr)
        failures += 1
    return failures


def check(bench: dict, max_ratio: float, max_coarse_ratio: float = 0.6,
          max_autotune_ratio: float = 1.0) -> int:
    rows = bench.get("rows", [])
    by_variant: dict = {}
    for r in rows:
        variant = r.get("variant", bench.get("bench", "default"))
        by_variant.setdefault(variant, {})[bool(r["packed"])] = r

    if not by_variant:
        print("bench gate: no rows found in benchmark JSON", file=sys.stderr)
        return 1

    failures = 0
    print("variant,packed_bytes,unpacked_bytes,ratio,limit,status")
    for variant, pair in sorted(by_variant.items()):
        if True not in pair or False not in pair:
            print(f"{variant},?,?,?,{max_ratio},MISSING-PAIR")
            failures += 1
            continue
        p, u = _row_bytes(pair[True]), _row_bytes(pair[False])
        if p is None or u is None or u <= 0:
            print(f"{variant},{p},{u},?,{max_ratio},BAD-BYTES")
            failures += 1
            continue
        ratio = p / u
        ok = ratio <= max_ratio
        print(f"{variant},{p},{u},{ratio:.4f},{max_ratio},"
              f"{'ok' if ok else 'FAIL'}")
        if not ok:
            failures += 1
    if failures:
        print(f"bench gate: {failures} variant(s) violate the packed-byte "
              f"invariant (ratio <= {max_ratio})", file=sys.stderr)
    # The bi-granular, bits-per-dimension, autotune and probe-budget
    # sections ride on the scan bench specifically; BENCH_hnsw_scan.json
    # flows through the same pairing logic above but carries none of them.
    if bench.get("bench") == "sdc_scan":
        failures += check_bigranular(bench, max_coarse_ratio)
        failures += check_bits_sweep(bench, max_ratio)
        failures += check_autotune(bench, max_autotune_ratio)
        failures += check_probe_budget(bench)
    return 1 if failures else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("bench_json", help="path to BENCH_sdc_scan.json")
    ap.add_argument("--max-packed-ratio", type=float, default=0.55,
                    help="max allowed packed/unpacked bytes_scanned ratio")
    ap.add_argument("--max-coarse-ratio", type=float, default=0.6,
                    help="max allowed coarse/full-level bytes ratio for the "
                         "bigranular sweep at coarse_levels = levels // 2 "
                         "(BENCH_sdc_scan.json only: half the levels plus "
                         "per-doc metadata packing cannot shrink)")
    ap.add_argument("--max-autotune-ratio", type=float, default=1.0,
                    help="max allowed tuned/default ms ratio in the "
                         "autotune section (BENCH_sdc_scan.json only; the "
                         "sweep times the default as a candidate, so the "
                         "tuned plan can never honestly lose — default 1.0)")
    ap.add_argument("--min-serving-ratio", type=float, default=1.0,
                    help="min allowed overlapped/sequential QPS ratio "
                         "(BENCH_serving.json only)")
    ap.add_argument("--min-replica-ratio", type=float, default=0.9,
                    help="min allowed replicated(N>1)/replicated(1) paired "
                         "QPS ratio (BENCH_serving.json replica sweep; "
                         "< 1.0 because a shared-core host cannot scale "
                         "with replicas, but the router must not cost "
                         "throughput)")
    ap.add_argument("--min-upgrade-recall", type=float, default=0.5,
                    help="floor for the upgrade row's own recall_floor: "
                         "per-version recall over the live migration is "
                         "gated at max(row recall_floor, this), so an "
                         "emitter cannot pass by shipping a zero floor")
    args = ap.parse_args()
    with open(args.bench_json) as f:
        bench = json.load(f)
    if bench.get("bench") == "serving":
        return check_serving(bench, args.min_serving_ratio,
                             args.min_replica_ratio,
                             args.min_upgrade_recall)
    return check(bench, args.max_packed_ratio, args.max_coarse_ratio,
                 args.max_autotune_ratio)


if __name__ == "__main__":
    sys.exit(main())
