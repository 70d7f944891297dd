"""Distributed BEBR serving demo (paper Figure 5: proxy -> leaf -> merge).

    PYTHONPATH=src python examples/serve_bebr.py [--index flat|hnsw]
                                                 [--replicas N] [--router P]

Carves the devices JAX finds into ``--replicas`` disjoint submeshes
(``mesh.make_replica_meshes``): the chips of a TPU host, or 8 forced
host devices on a CPU-only machine. Each replica shards the whole
binary index over its own leaves and runs the same shard_map
proxy/leaf/merge program the 512-chip dry-run compiles; a ``QueryRouter``
(``launch/proxy.py``) spreads query batches across the replicas —
admission queue -> router -> replica pipelines -> engine leaves, the full
serving tier at laptop scale. Compares against the exact single-host
search and reports agreement + index bytes.

``--index hnsw`` swaps the exhaustive leaf scan for the batched-frontier
graph search: one NSW graph per leaf (host-side build), each leaf walking
its graph through the gather-then-scan kernel substrate, merged by the
identical proxy. The corpus shrinks to 16k docs because the NSW build is
host-side O(N^2) — the *search* program is the production one.
"""

import os

# Only the CPU platform reads this flag: a CPU-only run gets 8 host
# devices to carve into submeshes; on a TPU host the chips are the devices.
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

# ruff: noqa: E402
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import BinarizerConfig, TrainConfig, binarize_lib
import repro.core.losses as losses_lib
from repro.data.synthetic import clustered_corpus
from repro.kernels.sdc import ref as R
from repro.launch import (
    autoscale,
    binarizer_cache,
    compile_cache,
    faults,
    lifecycle,
    proxy,
    serving,
)
from repro.launch.mesh import make_replica_meshes
from repro.train import optim


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--index", choices=["flat", "hnsw"], default="flat")
    ap.add_argument("--replicas", type=int, default=2,
                    help="engine replicas; the devices are split into "
                         "this many disjoint submeshes")
    ap.add_argument("--router", choices=sorted(proxy.ROUTING_POLICIES),
                    default="round-robin", help="replica routing policy")
    ap.add_argument("--tier-spec", default=None, metavar="SPEC.json",
                    help="declarative tier spec (launch/autoscale.py): "
                         "starts the tier at min_replicas and runs the "
                         "shed-pressure autoscaler over the stream. The "
                         "devices are carved into max_replicas "
                         "submeshes up front, so every replica the "
                         "autoscaler may ever add already owns its "
                         "devices; scale-ups build the engine program on "
                         "submesh i via builder.build(snapshot, "
                         "replica=i). Overrides --replicas/--router")
    ap.add_argument("--steps", type=int, default=150,
                    help="binarizer training steps (first run only; the "
                         "checkpoint is cached under a content digest)")
    ap.add_argument("--ckpt-cache", default=None, metavar="DIR",
                    help="binarizer checkpoint cache dir (default: "
                         "$REPRO_BEBR_CACHE, else ~/.cache/repro-bebr)")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep (block_q, block_n) launch shapes for the "
                         "per-leaf scan on the "
                         "live shard sizes before serving; winners "
                         "persist in the tune cache "
                         "($REPRO_BEBR_CACHE), so every replica and "
                         "later launch shares one plan; bit-identical "
                         "scores either way (launch/autotune.py)")
    ap.add_argument("--coarse-levels", type=int, default=0, metavar="C",
                    help="bi-granular engine (flat only): per-leaf coarse "
                         "scan over the first C levels, post-merge "
                         "full-level rerank of --k-coarse survivors; "
                         "0 disables")
    ap.add_argument("--k-coarse", type=int, default=0, metavar="K'",
                    help="bi-granular engine: survivors rescored at full "
                         "depth; 0 disables (set with --coarse-levels)")
    ap.add_argument("--swap-after", type=int, default=0, metavar="N",
                    help="after N routed batches, rolling-swap every "
                         "replica's index from a fresh corpus snapshot "
                         "(drain -> rebuild on its submesh -> warm -> "
                         "canary re-probe) under the live stream; "
                         "0 disables")
    ap.add_argument("--probe-every", type=float, default=0.0, metavar="S",
                    help="period (s) of the router's canary health "
                         "re-probe; revives unhealthy replicas; 0 off")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault injection on the replica "
                         "fns (launch/faults.py grammar), e.g. "
                         "'r0.search.fail@3' — pair with --probe-every "
                         "to watch failover + revival on the sharded "
                         "tier")
    args = ap.parse_args()
    cache_dir, warm = compile_cache.enable_compile_cache()
    devices = jax.devices()
    n_devices = len(devices)
    print(f"devices: platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={n_devices}; compile cache "
          f"{cache_dir} ({'warm' if warm else 'cold'})")
    spec = None
    if args.tier_spec:
        try:
            spec = autoscale.TierSpec.from_file(args.tier_spec)
        except autoscale.InvalidTierSpec as e:
            ap.error(f"--tier-spec: {e}")
        args.replicas = spec.min_replicas
        args.router = spec.router
    # The submesh carve is sized for the LARGEST tier the spec allows:
    # scale-up must only instantiate a program on an already-reserved
    # submesh, never re-partition live devices.
    n_slots = spec.max_replicas if spec is not None else args.replicas
    if n_devices % n_slots:
        ap.error(f"replica slots ({n_slots}) must divide the {n_devices} "
                 "devices")
    if bool(args.coarse_levels) != bool(args.k_coarse):
        ap.error("--coarse-levels and --k-coarse must be set together")
    if args.coarse_levels and args.index != "flat":
        ap.error("--coarse-levels requires --index flat (per-leaf coarse "
                 "scan + post-merge rerank)")
    per = n_devices // n_slots
    shape = (per // 2, 2) if per % 2 == 0 else (per, 1)

    dim, code, levels = 128, 64, 4
    n_docs = 100_000 if args.index == "flat" else 16_000
    docs, queries, gt = clustered_corpus(0, n_docs, 64, dim, n_clusters=256)

    # binarize: a real (small) recurrent-MLP binarizer, trained emb2emb
    # on the corpus and checkpointed under a content digest — only the
    # first launch pays for training; later runs reload the weights
    # (launch/binarizer_cache.py). The old hidden_dim=0 shortcut (an
    # untrained random projection) skipped training but gave away the
    # recall the recurrent residual levels exist to recover.
    bcfg = BinarizerConfig(input_dim=dim, code_dim=code, n_levels=levels,
                           hidden_dim=2 * dim)
    tcfg = TrainConfig(
        binarizer=bcfg,
        queue=losses_lib.QueueConfig(length=2048, dim=code, top_k=32),
        adam=optim.AdamConfig(lr=2e-3, clip_norm=5.0),
    )
    t0 = time.time()
    ckpt = binarizer_cache.trained_binarizer(
        docs, tcfg, steps=args.steps, seed=0, cache_dir=args.ckpt_cache
    )
    verb = "trained" if ckpt.trained else "loaded cached"
    print(f"binarizer: {verb} checkpoint {ckpt.digest} in "
          f"{time.time() - t0:.1f}s (hidden={bcfg.hidden_dim}, "
          f"{args.steps} steps)")
    enc = binarize_lib.make_encode_fn(ckpt.params, ckpt.bn_state, bcfg)
    d_codes, q_codes = enc(docs), enc(queries)

    meshes = make_replica_meshes(n_slots, shape=shape)
    print(f"replica submeshes: {n_slots} x {dict(meshes[0].shape)} — "
          f"{args.index} index of {d_codes.shape[0]} codes sharded over "
          f"{per} leaves per replica, router={args.router}"
          + (f" (serving {args.replicas}, autoscaling up to {n_slots})"
             if spec is not None else ""))

    # jit'd per-batch encode, shared across replicas: the eager path
    # would fight the leaf scans for the GIL. Query device placement
    # happens inside each replica's search closure (the builder emits
    # submesh-aware SearchFns).
    encode = enc

    # The same builder serves the initial tier AND the rolling swap: each
    # replica's index is `builder.build(snapshot, replica=i)` — the
    # shard_map program over ITS submesh, closed over its device-placed
    # corpus shards. For hnsw the host-side sharded graph is built once
    # per snapshot digest and shared across replicas (same leaf layout).
    snapshot = lifecycle.CorpusSnapshot(codes=np.asarray(d_codes),
                                        n_levels=levels)
    # Tuned launch shapes for the per-leaf scan, keyed on the PER-LEAF
    # shard size — that is the corpus each kernel launch actually sees.
    # Plans never change scores; the agreement check below holds either
    # way.
    block_plan = None
    if args.autotune:
        from repro.launch import autotune

        n_shard = -(-d_codes.shape[0] // per)  # rows per leaf, padded up
        tp = autotune.tuned_block_plan(
            "scan", code_dim=code, n_shard=n_shard,
            k=(args.k_coarse or 10), n_levels=levels,
        )
        block_plan = {"scan": tp.plan}
        print(f"tune scan: block_q={tp.plan.block_q} "
              f"block_n={tp.plan.block_n} ({tp.plan.source})")
    builder = lifecycle.EngineBuilder(
        meshes, index=args.index, n_levels=levels, k=10,
        M=16, ef_construction=48, ef=64, beam=16,
        coarse_levels=args.coarse_levels or None,
        k_coarse=args.k_coarse or None,
        block_plan=block_plan,
    )
    replica_fns = [(encode, builder.build(snapshot, replica=i))
                   for i in range(args.replicas)]

    batch = 16
    batches = [queries[i:i + batch]
               for i in range(0, queries.shape[0], batch)]
    # Compile every replica's encode + engine program for both drivers
    # outside the timed region (see warmup_replicas: worker threads
    # carry thread-local jit caches, ragged tails are their own shape).
    serving.warmup_replicas(replica_fns, batches)

    rounds = 4
    stream = batches * rounds
    enc0, search0 = replica_fns[0]
    t0 = time.time()
    serving.serve_sequential(enc0, search0, stream)
    dt_seq = time.time() - t0
    # Chaos wrapping AFTER warmup and the sequential baseline: the fault
    # schedule is a function of the call index, so earlier traffic must
    # not consume it — and the faults target the ROUTED tier, not the
    # un-routed reference leg.
    replica_fns, injectors = faults.apply_chaos(replica_fns, args.chaos)
    t0 = time.time()
    # share_device stays False: the submeshes model disjoint production
    # hardware (where replica scans genuinely run in parallel). On a CPU
    # host the 8 forced "devices" actually share this machine's cores, so
    # the demo's QPS numbers carry that contention — agreement, routing,
    # failover and rolling-swap semantics are what this example
    # demonstrates. The router is driven directly (rather than through
    # serve_replicated) so a mid-stream rolling swap / canary probe can
    # run against the live tier.
    router = proxy.QueryRouter(
        proxy.ReplicaSet(replica_fns, config=serving.ServingConfig()),
        policy=args.router,
    )
    controller = None
    if args.swap_after:
        controller = lifecycle.RollingSwapController(
            router, builder, warm_batches=batches[:1], encode_fn=encode
        )
    if args.probe_every:
        router.start_health_probe(batches[0], interval=args.probe_every)
    scaler = None
    if spec is not None:
        # Engine tiers hand the autoscaler a replica factory instead of
        # (snapshot, encode_fn): slot i's search closure is the shard_map
        # program over submesh i, built by the SAME EngineBuilder the
        # rolling swap uses.
        scaler = autoscale.Autoscaler(
            router, spec,
            replica_factory=lambda slot: (
                encode, builder.build(snapshot, replica=slot)
            ),
            warm_batches=batches[:1],
            on_event=lambda msg: print(f"autoscale: {msg}"),
        )
        scaler.start()
    results, swap_report = lifecycle.run_stream_with_swap(
        router, stream, controller=controller, snapshot=snapshot,
        swap_after=args.swap_after,
    )
    if scaler is not None:
        scaler.stop()
    for inj in injectors.values():
        inj.release()  # a still-stuck scan would wedge close()'s joins
    router.close()
    stats = router.stats()
    dt = time.time() - t0
    # host-side concat: replica results live on disjoint device sets
    ids = np.concatenate([np.asarray(i) for _, i in results[: len(batches)]], 0)

    ev, ei = jax.lax.top_k(R.sdc_ref(q_codes, d_codes, levels), 10)
    agree = np.mean([
        len(set(np.asarray(ids[i]).tolist()) & set(np.asarray(ei[i]).tolist())) / 10
        for i in range(q_codes.shape[0])
    ])
    recall = float(jnp.mean(jnp.any(ids == jnp.asarray(gt)[:, None], -1)))
    n_q = queries.shape[0] * rounds
    print(f"leaf/merge top-10 vs exact agreement: {agree:.3f}")
    print(f"ground-truth recall@10: {recall:.3f}")
    print(f"sequential (1 replica): {n_q/dt_seq:.0f} QPS | routed "
          f"({args.replicas} replicas): {n_q/dt:.0f} QPS on {n_devices} "
          f"{devices[0].platform} leaves (p50 {stats['latency_p50_ms']:.1f} ms, "
          f"p99 {stats['latency_p99_ms']:.1f} ms, scan stage waiting for "
          f"input {100*stats['scan_input_wait_frac']:.0f}%)")
    for srep in stats["per_replica"]:
        print(f"  replica {srep['replica']}: {srep['requests']} req "
              f"({srep['queries']} queries), scan stage waiting for input "
              f"{100*srep['scan_input_wait_frac']:.0f}%, "
              f"generation {srep['generation']}")
    if swap_report is not None:
        rep = swap_report
        print(f"rolling swap -> {rep.version.tag}: {rep.swapped} replica(s) "
              f"re-indexed under the live stream in {rep.total_s*1e3:.0f} ms")
        for row in rep.replicas:
            print(f"  replica {row['replica']}: drain {row['drain_s']*1e3:.0f}"
                  f" ms, build {row['build_s']*1e3:.0f} ms, warm "
                  f"{row['warm_s']*1e3:.0f} ms, probe {row['probe_s']*1e3:.0f}"
                  f" ms")
    if args.probe_every:
        print(f"canary re-probe every {args.probe_every}s: "
              f"{stats['revivals']} revival(s)")
    if scaler is not None:
        sm = scaler.summary()
        print(f"autoscale [{sm['replicas_min']}, {sm['replicas_max']}]: "
              f"{sm['scale_ups']} up / {sm['scale_downs']} down over "
              f"{sm['decisions']} tick(s); ended at {sm['replicas']} "
              f"replica(s)")
    for i, inj in sorted(injectors.items()):
        fired = ", ".join(f"{s}#{n}:{k}" for s, n, k in inj.log) or "none"
        print(f"chaos replica {i}: {len(inj.log)} fault(s) fired ({fired})")
    packed = (code * levels + 7) // 8 + 4
    print(f"index bytes: {d_codes.shape[0]*packed/2**20:.1f} MiB vs "
          f"float {docs.nbytes/2**20:.1f} MiB")


if __name__ == "__main__":
    main()
