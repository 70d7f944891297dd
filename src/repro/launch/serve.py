"""BEBR serving launcher (paper Figure 5: query -> phi -> proxy/leaf/merge).

    PYTHONPATH=src python -m repro.launch.serve --docs 20000 --queries 64

End-to-end: train a binarizer on the corpus embeddings (emb2emb; the
checkpoint is cached under a content digest, so only the first launch
pays for training — see launch/binarizer_cache.py), binarize + index the
corpus, then serve batched queries through
  float backbone emb -> recurrent binarization -> SDC search (flat or IVF)
and report recall vs the float-embedding exhaustive baseline, plus index
bytes (the paper's memory-saving claim) and per-batch latency.
``--coarse-levels C --k-coarse K'`` switch every index family to the
bi-granular mode: hot coarse scan over the first C levels, cold
full-level rerank of the K' survivors.
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    BinarizerConfig,
    TrainConfig,
    bc_train_step,
    binarize_eval,
    init_train_state,
    pack_codes,
)
from repro.core import binarize_lib
import repro.core.losses as losses_lib
from repro.data import synthetic
from repro.index import hnsw_lite
from repro.index import ivf as ivf_lib
from repro.index.flat import FlatFloat, FlatSDC
from repro.kernels.sdc import ref as sdc_ref
from repro.launch import (
    autoscale,
    binarizer_cache,
    compile_cache,
    faults,
    lifecycle,
    proxy,
    serving,
)
from repro.kernels.sdc.ops import SDC_BACKENDS


def train_binarizer(docs: np.ndarray, cfg: TrainConfig, steps: int = 300,
                    batch: int = 256, seed: int = 0,
                    cache_dir: str | None = None):
    """Train the binarizer once per (corpus, config, steps, seed) digest.

    Later launches with identical inputs reload the checkpointed
    weights instead of re-running the emb2emb loop; see
    ``launch/binarizer_cache.py``. Returns a ``BinarizerCheckpoint``
    (``.params``/``.bn_state`` drop in for the ``TrainState`` fields).
    """
    return binarizer_cache.trained_binarizer(
        docs, cfg, steps=steps, batch=batch, seed=seed, cache_dir=cache_dir
    )


def encode_codes(state, emb: np.ndarray, bcfg: BinarizerConfig, batch=4096):
    outs = []
    for i in range(0, emb.shape[0], batch):
        bits, _, _ = binarize_lib.binarize(
            state.params, state.bn_state, jnp.asarray(emb[i : i + batch]), bcfg
        )
        outs.append(pack_codes(bits))
    return jnp.concatenate(outs, 0)


def bc_train_binarizer(old, old_docs: np.ndarray, new_docs: np.ndarray,
                       cfg: TrainConfig, steps: int = 300, batch: int = 256,
                       seed: int = 7):
    """Backward-compatible training (paper §3.2.3): warm-start phi_new
    from phi_old and anchor its output space to phi_old's on the shared
    items, so new-backbone queries can search the old binary index."""
    copy = functools.partial(jax.tree_util.tree_map, jnp.copy)
    state = init_train_state(jax.random.PRNGKey(seed), cfg)._replace(
        params=copy(old.params), m_params=copy(old.params),
        bn_state=copy(old.bn_state), m_bn_state=copy(old.bn_state),
    )
    step = jax.jit(functools.partial(bc_train_step, cfg=cfg))
    rng = np.random.default_rng(seed + 1)
    dim = old_docs.shape[-1]
    for _ in range(steps):
        idx = rng.integers(0, old_docs.shape[0], batch)
        noise = rng.normal(size=(batch, dim)).astype(np.float32) * 0.02
        a = new_docs[idx] + noise
        a /= np.linalg.norm(a, axis=-1, keepdims=True) + 1e-12
        state, _ = step(state, old.params, old.bn_state, jnp.asarray(a),
                        jnp.asarray(old_docs[idx]))
    return state


def _next_version(tag: str) -> str:
    if tag.startswith("v") and tag[1:].isdigit():
        return f"v{int(tag[1:]) + 1}"
    return tag + "+1"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=20000)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--code-dim", type=int, default=128)
    ap.add_argument("--levels", type=int, default=4)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-cache", default=None, metavar="DIR",
                    help="binarizer checkpoint cache dir (default: "
                         "$REPRO_BEBR_CACHE, else ~/.cache/repro-bebr); "
                         "training runs once per (corpus, config, steps, "
                         "seed) digest and later launches reload the "
                         "weights")
    ap.add_argument("--index", choices=["flat", "ivf", "hnsw"], default="flat")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--coarse-levels", type=int, default=0, metavar="C",
                    help="bi-granular mode: coarse-scan the first C "
                         "residual levels (hot tier), then rerank the "
                         "--k-coarse survivors on the full-level codes "
                         "(cold tier); 0 disables (set with --k-coarse)")
    ap.add_argument("--k-coarse", type=int, default=0, metavar="K'",
                    help="bi-granular mode: survivors kept per query by "
                         "the coarse scan and rescored at full depth; "
                         "0 disables (set with --coarse-levels)")
    ap.add_argument("--ef", type=int, default=64,
                    help="hnsw: result-list width (and per-hop top-k)")
    ap.add_argument("--beam", type=int, default=8,
                    help="hnsw: frontier nodes expanded per hop")
    ap.add_argument("--packed", action="store_true",
                    help="int4 nibble-packed code storage (2 dims/byte; "
                         "halves scan bandwidth, bit-identical scores)")
    ap.add_argument("--backend", default="auto", choices=SDC_BACKENDS,
                    help="SDC scoring backend (auto: Pallas kernel on TPU, "
                         "jnp fallback elsewhere)")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep (block_q, block_n) launch shapes for the "
                         "live corpus/kernel signatures on startup and "
                         "serve with the winners; winners persist in the "
                         "tune cache so replicas and later launches share "
                         "one plan (launch/autotune.py); scores are "
                         "bit-identical with or without this flag")
    ap.add_argument("--tune-cache", default=None, metavar="DIR",
                    help="block-plan tune cache dir (default: "
                         "$REPRO_BEBR_CACHE, else ~/.cache/repro-bebr); "
                         "the first launch to tune a signature pays the "
                         "sweep, everyone else loads its winner")
    ap.add_argument("--probe-budget", type=int, default=0, metavar="B",
                    help="ivf: occupancy-weighted probe allocation — B "
                         "per-centroid rank slots are split across the "
                         "coarse centroids in proportion to list "
                         "occupancy instead of a flat per-query "
                         "--nprobe; B = nprobe*nlist costs the same "
                         "scans as flat nprobe (and is bit-identical at "
                         "exact multiples); 0 disables")
    ap.add_argument("--batch", type=int, default=0,
                    help="serving batch size (0: all queries in one batch)")
    ap.add_argument("--rounds", type=int, default=4,
                    help="times the query stream is replayed for "
                         "steady-state timing")
    ap.add_argument("--queue-depth", type=int, default=8,
                    help="admission-queue depth (requests, per replica)")
    ap.add_argument("--policy", choices=["block", "shed"], default="block",
                    help="admission policy when a replica queue is full "
                         "(the proxy sheds only when EVERY replica is "
                         "saturated)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving replicas behind the query router (on a "
                         "single host they share the device and index "
                         "arrays; each still gets its own pipeline + "
                         "admission queue)")
    ap.add_argument("--router", choices=sorted(proxy.ROUTING_POLICIES),
                    default="round-robin",
                    help="replica routing policy")
    ap.add_argument("--tier-spec", default=None, metavar="SPEC.json",
                    help="declarative tier spec (launch/autoscale.py "
                         "TierSpec JSON): replica min/max, index kind + "
                         "build params, router policy, admission policy/"
                         "queue depth, swap cadence, and scale thresholds. "
                         "Overrides --replicas/--router/--queue-depth/"
                         "--policy/--index/--backend, starts the tier at "
                         "min_replicas, and runs the shed-pressure "
                         "autoscaler over the stream (scale-up replicas "
                         "are built from the spec's index params, warmed, "
                         "and canary-probed before taking traffic; "
                         "scale-down drains losslessly). swap_every_s > 0 "
                         "schedules one rolling swap mid-stream when "
                         "--swap-after/--upgrade-after are unset")
    ap.add_argument("--embedding-version", default="v1",
                    help="embedding-version tag for the trained binarizer, "
                         "the corpus snapshot, and the tier's replicas; "
                         "typed SearchRequests are routed by this tag")
    ap.add_argument("--upgrade-after", type=int, default=0, metavar="N",
                    help="after N batches, run a LIVE embedding-version "
                         "migration: bc-train the next-version binarizer "
                         "against a drifted backbone "
                         "(data/synthetic.backbone_upgrade), register "
                         "cross-version compat encoders, and rolling-swap "
                         "every replica to the new index while the stream "
                         "mixes old- and new-version queries; 0 disables "
                         "(mutually exclusive with --swap-after)")
    ap.add_argument("--swap-after", type=int, default=0, metavar="N",
                    help="after N batches of the routed stream, run a "
                         "rolling index swap (drain -> rebuild -> warm -> "
                         "canary re-probe, one replica at a time) under "
                         "the live traffic; 0 disables")
    ap.add_argument("--probe-every", type=float, default=0.0, metavar="S",
                    help="period (s) of the router's canary health "
                         "re-probe loop — unhealthy replicas that answer "
                         "the canary are revived; 0 disables")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault injection on the serving "
                         "fns: comma-joined clauses "
                         "'[rN.][stage.]kind[@AT][xCOUNT][~PROB][:ARG]' "
                         "with kind in fail|delay|stick|flap (see "
                         "launch/faults.py). e.g. "
                         "'r0.search.fail@3,r1.search.delay~0.5:0.01' — "
                         "pair with --probe-every / --scan-budget-ms to "
                         "watch the tier heal")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-batch deadline (ms) enforced through the "
                         "tier: expired work is shed at dequeue (counted, "
                         "never scanned) and lands as a None result; "
                         "0 disables")
    ap.add_argument("--scan-budget-ms", type=float, default=0.0,
                    help="stuck-scan watchdog budget (ms): a scan running "
                         "past it marks its replica unhealthy and fails "
                         "its in-flight work over to the survivors; "
                         "0 disables")
    args = ap.parse_args()
    if args.swap_after and args.upgrade_after:
        ap.error("--swap-after and --upgrade-after are mutually exclusive "
                 "(the upgrade IS a rolling swap, to the next-version index)")
    if bool(args.coarse_levels) != bool(args.k_coarse):
        ap.error("--coarse-levels and --k-coarse must be set together")
    if args.coarse_levels and not 0 < args.coarse_levels < args.levels:
        ap.error(f"--coarse-levels must be in [1, {args.levels - 1}] "
                 f"(got {args.coarse_levels} of --levels {args.levels})")
    if args.probe_budget and args.index != "ivf":
        ap.error("--probe-budget only applies to --index ivf")

    cache_dir, warm = compile_cache.enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}; compile cache {cache_dir} "
          f"({'warm' if warm else 'cold'})")

    # Declarative tier spec: ONE artifact describes the tier's desired
    # state; the flags it covers are overridden so an operator cannot
    # half-apply it. The autoscaler re-applies the same spec as it
    # resizes — scale-up replicas are built from spec.build_params, not
    # from whatever flags happened to be on the command line.
    spec = None
    if args.tier_spec:
        try:
            spec = autoscale.TierSpec.from_file(args.tier_spec)
        except autoscale.InvalidTierSpec as e:
            ap.error(f"--tier-spec: {e}")
        args.index = spec.index
        args.replicas = spec.min_replicas
        args.router = spec.router
        args.queue_depth = spec.queue_depth
        args.policy = spec.policy
        args.backend = spec.backend
        print(f"[tier-spec] {args.tier_spec}: index={spec.index} "
              f"backend={spec.backend} "
              f"replicas=[{spec.min_replicas}, {spec.max_replicas}] "
              f"router={spec.router} policy={spec.policy} "
              f"water=({spec.low_water}, {spec.high_water}) "
              f"cooldown={spec.cooldown_s}s window={spec.window_s}s")

    print(f"[data] {args.docs} docs, {args.queries} queries, dim={args.dim}")
    docs, queries, gt = synthetic.clustered_corpus(
        0, args.docs, args.queries, args.dim
    )

    bcfg = BinarizerConfig(
        input_dim=args.dim, code_dim=args.code_dim, n_levels=args.levels,
        hidden_dim=2 * args.dim,
    )
    from repro.train import optim

    tcfg = TrainConfig(
        binarizer=bcfg,
        queue=losses_lib.QueueConfig(length=4096, dim=args.code_dim, top_k=64),
        adam=optim.AdamConfig(lr=2e-3, clip_norm=5.0),
    )
    print(f"[train] binarizer {bcfg.total_bits} bits "
          f"({32 * args.dim // bcfg.total_bits}x compression), "
          f"{args.steps} steps")
    t0 = time.time()
    state = train_binarizer(docs, tcfg, steps=args.steps,
                            cache_dir=args.ckpt_cache)
    verb = "trained" if state.trained else "loaded cached checkpoint"
    print(f"[train] {verb} ({state.digest}) in {time.time() - t0:.1f}s")

    # --- index build ---
    d_codes = encode_codes(state, docs, bcfg)

    # The lifecycle builder is the single source of build params: the
    # initial index below consumes builder.params, so a mid-stream
    # rolling swap (--swap-after) provably rebuilds the SAME index and
    # the demo's bit-identity claim cannot drift out from under it.
    flat_float = FlatFloat.build(jnp.asarray(docs))
    cl = args.coarse_levels or None
    kc = args.k_coarse or None

    # Adaptive execution: tune (or reload) the scan's block plan for the
    # live corpus shape (the gather and rerank kernels have layout-fixed
    # geometry). Plans only move launch geometry — every score below is
    # bit-identical with block_plan=None.
    block_plan = None
    if args.autotune:
        from repro.launch import autotune

        tp = autotune.tuned_block_plan(
            "scan", code_dim=args.code_dim, n_shard=args.docs,
            packed=args.packed, k=(kc or args.k), n_levels=args.levels,
            backend=args.backend, cache_dir=args.tune_cache,
        )
        block_plan = {"scan": tp.plan}
        print(f"[tune] scan: block_q={tp.plan.block_q} "
              f"block_n={tp.plan.block_n} ({tp.plan.source}"
              f"{', swept now' if tp.tuned else ''})")

    if spec is not None:
        # The spec's build params are the single source of truth; the
        # per-family branches below consume builder.params so the
        # initial index, every swap, and every autoscaler scale-up all
        # build the SAME index.
        builder = spec.make_index_builder()
    elif args.index == "flat":
        builder = lifecycle.FlatBuilder(
            k=args.k, packed=args.packed, backend=args.backend,
            coarse_levels=cl, k_coarse=kc, block_plan=block_plan,
        )
    elif args.index == "ivf":
        builder = lifecycle.IVFBuilder(
            k=args.k, nlist=64, nprobe=32, seed=1, packed=args.packed,
            backend=args.backend, coarse_levels=cl, k_coarse=kc,
            probe_budget=args.probe_budget or None, block_plan=block_plan,
        )
    else:
        builder = lifecycle.HNSWBuilder(
            k=args.k, M=16, ef_construction=64, ef=args.ef, beam=args.beam,
            packed=args.packed, backend=args.backend,
            coarse_levels=cl, k_coarse=kc, block_plan=block_plan,
        )
    p = builder.params

    if args.index == "hnsw":
        print("[index] building NSW graph (host-side, O(N^2) incremental "
              "construction — use --docs <= 20000 for a quick demo)")
    if cl is not None:
        # Bi-granular mode serves through the lifecycle builder from the
        # first query: it is the same fn a rolling swap of the identical
        # snapshot would install (digest-cached), so the swap demo's
        # bit-identity claim holds with rerank on.
        snapshot0 = lifecycle.CorpusSnapshot(
            codes=np.asarray(d_codes), n_levels=bcfg.n_levels,
            embedding_version=args.embedding_version,
        )
        search = builder.build(snapshot0)
        per_doc = lambda lv: (args.code_dim * lv + 7) // 8 + 4
        coarse_b = args.docs * per_doc(cl)
        fine_b = args.docs * per_doc(args.levels)
        nbytes = coarse_b + fine_b
        print(f"[index] bi-granular tiers (serialized): "
              f"coarse {coarse_b/2**20:.2f} MiB (hot, {cl}/{args.levels} "
              f"levels), fine {fine_b/2**20:.2f} MiB (cold), "
              f"rerank k'={kc}")
    elif args.index == "flat":
        from repro.kernels.sdc.defaults import plan_for

        index = FlatSDC.build(
            d_codes, bcfg.n_levels, packed=p["packed"], backend=p["backend"]
        )
        scan_plan = plan_for(block_plan, "scan")
        search = lambda q: index.search(q, p["k"], block_plan=scan_plan)
        nbytes = index.nbytes()
    elif args.index == "ivf":
        index = ivf_lib.build_ivf(
            jax.random.PRNGKey(p["seed"]), d_codes, n_levels=bcfg.n_levels,
            nlist=p["nlist"], kmeans_iters=p["kmeans_iters"],
            packed=p["packed"],
        )
        if p["probe_budget"]:
            search = lambda q: ivf_lib.search_budget(
                index, q, probe_budget=p["probe_budget"], k=p["k"],
                backend=p["backend"],
            )
        else:
            search = lambda q: ivf_lib.search(
                index, q, nprobe=p["nprobe"], k=p["k"], backend=p["backend"]
            )
        nbytes = index.nbytes()
    else:  # hnsw: batched-frontier graph search on the gather kernel
        inv = np.asarray(sdc_ref.doc_inv_norms(d_codes, bcfg.n_levels))
        index = hnsw_lite.build_hnsw(
            np.asarray(d_codes), inv, n_levels=bcfg.n_levels, M=p["M"],
            ef_construction=p["ef_construction"], seed=p["seed"],
            packed=p["packed"],
        )
        tables = hnsw_lite.prepare_batched(index)
        search = lambda q: hnsw_lite.search_hnsw_batched(
            tables, q, k=p["k"], ef=p["ef"], beam=p["beam"],
            backend=p["backend"],
        )
        nbytes = index.nbytes()

    float_bytes = flat_float.nbytes()
    print(f"[index] {args.index}: {nbytes/2**20:.2f} MiB "
          f"(float flat: {float_bytes/2**20:.2f} MiB, "
          f"saving {100*(1-nbytes/float_bytes):.1f}%)")

    # --- serve: replicated pipelines behind the query router ---
    _, idx_f = flat_float.search(jnp.asarray(queries), args.k)

    # jit'd per-batch encode: the eager path dispatches dozens of small
    # ops per batch and would fight the scan threads for the GIL.
    encode = binarize_lib.make_encode_fn(state.params, state.bn_state, bcfg)
    batch = args.batch or args.queries
    batches = [queries[i:i + batch] for i in range(0, args.queries, batch)]
    stream = batches * args.rounds
    n_q = args.queries * args.rounds

    # Single-host replicas share the index closure: N pipelines (each
    # its own admission queue + worker threads) over the same arrays.
    replica_fns = [(encode, search)] * args.replicas
    serving.warmup_replicas(replica_fns, batches)
    # Chaos wrapping AFTER warmup: the fault schedule is a function of
    # the call index, and warmup traffic must not consume (or trip) it.
    replica_fns, injectors = faults.apply_chaos(replica_fns, args.chaos)

    t0 = time.time()
    serving.serve_sequential(encode, search, stream)
    dt_seq = time.time() - t0

    # Drive the router directly so --policy is honoured: submits that
    # shed off EVERY replica's full admission queue are retried after a
    # short pause (observable in stats["shed"]); block policy
    # back-pressures inside submit.
    pcfg = serving.ServingConfig(queue_depth=args.queue_depth,
                                 policy=args.policy)
    # share_device: single-host replicas sit on one device; their scan
    # stages take turns instead of oversubscribing the host cores.
    compat = proxy.CompatibilityMatrix()
    # share_device also when a tier spec may scale up later: added
    # replicas land on the same host device as the originals.
    share = args.replicas > 1 or (spec is not None and spec.max_replicas > 1)
    router = proxy.QueryRouter(
        proxy.ReplicaSet(replica_fns, config=pcfg, share_device=share),
        policy=args.router, compat=compat,
    )
    from_version = args.embedding_version
    for r in range(args.replicas):
        router.set_version(r, from_version)

    # Live index lifecycle: a rolling swap mid-stream rebuilds each
    # replica's index from a fresh corpus snapshot (here: the same codes,
    # so results stay bit-identical and recall is unchanged — the point
    # of the demo is that the traffic never stops), and the periodic
    # canary probe revives replicas whose transient faults clear.
    controller = snapshot = None
    to_version = None
    stream_meta = None
    if spec is not None and spec.swap_every_s > 0 \
            and not (args.swap_after or args.upgrade_after):
        # The spec's declared swap cadence, mapped onto this
        # finite-stream demo driver: one rolling swap at mid-stream.
        args.swap_after = max(1, len(stream) // 2)
    if args.swap_after:
        snapshot = lifecycle.CorpusSnapshot(
            codes=np.asarray(d_codes), n_levels=bcfg.n_levels,
            embedding_version=from_version,
        )
        controller = lifecycle.RollingSwapController(
            router, builder, warm_batches=batches[:1], encode_fn=encode
        )
    elif args.upgrade_after:
        # Live embedding-version migration: bc-train the next-version
        # binarizer against a drifted backbone, register cross-version
        # compat encoders (v_new queries search the v_old index and vice
        # versa through the bc-anchored output space), then rolling-swap
        # the tier to the new index under mixed-version traffic.
        to_version = _next_version(from_version)
        print(f"[upgrade] backbone drift + bc-training {to_version} "
              f"binarizer ({args.steps} steps)")
        new_docs = synthetic.backbone_upgrade(docs, 5)
        new_queries = synthetic.backbone_upgrade(queries, 5)
        new_state = bc_train_binarizer(state, docs, new_docs, tcfg,
                                       steps=args.steps)
        enc_new = binarize_lib.make_encode_fn(
            new_state.params, new_state.bn_state, bcfg
        )
        compat.register(to_version, from_version, enc_new)
        compat.register(from_version, to_version, encode)
        snapshot = lifecycle.CorpusSnapshot(
            codes=np.asarray(encode_codes(new_state, new_docs, bcfg)),
            n_levels=bcfg.n_levels, embedding_version=to_version,
        )
        controller = lifecycle.RollingSwapController(
            router, builder, warm_batches=batches[:1], encode_fn=enc_new
        )
        # the compat hop runs enc_new on the still-v_old replicas before
        # the swap reaches them: pre-compile it like every other stage
        serving.warmup_replicas([(enc_new, search)], batches[:1])
        new_batches = [new_queries[i:i + batch]
                       for i in range(0, args.queries, batch)]
        # mixed-version stream: each round alternates an old-version and
        # a new-version request per batch index
        stream, stream_meta = [], []
        for _ in range(args.rounds):
            for i, (b, nb) in enumerate(zip(batches, new_batches)):
                stream.append(serving.SearchRequest(
                    queries=b, embedding_version=from_version))
                stream_meta.append((from_version, i))
                stream.append(serving.SearchRequest(
                    queries=nb, embedding_version=to_version))
                stream_meta.append((to_version, i))
    if args.probe_every:
        router.start_health_probe(batches[0], interval=args.probe_every)
    if args.scan_budget_ms:
        router.start_watchdogs(args.scan_budget_ms / 1e3)

    scaler = None
    if spec is not None:
        as_snapshot = snapshot if snapshot is not None else \
            lifecycle.CorpusSnapshot(
                codes=np.asarray(d_codes), n_levels=bcfg.n_levels,
                embedding_version=from_version,
            )
        scaler = autoscale.Autoscaler(
            router, spec, snapshot=as_snapshot, encode_fn=encode,
            warm_batches=batches[:1],
            on_event=lambda msg: print(f"[autoscale] {msg}"),
        )
        scaler.start()

    t0 = time.time()
    results, swap_report = lifecycle.run_stream_with_swap(
        router, stream, controller=controller, snapshot=snapshot,
        swap_after=args.swap_after or args.upgrade_after,
        deadline_s=(args.deadline_ms / 1e3) if args.deadline_ms else None,
    )
    dt_pipe = time.time() - t0
    if scaler is not None:
        scaler.stop()
    for inj in injectors.values():
        inj.release()  # a still-stuck scan would wedge close()'s joins
    router.close()
    stats = router.stats()

    gt_t = jnp.asarray(gt)[:, None]
    r_float = float(jnp.mean(jnp.any(idx_f == gt_t, axis=-1)))
    if stream_meta is not None:
        # mixed-version stream: per-version recall over every answered
        # request across the whole migration window
        hits = {from_version: [], to_version: []}
        for (ver, i), r in zip(stream_meta, results):
            if r is None:
                continue
            ids = np.asarray(r[1])
            g = np.asarray(gt)[i * batch : i * batch + ids.shape[0]]
            hits[ver].append(float(np.mean(np.any(ids == g[:, None], -1))))
        per_ver = " ".join(
            f"{v}={np.mean(h):.4f}" if h else f"{v}=n/a"
            for v, h in hits.items()
        )
        print(f"[serve] recall@{args.k}: float={r_float:.4f} "
              f"BEBR[{per_ver}] (across the live migration)")
    elif all(r is not None for r in results[: len(batches)]):
        first = results[: len(batches)]
        idx_b = jnp.concatenate([ids for _, ids in first], 0)
        r_bebr = float(jnp.mean(jnp.any(idx_b == gt_t, axis=-1)))
        print(f"[serve] recall@{args.k}: float={r_float:.4f} "
              f"BEBR={r_bebr:.4f}")
    else:
        # Deadline sheds are accounted answers, but recall needs the
        # full first replay of the stream.
        first = results[: len(batches)]
        print(f"[serve] recall@{args.k}: float={r_float:.4f} BEBR=n/a "
              f"({sum(r is None for r in first)}/{len(first)} first-round "
              "batches missed their deadline)")
    print(f"[serve] sequential: {1e3 * dt_seq / (len(batches) * args.rounds):.1f} "
          f"ms/batch ({n_q / dt_seq:.0f} QPS, warmed)")
    n_q_routed = sum(
        getattr(b, "n_queries", None) or b.shape[0] for b in stream
    )
    shed = f", {stats['shed']} shed" if stats["shed"] else ""
    print(f"[serve] routed ({args.replicas} replica(s), {args.router}): "
          f"{1e3 * dt_pipe / len(stream):.1f} ms/batch "
          f"({n_q_routed / dt_pipe:.0f} QPS; "
          f"p50={stats['latency_p50_ms']:.1f} ms "
          f"p99={stats['latency_p99_ms']:.1f} ms, scan stage waiting "
          f"for input {100 * stats['scan_input_wait_frac']:.0f}%{shed})")
    if args.replicas > 1:
        for s in stats["per_replica"]:
            print(f"[serve]   replica {s['replica']}: {s['requests']} req "
                  f"({s['queries']} queries), shed {s['shed']}, scan stage "
                  f"waiting for input {100 * s['scan_input_wait_frac']:.0f}%")
    if swap_report is not None:
        rep = swap_report
        print(f"[swap] rolling swap -> {rep.version.tag}: {rep.swapped} "
              f"replica(s) re-indexed in {rep.total_s * 1e3:.0f} ms under "
              f"live traffic (zero results lost)")
        for row in rep.replicas:
            print(f"[swap]   replica {row['replica']}: "
                  f"drain {row['drain_s'] * 1e3:.0f} ms, "
                  f"build {row['build_s'] * 1e3:.0f} ms, "
                  f"warm {row['warm_s'] * 1e3:.0f} ms, "
                  f"probe {row['probe_s'] * 1e3:.0f} ms "
                  f"(generation {row['generation']})")
    if to_version is not None and swap_report is not None:
        finals = [pr["embedding_version"] for pr in stats["per_replica"]]
        print(f"[upgrade] {from_version} -> {to_version} migration: "
              f"{stats['compat_dispatches']} compat-encoded dispatch(es) "
              f"covered the transition window; final replica versions "
              f"{finals}")
    if args.probe_every:
        print(f"[probe] canary re-probe every {args.probe_every}s: "
              f"{stats['revivals']} revival(s), states {stats['states']}")
    if args.deadline_ms:
        print(f"[deadline] {args.deadline_ms:.0f} ms budget: "
              f"{stats['deadline_expired']} expired "
              f"({sum(r is None for r in results)}/{len(results)} batches "
              "unanswered)")
    if args.scan_budget_ms:
        print(f"[watchdog] {args.scan_budget_ms:.0f} ms scan budget: "
              f"{stats['watchdog_stalls']} stall(s), "
              f"{stats['failovers']} failover(s)")
    if scaler is not None:
        sm = scaler.summary()
        print(f"[autoscale] spec [{sm['replicas_min']}, "
              f"{sm['replicas_max']}]: {sm['scale_ups']} scale-up(s), "
              f"{sm['scale_downs']} scale-down(s) over {sm['decisions']} "
              f"tick(s); replicas ended at {sm['replicas']} "
              f"(seen [{sm['min_replicas_seen']}, "
              f"{sm['max_replicas_seen']}])")
    for i, inj in sorted(injectors.items()):
        fired = ", ".join(f"{s}#{n}:{k}" for s, n, k in inj.log) or "none"
        print(f"[chaos] replica {i}: {len(inj.log)} fault(s) fired "
              f"({fired})")


if __name__ == "__main__":
    main()
