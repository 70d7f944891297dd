"""Chip smoke test: the BEBR serving path, end to end, on TPU.

    python chip_smoke.py               # one chip: five index phases
    python chip_smoke.py --four-chips  # four chips: distributed engine only

One process drives the normal served path on the paper's "web" product
(``benchmarks.common.PRODUCTS``: 256-d float embeddings in 96 clusters,
a recurrent binarizer with hidden width 512, 128-d codes at 4 levels,
k=10): corpus -> binarizer trained on the
chip (``binarizer_cache.trained_binarizer``) -> corpus encoded on the
device -> index built by a ``lifecycle`` builder with ``backend="pallas"``
-> batches of 8 and 128 float queries served through a one-replica
``proxy.QueryRouter`` -> ``SearchResult``s.

One chip, 2**21 documents (fixed, not a flag):
  (a) flat, unpacked     (b) flat, nibble-packed
  (c) IVF on the gather kernel (nlist 1024, nprobe 32)
  (d) bi-granular flat (coarse_levels 2, k_coarse 100)
  (e) HNSW at 16,384 documents — the limit of its host-side O(N^2) build.

Every phase checks that the served ids equal the jnp twin's on the same
chip (the same builder with ``backend="xla"``), prints whether the
scores are bit-identical, holds recall@10 (each query's planted positive
in its top 10, beside the exact float search's) to the phase's floor,
and checks that the compiled serving program holds a Pallas kernel
(``tpu_custom_call``), so no interpreter or twin stood in.

``--four-chips`` serves 2**23 documents (four one-chip shards), drawn
from one generator on the device, through ``lifecycle.EngineBuilder``
over a 4-device mesh and through a two-replica router over two 2-chip
submeshes, and compares both with a single-device Pallas scan of the
same corpus, whose answers must reach every shard.

The script exits non-zero, with no ``ok`` line, when JAX finds no TPU,
when any phase raises, or when any check fails. Its last line is one
JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# ruff: noqa: E402
import jax
import jax.numpy as jnp
import numpy as np

N_DOCS = 2**21
N_DOCS_FOUR_CHIPS = 2**23
N_DOCS_HNSW = 16384
N_QUERIES = 256
SEED = 0
K = 10
BATCHES = (8, 128)
TRAIN_STEPS = 300

# Recall@10 (planted positive in the top 10) per phase, at this seed.
# Flat search at 2**21 docs after 300 training steps measured 0.203 on
# a CPU (jnp backend, f32 throughout), and on a v5e chip 0.184 with the
# binarizer's matmuls at default precision, 0.188 at HIGHEST. The 128-d
# codes, not the chip, set that level. HNSW at 16,384 docs measured
# 0.535 on the chip. A broken path scores ~1e-5 (10 of 2**21 at random);
# each floor sits below its readings by more than their spread.
RECALL_FLOORS = {
    "flat": 0.15,
    "flat-packed": 0.15,
    "ivf": 0.15,
    "bigranular": 0.15,
    "hnsw": 0.45,
}


class CheckFailed(RuntimeError):
    """A smoke check did not hold."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def web_corpus(seed: int, n_docs: int, n_queries: int):
    """The paper's "web" product corpus (``benchmarks.common.PRODUCTS``)
    at ``n_docs`` documents: (docs, queries, planted positive ids)."""
    from benchmarks.common import PRODUCTS
    from repro.data import synthetic

    web = PRODUCTS["web"]
    return synthetic.clustered_corpus(
        seed, n_docs, n_queries, web["dim"], n_clusters=web["clusters"],
        noise=web["noise"], query_noise=web["qnoise"],
        spectrum=web["spectrum"],
    )


def web_configs():
    """The paper's "web" product widths, at serve.py's training recipe."""
    from benchmarks.common import PRODUCTS
    from repro.core import BinarizerConfig, TrainConfig
    import repro.core.losses as losses_lib
    from repro.train import optim

    web = PRODUCTS["web"]
    dim, code = web["dim"], web["code"]
    bcfg = BinarizerConfig(input_dim=dim, code_dim=code,
                           n_levels=web["levels"], hidden_dim=2 * dim)
    tcfg = TrainConfig(
        binarizer=bcfg,
        queue=losses_lib.QueueConfig(length=4096, dim=code, top_k=64),
        adam=optim.AdamConfig(lr=2e-3, clip_norm=5.0),
    )
    return bcfg, tcfg


def train_encoder(docs, tcfg):
    """Train the binarizer on the device; returns the serving encode fn."""
    from repro.core import binarize_lib
    from repro.launch import binarizer_cache

    t0 = time.perf_counter()
    # A private cache directory: the run always trains, and leaves no file.
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = binarizer_cache.trained_binarizer(
            docs, tcfg, steps=TRAIN_STEPS, seed=SEED, cache_dir=tmp
        )
    log(f"[train] binarizer trained on the device: {TRAIN_STEPS} steps in "
        f"{time.perf_counter() - t0:.1f} s")
    return binarize_lib.make_encode_fn(ckpt.params, ckpt.bn_state,
                                       tcfg.binarizer)


def encode_corpus(encode, docs, chunk: int = 65536):
    """[N, dim] floats -> [N, code] int8 codes, resident on the device."""
    out = [encode(docs[i:i + chunk]) for i in range(0, docs.shape[0], chunk)]
    return jax.block_until_ready(jnp.concatenate(out, 0))


def recall_at_k(ids, gt) -> float:
    """Share of queries whose planted positive is among their ids."""
    return float(np.mean(np.any(np.asarray(ids) == np.asarray(gt)[:, None],
                                axis=-1)))


def float_recall(docs, queries, gt, chunk: int = 64) -> float:
    """Recall@K of the exact float search (cosine): the baseline. Queries
    go in chunks, so the [chunk, N] score matrix stays small."""
    from repro.index.flat import FlatFloat

    index = FlatFloat.build(jnp.asarray(docs))
    ids = [jax.device_get(index.search(jnp.asarray(queries[i:i + chunk]), K)[1])
           for i in range(0, queries.shape[0], chunk)]
    return recall_at_k(np.concatenate(ids), gt)


def compiled_program(search, q_codes):
    """Compile the whole traced serving program for one batch shape.

    The index arrays the search closure holds become arguments of the
    program (not constants), exactly as the served calls pass them.
    Returns (compiled executable, seconds).
    """
    from jax.extend import core as jex_core

    t0 = time.perf_counter()
    closed = jax.make_jaxpr(search)(q_codes)

    def program(consts, q):
        return jex_core.jaxpr_as_fun(jex_core.ClosedJaxpr(closed.jaxpr, consts))(q)

    compiled = jax.jit(program).lower(closed.consts, q_codes).compile()
    return compiled, time.perf_counter() - t0


def serve_through_router(encode, search, queries, *, want_reranked=False):
    """Serve batches of 8 and of 128 through a one-replica router.

    Returns {batch size: per-query (scores, ids)} from the
    ``SearchResult``s, in query order.
    """
    from repro.launch import proxy, serving

    out = {}
    for b in BATCHES:
        batches = [queries[i:i + b] for i in range(0, queries.shape[0], b)]
        serving.warmup_replicas([(encode, search)], batches[:1])
        router = proxy.QueryRouter(proxy.ReplicaSet([(encode, search)]))
        try:
            tickets = [router.submit(serving.SearchRequest(queries=x))
                       for x in batches]
            results = [t.search_result(timeout=600) for t in tickets]
        finally:
            router.close()
        for r in results:
            check(isinstance(r, serving.SearchResult), "router result type")
            check(r.reranked == want_reranked,
                  f"SearchResult.reranked={r.reranked}, want {want_reranked}")
        out[b] = (np.concatenate([np.asarray(r.scores) for r in results]),
                  np.concatenate([np.asarray(r.ids) for r in results]))
    return out


def twin_results(twin_search, q_codes, chunk: int = 8):
    """The jnp twin's answers, 8 queries at a time (its gathers are
    [Q, nprobe, L, D]-sized)."""
    outs = [jax.device_get(twin_search(q_codes[i:i + chunk]))
            for i in range(0, q_codes.shape[0], chunk)]
    return (np.concatenate([o[0] for o in outs]),
            np.concatenate([o[1] for o in outs]))


def compare(tag: str, got, want) -> None:
    """Ids must be equal; scores are reported as bit-identical or not."""
    (gs, gi), (ws, wi) = got, want
    check(np.array_equal(gi, wi),
          f"{tag}: ids differ from the reference in "
          f"{int(np.sum(np.any(gi != wi, axis=1)))} of {gi.shape[0]} queries")
    same = np.array_equal(gs, ws)
    diff = "" if same else f", largest difference {np.max(np.abs(gs - ws)):.3g}"
    log(f"[{tag}] ids equal over {gi.shape[0]} queries; scores "
        f"bit-identical: {same}{diff}")


def run_phase(name, builder, twin_builder, snapshot, encode, queries, gt,
              base_recall, *, want_reranked=False):
    """Build, compile, serve and check one index phase."""
    dev = jax.devices()[0]
    log(f"=== phase {name}: {snapshot.codes.shape[0]} docs on "
        f"{dev.platform} {dev.device_kind} x{len(jax.devices())} ===")
    t0 = time.perf_counter()
    search = builder.build(snapshot)
    log(f"[{name}] built in {time.perf_counter() - t0:.1f} s")
    q_codes = encode(queries)
    for b in BATCHES:
        compiled, dt = compiled_program(search, q_codes[:b])
        has_kernel = "tpu_custom_call" in compiled.as_text()
        log(f"[{name}] compile, batch {b}: {dt:.1f} s; Pallas kernel in "
            f"the program: {has_kernel}")
        check(has_kernel, f"{name}: no tpu_custom_call in the compiled "
                          f"batch-{b} program")
    t0 = time.perf_counter()
    served = serve_through_router(encode, search, queries,
                                  want_reranked=want_reranked)
    log(f"[{name}] served {len(BATCHES)} streams through the router in "
        f"{time.perf_counter() - t0:.1f} s (compiles included)")
    twin = twin_results(twin_builder.build(snapshot), q_codes)
    for b, got in served.items():
        compare(f"{name} batch {b} vs jnp twin", got, twin)
    rec = recall_at_k(served[BATCHES[-1]][1], gt)
    floor = RECALL_FLOORS[name]
    log(f"[{name}] recall@{K}: {rec:.4f} (floor {floor}; exact float "
        f"search {base_recall:.4f})")
    check(rec >= floor, f"{name}: recall {rec:.4f} below floor {floor}")


def one_chip() -> None:
    from repro.launch import lifecycle

    bcfg, tcfg = web_configs()
    t0 = time.perf_counter()
    docs, queries, gt = web_corpus(SEED, N_DOCS, N_QUERIES)
    log(f"[data] web corpus seed={SEED}: {N_DOCS} docs x "
        f"{bcfg.input_dim}-d, {N_QUERIES} queries in "
        f"{time.perf_counter() - t0:.1f} s")
    encode = train_encoder(docs, tcfg)
    t0 = time.perf_counter()
    codes = encode_corpus(encode, docs)
    log(f"[encode] {codes.shape[0]} codes x {codes.shape[1]} on the device "
        f"in {time.perf_counter() - t0:.1f} s")
    base = float_recall(docs, queries, gt)
    del docs
    # HNSW's host-side build is O(N^2): it gets a corpus of its own size
    # (same seed, so the same cluster centres), with its own queries.
    docs_h, queries_h, gt_h = web_corpus(SEED, N_DOCS_HNSW, N_QUERIES)
    codes_h = encode_corpus(encode, docs_h)
    base_h = float_recall(docs_h, queries_h, gt_h)

    levels = bcfg.n_levels
    full = (lifecycle.CorpusSnapshot(codes=codes, n_levels=levels),
            queries, gt, base)
    small = (lifecycle.CorpusSnapshot(codes=codes_h, n_levels=levels),
             queries_h, gt_h, base_h)
    phases = [
        ("flat", lifecycle.FlatBuilder, dict(k=K), full),
        ("flat-packed", lifecycle.FlatBuilder, dict(k=K, packed=True), full),
        ("ivf", lifecycle.IVFBuilder, dict(k=K, nlist=1024, nprobe=32, seed=1),
         full),
        ("bigranular", lifecycle.FlatBuilder,
         dict(k=K, coarse_levels=2, k_coarse=100), full),
        ("hnsw", lifecycle.HNSWBuilder,
         dict(k=K, M=16, ef_construction=64, ef=64, beam=8), small),
    ]
    for name, cls, params, (snap, qs, g, b) in phases:
        run_phase(
            name, cls(backend="pallas", **params), cls(backend="xla", **params),
            snap, encode, qs, g, b, want_reranked="coarse_levels" in params,
        )


def web_corpus_on_device(seed: int, n_docs: int, n_queries: int,
                         chunk: int = 2**20):
    """The generative model of ``synthetic.clustered_corpus`` at the web
    product's widths, drawn on the device in chunks of ``chunk`` rows.

    One set of cluster centres and one anisotropic scale + rotation hold
    for every row, so a query's same-cluster documents are spread over
    the whole row range. Returns (emb, queries [n_queries, dim], gt):
    ``emb(i)`` gives rows [i * chunk, (i + 1) * chunk) as unit vectors on
    the device, redrawn identically on every call, and queries are noisy
    views of their planted rows ``gt``, drawn over all ``n_docs`` rows.
    """
    from benchmarks.common import PRODUCTS

    web = PRODUCTS["web"]
    dim = web["dim"]
    rng = np.random.default_rng(seed)
    centers = jnp.asarray(rng.normal(size=(web["clusters"], dim)), jnp.float32)
    scales = jnp.asarray(1.0 / (1.0 + np.arange(dim)) ** web["spectrum"],
                         jnp.float32)
    rot = jnp.asarray(np.linalg.qr(rng.normal(size=(dim, dim)))[0], jnp.float32)
    gt = rng.choice(n_docs, size=n_queries, replace=False)
    key = jax.random.PRNGKey(seed)

    def unit(raw):
        x = jnp.dot(raw * scales, rot, precision=jax.lax.Precision.HIGHEST)
        return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)

    @jax.jit
    def raw(i):
        ka, kn = jax.random.split(jax.random.fold_in(key, i))
        assign = jax.random.randint(ka, (chunk,), 0, web["clusters"])
        return centers[assign] + web["noise"] * jax.random.normal(
            kn, (chunk, dim))

    emb = jax.jit(lambda i: unit(raw(i)))
    q_raw = np.zeros((n_queries, dim), np.float32)
    for i in range(n_docs // chunk):
        mine = gt // chunk == i
        if mine.any():
            q_raw[mine] = np.asarray(raw(i)[gt[mine] - i * chunk])
    q_noise = jax.random.normal(jax.random.fold_in(key, n_docs // chunk),
                                (n_queries, dim))
    queries = np.asarray(unit(jnp.asarray(q_raw) + web["qnoise"] * q_noise))
    return emb, queries, gt


def placed_corpus(search, q_codes, n_rows: int):
    """The device-placed corpus array the serving program closes over: the
    one [n_rows, ...] constant of its traced program."""
    consts = [c for c in jax.make_jaxpr(search)(q_codes).consts
              if isinstance(c, jax.Array) and c.ndim == 2
              and c.shape[0] == n_rows]
    check(len(consts) == 1, f"{len(consts)} corpus-sized arrays in the "
                            f"serving program, want 1")
    return consts[0]


def four_chips() -> None:
    from repro.kernels.sdc import ref as sdc_ref
    from repro.kernels.sdc.ops import sdc_search_backend
    from repro.launch import lifecycle, proxy, serving
    from repro.launch.mesh import make_host_mesh, make_replica_meshes

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, "
                                   f"found {len(jax.devices())}")
    bcfg, tcfg = web_configs()
    n, chunk, shard = N_DOCS_FOUR_CHIPS, 2**20, N_DOCS_FOUR_CHIPS // 4
    t0 = time.perf_counter()
    emb, queries, gt = web_corpus_on_device(SEED, n, N_QUERIES, chunk)
    encode = train_encoder(np.asarray(emb(0)), tcfg)
    codes = jnp.concatenate(
        [encode_corpus(encode, emb(i)) for i in range(n // chunk)], 0)
    log(f"[data] {n} web-corpus docs (one generator, drawn on the device) "
        f"encoded in {time.perf_counter() - t0:.1f} s; planted rows per "
        f"shard: {np.bincount(gt // shard, minlength=4).tolist()}")
    snapshot = lifecycle.CorpusSnapshot(codes=codes, n_levels=bcfg.n_levels)
    q_codes = encode(queries)

    # Reference: one Pallas scan of the whole corpus on one device.
    inv = sdc_ref.doc_inv_norms(codes, bcfg.n_levels)
    ref = jax.device_get(sdc_search_backend(
        q_codes, codes, inv, n_levels=bcfg.n_levels, k=K, backend="pallas"
    ))
    log(f"[reference] single-device pallas scan of {n} docs on "
        f"{codes.devices()}")
    # Every shard must hold reference answers, or a leaf that returns
    # nothing (or a wrong global-id offset) would go unseen.
    per_shard = np.bincount(ref[1].ravel() // shard, minlength=4).tolist()
    log(f"[reference] top-{K} ids per shard of {shard} rows: {per_shard}")
    check(len(per_shard) == 4 and min(per_shard) > 0,
          f"reference top-{K} ids do not reach every shard: {per_shard}")

    log("=== phase engine: 4-device mesh ===")
    mesh = make_host_mesh((2, 2))
    engine = lifecycle.EngineBuilder(
        [mesh], index="flat", n_levels=bcfg.n_levels, k=K, backend="pallas"
    )
    search = engine.build(snapshot)
    placed = placed_corpus(search, q_codes[:BATCHES[0]], n)
    shard_devices = [str(s.device) for s in placed.addressable_shards]
    for s in placed.addressable_shards:
        log(f"[engine] shard rows {s.index[0].start}:{s.index[0].stop} on "
            f"{s.device}")
    check(len(set(shard_devices)) == 4,
          f"engine shards sit on {len(set(shard_devices))} devices, want 4")
    for b in BATCHES:
        compiled, dt = compiled_program(search, q_codes[:b])
        has_kernel = "tpu_custom_call" in compiled.as_text()
        log(f"[engine] compile, batch {b}: {dt:.1f} s; Pallas kernel in the "
            f"program: {has_kernel}")
        check(has_kernel, f"engine: no tpu_custom_call in the batch-{b} "
                          f"program")
        got = [jax.device_get(search(q_codes[i:i + b]))
               for i in range(0, N_QUERIES, b)]
        compare(f"engine 4-device mesh batch {b} vs single-device scan",
                (np.concatenate([g[0] for g in got]),
                 np.concatenate([g[1] for g in got])), ref)

    log("=== phase engine replicas: 2 x (1, 2) submeshes behind the router ===")
    meshes = make_replica_meshes(2, shape=(1, 2))
    replicas = lifecycle.EngineBuilder(
        meshes, index="flat", n_levels=bcfg.n_levels, k=K, backend="pallas"
    )
    fns = [(encode, replicas.build(snapshot, replica=i)) for i in range(2)]
    for i, (_, fn) in enumerate(fns):
        placed = placed_corpus(fn, q_codes[:BATCHES[0]], n)
        devs = sorted({str(s.device) for s in placed.addressable_shards})
        log(f"[replicas] replica {i} shards on {devs}")
        check(len(devs) == 2, f"replica {i} shards sit on {devs}, want 2 "
                              f"devices")
    for b in BATCHES:
        batches = [queries[i:i + b] for i in range(0, N_QUERIES, b)]
        serving.warmup_replicas(fns, batches[:1])
        router = proxy.QueryRouter(proxy.ReplicaSet(fns))
        try:
            tickets = [router.submit(serving.SearchRequest(queries=x))
                       for x in batches]
            results = [t.search_result(timeout=600) for t in tickets]
        finally:
            router.close()
        served_by = sorted({r.replica for r in results})
        log(f"[replicas] batch {b}: {len(results)} results from replicas "
            f"{served_by}")
        check(served_by == [0, 1] or len(results) < 2,
              "the router did not use both replicas")
        compare(f"router 2x2-chip batch {b} vs single-device scan",
                (np.concatenate([np.asarray(r.scores) for r in results]),
                 np.concatenate([np.asarray(r.ids) for r in results])), ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed engine on 4 chips and "
                         "its single-device comparison")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir, warm = enable_compile_cache()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"[device] platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    log(f"[compile-cache] {cache_dir} ({'warm' if warm else 'cold'})")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
