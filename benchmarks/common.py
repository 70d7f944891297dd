"""Shared benchmark utilities: corpora, binarizer training, timing."""

from __future__ import annotations

import functools
import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.core.losses as L
from repro.core import (
    BinarizerConfig,
    TrainConfig,
    binarize_lib,
    init_train_state,
    pack_codes,
    train_step,
)
from repro.data.synthetic import clustered_corpus, pair_batches


# The paper's three products, at their published dimensionalities (the
# corpus sizes are scaled to CPU-runnable ones):
#   coco:      512-dim float (16384-bit) CLIP-like, -> 1024-bit codes
#   web:       256-dim float (8192-bit) web search, -> 512-bit codes
#   video:     128-dim float (4096-bit) copyright,  -> 256-bit codes
PRODUCTS = {
    "coco": dict(dim=512, code=256, levels=4, docs=8000, queries=256,
                 clusters=80, noise=0.30, qnoise=0.20, spectrum=0.5),
    "web": dict(dim=256, code=128, levels=4, docs=10000, queries=256,
                clusters=96, noise=0.30, qnoise=0.25, spectrum=0.5),
    "video": dict(dim=128, code=64, levels=4, docs=10000, queries=256,
                  clusters=96, noise=0.25, qnoise=0.20, spectrum=0.5),
}


def make_corpus(name: str):
    """One product's corpus (``PRODUCTS``) at its benchmark size."""
    spec = PRODUCTS[name]
    docs, queries, gt = clustered_corpus(
        hash(name) % 2**31, spec["docs"], spec["queries"], spec["dim"],
        n_clusters=spec["clusters"], noise=spec["noise"],
        query_noise=spec["qnoise"], spectrum=spec["spectrum"],
    )
    return docs, queries, gt, spec


def train_binarizer(docs: np.ndarray, dim: int, code: int, levels: int,
                    steps: int = 400, batch: int = 256, seed: int = 0,
                    lr: float = 2e-3):
    from repro.train import optim

    cfg = TrainConfig(
        binarizer=BinarizerConfig(input_dim=dim, code_dim=code,
                                  n_levels=levels, hidden_dim=2 * dim),
        queue=L.QueueConfig(length=16 * batch, dim=code, top_k=64),
        adam=optim.AdamConfig(lr=lr, clip_norm=5.0),
    )
    state = init_train_state(jax.random.PRNGKey(seed), cfg)
    step = jax.jit(functools.partial(train_step, cfg=cfg))
    gen = pair_batches(docs, seed + 1, batch, noise=0.08)
    t0 = time.time()
    for _ in range(steps):
        a, p = next(gen)
        state, metrics = step(state, a, p)
    wall = time.time() - t0
    return state, cfg, wall


def encode(state, cfg: TrainConfig, emb: np.ndarray, batch: int = 4096):
    outs = []
    for i in range(0, emb.shape[0], batch):
        bits, _, _ = binarize_lib.binarize(
            state.params, state.bn_state, jnp.asarray(emb[i:i + batch]),
            cfg.binarizer,
        )
        outs.append(pack_codes(bits))
    return jnp.concatenate(outs, 0)


def recall_at(idx: jax.Array, gt: np.ndarray, k: int) -> float:
    return float(jnp.mean(jnp.any(idx[:, :k] == jnp.asarray(gt)[:, None], -1)))


def timeit(fn, *args, warmup: int = 1, iters: int = 3) -> Tuple[float, object]:
    out = None
    for _ in range(warmup):
        out = jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters, out
