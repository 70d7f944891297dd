"""End-to-end system behaviour: the full BEBR pipeline on synthetic EBR
data — train binarizer (emb2emb, momentum queue), binarize corpus, build
index, search, and beat the 1-bit hash baseline while approaching the
float ceiling (paper Tables 1-2 at test scale)."""

import functools

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
from repro.index.flat import FlatFloat, FlatSDC

DIM, CODE, LEVELS = 64, 32, 4  # 2048-bit float -> 128-bit code (16x)


def _train_binarizer(docs, steps=300, n_levels=LEVELS, seed=0):
    from repro.train import optim

    # Warmup-decay recipe: the linear warmup spans the queue burn-in (the
    # momentum queue starts zero-filled, so early hard negatives are
    # junk), and the cosine decay sharpens convergence; 300 steps instead
    # of the seed's 150 lets the queue fully turn over. Lifts recall from
    # ~0.84 (below the 0.85*float bar) to ~0.92 on this corpus.
    cfg = TrainConfig(
        binarizer=BinarizerConfig(input_dim=DIM, code_dim=CODE,
                                  n_levels=n_levels, hidden_dim=128),
        queue=L.QueueConfig(length=1024, dim=CODE, top_k=32),
        adam=optim.AdamConfig(
            lr=2e-3, clip_norm=5.0,
            schedule=optim.cosine_schedule(steps, warmup=steps // 10,
                                           floor=0.05),
        ),
    )
    state = init_train_state(jax.random.PRNGKey(seed), cfg)
    step = jax.jit(functools.partial(train_step, cfg=cfg))
    gen = pair_batches(docs, seed + 1, 128)
    for _ in range(steps):
        a, p = next(gen)
        state, _ = step(state, a, p)
    return state, cfg


def _encode(state, cfg, emb):
    bits, _, _ = binarize_lib.binarize(
        state.params, state.bn_state, jnp.asarray(emb), cfg.binarizer
    )
    return pack_codes(bits)


def _recall_at(idx, gt, k):
    return float(jnp.mean(jnp.any(idx[:, :k] == jnp.asarray(gt)[:, None], -1)))


def test_bebr_end_to_end_recall():
    docs, queries, gt = clustered_corpus(0, 4000, 64, DIM, n_clusters=128)

    # float ceiling
    ff = FlatFloat.build(jnp.asarray(docs))
    _, idx_f = ff.search(jnp.asarray(queries), 10)
    r_float = _recall_at(idx_f, gt, 10)

    # recurrent binary (ours)
    state, cfg = _train_binarizer(docs)
    d_codes = _encode(state, cfg, docs)
    q_codes = _encode(state, cfg, queries)
    index = FlatSDC.build(d_codes, LEVELS, backend="interpret")
    _, idx_b = index.search(q_codes, 10)
    r_ours = _recall_at(idx_b, gt, 10)

    # 1-bit hash baseline (same trained stack restricted to the base level)
    state1, cfg1 = _train_binarizer(docs, n_levels=1, seed=3)
    d1 = _encode(state1, cfg1, docs)
    q1 = _encode(state1, cfg1, queries)
    index1 = FlatSDC.build(d1, 1, backend="interpret")
    _, idx_h = index1.search(q1, 10)
    r_hash = _recall_at(idx_h, gt, 10)

    # paper's ordering: hash <= ours <= float (ours ~ float)
    assert r_ours >= r_hash, (r_hash, r_ours, r_float)
    assert r_ours >= 0.85 * r_float, (r_hash, r_ours, r_float)
    # and the index is drastically smaller than float
    assert index.nbytes() < ff.nbytes() / 8


def test_training_is_restart_reproducible(tmp_path):
    """Binarizer training checkpoints and resumes to identical state."""
    from repro.train import checkpoint as ck

    docs, _, _ = clustered_corpus(1, 800, 8, DIM)
    cfg = TrainConfig(
        binarizer=BinarizerConfig(input_dim=DIM, code_dim=CODE, n_levels=2,
                                  hidden_dim=32),
        queue=L.QueueConfig(length=256, dim=CODE, top_k=8),
    )
    step = jax.jit(functools.partial(train_step, cfg=cfg))

    docs_gen = pair_batches(docs, 42, 32)
    hist = [next(docs_gen) for _ in range(10)]

    # uninterrupted: 10 steps
    st = init_train_state(jax.random.PRNGKey(0), cfg)
    for a, p in hist:
        st, _ = step(st, a, p)

    # interrupted at 5 + checkpoint + resume
    st2 = init_train_state(jax.random.PRNGKey(0), cfg)
    for a, p in hist[:5]:
        st2, _ = step(st2, a, p)
    ck.save(str(tmp_path), 5, st2)
    st3, _ = ck.restore(str(tmp_path), st2)
    for a, p in hist[5:]:
        st3, _ = step(st3, a, p)

    for a, b in zip(jax.tree_util.tree_leaves(st.params),
                    jax.tree_util.tree_leaves(st3.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
