"""Bi-granular fine rerank: bit-identity to a restricted flat scan.

The tentpole invariant of the coarse-scan + fine-rerank mode: reranking
the coarse survivors against the full-level codes must be BIT-IDENTICAL
to a full-level flat scan restricted to exactly those ids — packed and
unpacked, Pallas-interpret and jnp-twin backends, the host-gathered
cold-tier path (``np.memmap`` included), and the k' < k degenerate case
where the survivor set cannot even fill the top-k. Plus the snapshot /
rerank-arg validation and the k_coarse-first effort split the serving
tier leans on.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.binarize_lib import SDC_NEG_INF, pack_codes_nibbles
from repro.index._snapshot import (
    resolve_rerank_args,
    resolve_snapshot_args,
    split_effort,
)
from repro.index.flat import BiGranularFlat, FlatSDC, flat_search_from_snapshot
from repro.kernels.sdc import ref as R
from repro.kernels.sdc.ops import sdc_search_xla
from repro.kernels.sdc.rerank import (
    fine_inv_norms,
    sdc_rerank,
    sdc_rerank_backend,
    sdc_rerank_gathered,
    sdc_rerank_xla,
)

LEVELS = 4


def _world(seed, n=96, q=3, d=8):
    key = jax.random.PRNGKey(seed)
    cd = jax.random.randint(key, (n, d), 0, 2**LEVELS).astype(jnp.int8)
    cq = jax.random.randint(jax.random.fold_in(key, 1), (q, d), 0,
                            2**LEVELS).astype(jnp.int8)
    return cd, cq, R.doc_inv_norms(cd, LEVELS)


def _candidates(seed, n, q, kp, n_invalid=0):
    """Distinct survivor ids per query, shuffled (NOT pre-sorted — the
    rerank must impose its own ascending-id order), with ``n_invalid``
    trailing -1 slots mixed in."""
    rng = np.random.default_rng(seed)
    cand = np.stack([
        rng.choice(n, size=kp, replace=False) for _ in range(q)
    ]).astype(np.int32)
    if n_invalid:
        for r in range(q):
            cand[r, rng.choice(kp, size=n_invalid, replace=False)] = -1
    return cand


def _restricted_scan(cq, cd, inv, cand, k):
    """Reference: a full-level flat scan over ONLY each query's candidate
    rows (gathered in ascending-id order, the column order of the full
    scan — so top-k tie-breaking matches)."""
    cd_np, inv_np = np.asarray(cd), np.asarray(inv)
    scores = np.full((cq.shape[0], k), SDC_NEG_INF, np.float32)
    ids = np.full((cq.shape[0], k), -1, np.int32)
    for qi in range(cq.shape[0]):
        c = np.asarray(cand[qi])
        c = np.sort(c[c >= 0])
        v, i = sdc_search_xla(
            cq[qi:qi + 1], jnp.asarray(cd_np[c]), jnp.asarray(inv_np[c]),
            n_levels=LEVELS, k=k,
        )
        v, i = np.asarray(v)[0], np.asarray(i)[0]
        scores[qi] = v
        ids[qi] = np.where(i >= 0, c[np.clip(i, 0, len(c) - 1)], -1)
    return scores, ids


def _assert_same(got, want):
    gs, gi = np.asarray(got[0]), np.asarray(got[1])
    np.testing.assert_array_equal(gi, want[1])
    np.testing.assert_array_equal(gs, want[0])


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), kp=st.sampled_from([5, 16]))
def test_rerank_bit_identical_to_restricted_scan(seed, kp):
    """interpret kernel, jnp twin, and host-gather all equal the
    restricted full-level scan exactly — scores AND ids, ties included
    (int8 codes collide constantly at d=8)."""
    cd, cq, inv = _world(seed)
    cand = _candidates(seed, cd.shape[0], cq.shape[0], kp)
    k = 4
    ref = _restricted_scan(cq, cd, inv, cand, k)
    _assert_same(
        sdc_rerank(cq, cd, inv, jnp.asarray(cand), n_levels=LEVELS, k=k,
                   interpret=True), ref)
    _assert_same(
        sdc_rerank_xla(cq, cd, inv, jnp.asarray(cand), n_levels=LEVELS, k=k),
        ref)
    _assert_same(
        sdc_rerank_gathered(cq, np.asarray(cd), np.asarray(inv), cand,
                            n_levels=LEVELS, k=k), ref)


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_packed_rerank_bit_identical_to_unpacked_reference(seed):
    """Nibble-packed fine codes go through the even/odd half-matmul
    decomposition — same integer sums, so bit-identical to the unpacked
    restricted scan (kernel-interpret and twin both)."""
    cd, cq, inv = _world(seed)
    cand = _candidates(seed + 1, cd.shape[0], cq.shape[0], 12)
    k = 4
    ref = _restricted_scan(cq, cd, inv, cand, k)
    pd = pack_codes_nibbles(cd)
    _assert_same(
        sdc_rerank(cq, pd, inv, jnp.asarray(cand), n_levels=LEVELS, k=k,
                   interpret=True, packed=True), ref)
    _assert_same(
        sdc_rerank_xla(cq, pd, inv, jnp.asarray(cand), n_levels=LEVELS, k=k,
                       packed=True), ref)
    _assert_same(
        sdc_rerank_gathered(cq, np.asarray(pd), np.asarray(inv), cand,
                            n_levels=LEVELS, k=k, packed=True), ref)


def test_degenerate_fewer_survivors_than_k():
    """k' < k: the rerank pads with (SDC_NEG_INF, -1) instead of reading
    out of range — and the filled prefix still matches the restricted
    scan."""
    cd, cq, inv = _world(7)
    cand = _candidates(7, cd.shape[0], cq.shape[0], 3)
    k = 10
    ref = _restricted_scan(cq, cd, inv, cand, k)
    out = sdc_rerank_xla(cq, cd, inv, jnp.asarray(cand), n_levels=LEVELS, k=k)
    _assert_same(out, ref)
    ids = np.asarray(out[1])
    assert (ids[:, 3:] == -1).all()
    assert (np.asarray(out[0])[:, 3:] == SDC_NEG_INF).all()


def test_invalid_slots_are_masked_not_clamped():
    """-1 survivor slots must not leak doc 0 (the kernel clamps probes
    into range; only cand_mask/id masking can exclude them)."""
    cd, cq, inv = _world(11)
    cand = _candidates(11, cd.shape[0], cq.shape[0], 8, n_invalid=3)
    k = 6
    ref = _restricted_scan(cq, cd, inv, cand, k)
    _assert_same(
        sdc_rerank(cq, cd, inv, jnp.asarray(cand), n_levels=LEVELS, k=k,
                   interpret=True), ref)
    _assert_same(
        sdc_rerank_gathered(cq, np.asarray(cd), np.asarray(inv), cand,
                            n_levels=LEVELS, k=k), ref)


@pytest.mark.parametrize("path", ["interpret", "xla", "gathered"])
def test_long_survivor_lists_pad_to_lane_tiles(path):
    """k' above GATHER_CHUNK and off the 128-lane grid: every path pads
    the one survivor list per query to whole lane tiles (id -1 slots)
    and still equals the restricted scan bit-for-bit."""
    from repro.kernels.sdc.defaults import GATHER_CHUNK

    kp = GATHER_CHUNK + 60
    cd, cq, inv = _world(5, n=kp + 100, q=2)
    cand = _candidates(5, cd.shape[0], cq.shape[0], kp, n_invalid=7)
    k = 6
    ref = _restricted_scan(cq, cd, inv, cand, k)
    if path == "gathered":
        got = sdc_rerank_gathered(cq, np.asarray(cd), np.asarray(inv), cand,
                                  n_levels=LEVELS, k=k, backend="interpret")
    elif path == "xla":
        got = sdc_rerank_xla(cq, cd, inv, jnp.asarray(cand),
                             n_levels=LEVELS, k=k)
    else:
        got = sdc_rerank(cq, cd, inv, jnp.asarray(cand), n_levels=LEVELS,
                         k=k, interpret=True)
    _assert_same(got, ref)


def test_backend_dispatch_memmap_cold_tier(tmp_path):
    """A memory-mapped fine tier takes the host-gather path and still
    matches the restricted scan bit-for-bit; fine_inv_norms streams the
    cold tier in chunks to the same values as a single-shot compute."""
    cd, cq, inv = _world(3)
    path = tmp_path / "fine.codes"
    mm = np.memmap(path, dtype=np.int8, mode="w+", shape=cd.shape)
    mm[:] = np.asarray(cd)
    mm.flush()
    cold = np.memmap(path, dtype=np.int8, mode="r", shape=cd.shape)
    inv_cold = fine_inv_norms(cold, LEVELS, chunk=17)
    np.testing.assert_array_equal(inv_cold, np.asarray(inv))
    cand = _candidates(3, cd.shape[0], cq.shape[0], 9)
    k = 5
    ref = _restricted_scan(cq, cd, inv, cand, k)
    _assert_same(
        sdc_rerank_backend(cq, cold, inv_cold, cand, n_levels=LEVELS, k=k),
        ref)


def test_bigranular_full_depth_equals_flat_search():
    """k_coarse = N degenerates to the plain full-level flat scan: every
    doc survives the coarse stage, so the rerank IS the flat scan."""
    cd, cq, inv = _world(5, n=128)
    bigr = BiGranularFlat.build(cd, LEVELS, coarse_levels=2,
                                k_coarse=cd.shape[0])
    flat = FlatSDC.build(cd, LEVELS, backend="xla")
    _assert_same(bigr.search(cq, 10),
                 tuple(np.asarray(x) for x in flat.search(cq, 10)))


def test_rerank_recall_never_below_coarse_recall():
    """Any true top-k doc the coarse scan surfaces in its top-k' is
    recovered by the exact fine rerank — rerank recall dominates the
    coarse-only recall it refines."""
    from repro.core.binarize_lib import coarse_codes

    cd, cq, inv = _world(17, n=256, q=8)
    k = 10
    _, gt = sdc_search_xla(cq, cd, inv, n_levels=LEVELS, k=k)
    gt = np.asarray(gt)
    bigr = BiGranularFlat.build(cd, LEVELS, coarse_levels=2, k_coarse=4 * k)
    _, ids_r = bigr.search(cq, k)
    _, ids_c = bigr.coarse.search(coarse_codes(cq, LEVELS, 2), k)

    def recall(ids):
        ids = np.asarray(ids)
        return np.mean([
            len(set(ids[i]) & set(gt[i])) / k for i in range(gt.shape[0])
        ])

    assert recall(ids_r) >= recall(ids_c)


def test_snapshot_closure_carries_rerank_provenance_and_effort():
    """flat_search_from_snapshot(..., rerank=...) marks the closure
    reranked (the serving tier stamps provenance off it); effort level 0
    is bit-identical to no effort, and degradation levels halve k'
    (floored via split_effort)."""
    cd, cq, _ = _world(23, n=128)
    rr = {"coarse_levels": 2, "k_coarse": 32}
    plain = flat_search_from_snapshot(cd, LEVELS, k=5, rerank=rr)
    assert plain.reranked is True
    knob = types.SimpleNamespace(level=0)
    with_knob = flat_search_from_snapshot(cd, LEVELS, k=5, rerank=rr,
                                          effort=knob)
    assert with_knob.reranked is True
    _assert_same(with_knob(cq), tuple(np.asarray(x) for x in plain(cq)))
    # deep degradation: the closure re-reads the knob per call and lands
    # on split_effort's k' floor (32 -> 16 -> 8; 8 // 5 halts halving)
    knob.level = 9
    kc_floor, _ = split_effort(9, k=5, k_coarse=32)
    bigr = BiGranularFlat.build(cd, LEVELS, coarse_levels=2, k_coarse=32)
    _assert_same(
        with_knob(cq),
        tuple(np.asarray(x) for x in bigr.search(cq, 5, k_coarse=kc_floor)))


def test_split_effort_halves_k_coarse_first():
    # level 0: full effort, nothing spent
    assert split_effort(0, k=10, k_coarse=160) == (160, 0)
    # each level halves k'; nothing falls through while k' > k
    assert split_effort(1, k=10, k_coarse=160) == (80, 0)
    assert split_effort(3, k=10, k_coarse=160) == (20, 0)
    # k' floors at k (160 >> 4 = 10); surplus levels fall through to the
    # family's own knobs (nprobe/ef/beam)
    assert split_effort(4, k=10, k_coarse=160) == (10, 0)
    assert split_effort(6, k=10, k_coarse=160) == (10, 2)
    # k' already at the floor: everything falls through
    assert split_effort(2, k=10, k_coarse=10) == (10, 2)


def test_resolve_rerank_args_validation():
    assert resolve_rerank_args(None, 4) is None
    assert resolve_rerank_args({"coarse_levels": 2, "k_coarse": 64}, 4) \
        == (2, 64)
    with pytest.raises(ValueError, match="keys"):
        resolve_rerank_args({"coarse_levels": 2}, 4)
    with pytest.raises(ValueError, match="keys"):
        resolve_rerank_args(
            {"coarse_levels": 2, "k_coarse": 64, "typo": 1}, 4)
    with pytest.raises(ValueError, match="coarse_levels"):
        resolve_rerank_args({"coarse_levels": 4, "k_coarse": 64}, 4)
    with pytest.raises(ValueError, match="coarse_levels"):
        resolve_rerank_args({"coarse_levels": 0, "k_coarse": 64}, 4)
    with pytest.raises(ValueError, match="k_coarse"):
        resolve_rerank_args({"coarse_levels": 2, "k_coarse": 0}, 4)


def test_snapshot_with_codes_but_no_levels_is_rejected():
    """Satellite fix: a malformed snapshot (codes present, n_levels
    None) must raise a clear TypeError instead of blaming the caller
    for omitting n_levels."""
    snap = types.SimpleNamespace(codes=np.zeros((4, 8), np.int8),
                                 n_levels=None)
    with pytest.raises(TypeError, match="n_levels is None"):
        resolve_snapshot_args(snap, None)
