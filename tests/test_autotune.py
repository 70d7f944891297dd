"""Persistent block-plan autotuner (launch/autotune.py).

The tuner's contract mirrors the binarizer checkpoint cache: a cache
hit reloads exactly the plan the first toucher swept, every signature
knob moves the digest, and a corrupt or stale entry is re-tuned, never
trusted. On top of that sits the one invariant that makes autotuning
safe to ship at all: block plans change LAUNCH GEOMETRY only — any
plan, tuned or not, must produce bit-identical scores and ids.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.index.flat import FlatSDC
from repro.kernels.sdc.defaults import (
    BlockPlan,
    default_plan,
    plan_for,
)
from repro.kernels.sdc.ops import sdc_search_backend
from repro.launch import autotune

M, N, LEVELS = 16, 64, 2


def _codes(seed=0, n=N, m=M, q=4):
    rng = np.random.default_rng(seed)
    hi = 2 ** LEVELS
    cd = jnp.asarray(rng.integers(0, hi, size=(n, m)).astype(np.int8))
    cq = jnp.asarray(rng.integers(0, hi, size=(q, m)).astype(np.int8))
    return cd, cq


def _tune(kind="scan", cache_dir=None, **kw):
    kw.setdefault("code_dim", M)
    kw.setdefault("n_shard", N)
    kw.setdefault("k", 4)
    kw.setdefault("n_levels", LEVELS)
    kw.setdefault("backend", "interpret")
    kw.setdefault("sample_q", 2)
    kw.setdefault("reps", 1)
    return autotune.tuned_block_plan(kind, cache_dir=cache_dir, **kw)


def test_second_call_is_a_cache_hit(tmp_path):
    first = _tune(cache_dir=str(tmp_path))
    assert first.tuned is True
    assert first.plan.source == "tuned"
    second = _tune(cache_dir=str(tmp_path))
    assert second.tuned is False
    assert second.plan.source == "cache"
    assert second.digest == first.digest
    assert second.path == first.path
    assert second.plan.blocks() == first.plan.blocks()


def test_replicas_sharing_a_cache_dir_share_one_plan(tmp_path):
    # Replica launches differ only in who touched the cache first; all
    # of them must serve with the winner the first sweep persisted.
    plans = [_tune(cache_dir=str(tmp_path)) for _ in range(3)]
    assert [p.tuned for p in plans] == [True, False, False]
    assert len({p.plan.blocks() for p in plans}) == 1
    assert len({p.path for p in plans}) == 1


def test_every_signature_knob_moves_the_digest():
    base = dict(code_dim=M, n_shard=N, packed=False, k=4,
                backend="interpret")
    d0 = autotune.plan_digest("scan", **base)
    assert autotune.plan_digest("scan", **base) == d0
    for var in (
        dict(base, code_dim=2 * M),
        dict(base, n_shard=2 * N),
        dict(base, packed=True),
        dict(base, k=8),
        dict(base, backend="pallas"),
    ):
        assert autotune.plan_digest("scan", **var) != d0
    assert autotune.plan_digest("rerank", **base) != d0


def test_corrupt_plan_is_retuned_not_trusted(tmp_path):
    first = _tune(cache_dir=str(tmp_path))
    with open(first.path, "w") as f:
        f.write("not json {")
    again = _tune(cache_dir=str(tmp_path))
    assert again.tuned is True
    assert again.path == first.path


def test_stale_signature_is_retuned(tmp_path):
    first = _tune(cache_dir=str(tmp_path))
    with open(first.path) as f:
        payload = json.load(f)
    payload["signature"]["n_shard"] = N + 1  # drifted world
    with open(first.path, "w") as f:
        json.dump(payload, f)
    again = _tune(cache_dir=str(tmp_path))
    assert again.tuned is True


def test_corrupt_blocks_are_retuned(tmp_path):
    first = _tune(cache_dir=str(tmp_path))
    with open(first.path) as f:
        payload = json.load(f)
    payload["block_q"] = "wat"
    with open(first.path, "w") as f:
        json.dump(payload, f)
    assert _tune(cache_dir=str(tmp_path)).tuned is True


def test_env_var_override_is_honored(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path))
    tp = _tune(cache_dir=None)
    assert tp.path.startswith(str(tmp_path))


def test_explicit_cache_dir_beats_env(tmp_path, monkeypatch):
    env_dir, arg_dir = tmp_path / "env", tmp_path / "arg"
    monkeypatch.setenv(autotune.CACHE_ENV, str(env_dir))
    tp = _tune(cache_dir=str(arg_dir))
    assert tp.path.startswith(str(arg_dir))
    assert not env_dir.exists()


def test_unsweepable_signatures_short_circuit():
    # xla has no kernel tiles; gather's geometry is corpus-fixed.
    inert = _tune("scan", backend="xla")
    assert inert.plan.source == "inert-backend"
    assert inert.path is None and inert.tuned is False
    fixed = _tune("gather", backend="interpret")
    assert fixed.plan.source == "fixed-geometry"
    assert fixed.plan.blocks() == default_plan("gather").blocks()


def test_sweep_payload_records_paired_timings(tmp_path):
    # The bench gate reads default_ms/tuned_ms straight from this
    # payload; tuned is the min over all candidates INCLUDING the
    # default, so it can never exceed default.
    tp = _tune(cache_dir=str(tmp_path))
    with open(tp.path) as f:
        payload = json.load(f)
    assert payload["default_ms"] is not None
    assert payload["tuned_ms"] is not None
    assert payload["tuned_ms"] <= payload["default_ms"]
    assert payload["default_blocks"] == list(default_plan("scan").blocks())


def _refusing(refused):
    """A candidate timer whose launch raises for the ``refused`` blocks,
    as the chip's compiler does for an illegal tile."""
    real = autotune._candidate_timer

    def timer(kind, blocks, *args, **kw):
        if blocks in refused:
            def launch():
                raise RuntimeError(f"compiler refused {blocks}")
            return launch
        return real(kind, blocks, *args, **kw)

    return timer


def test_refused_candidates_are_skipped_and_counted(tmp_path, monkeypatch):
    grid = autotune.candidate_grid("scan", code_dim=M, n_shard=N,
                                   packed=False, k=4)
    refused = set(grid[1:3])
    monkeypatch.setattr(autotune, "_candidate_timer", _refusing(refused))
    tp = _tune(cache_dir=str(tmp_path))
    assert tp.tuned and tp.skipped == len(refused)
    assert tp.plan.blocks() not in refused
    with open(tp.path) as f:
        assert json.load(f)["skipped"] == len(refused)


def test_default_plan_compile_failure_raises(tmp_path, monkeypatch):
    # The default plan is what serves untuned: a sweep must not hide its
    # failure behind a "tuned" plan nothing ever timed.
    monkeypatch.setattr(autotune, "_candidate_timer",
                        _refusing({default_plan("scan").blocks()}))
    with pytest.raises(RuntimeError, match="compiler refused"):
        _tune(cache_dir=str(tmp_path))


def test_candidate_grid_leads_with_the_default():
    for kind in ("scan", "rerank", "gather"):
        grid = autotune.candidate_grid(kind, code_dim=M, n_shard=N,
                                       packed=False, k=4)
        assert grid[0] == default_plan(kind).blocks()
        assert len(grid) == len(set(grid))


def test_any_plan_is_bit_identical_through_the_scan(tmp_path):
    cd, cq = _codes()
    inv = jnp.ones(N, jnp.float32)
    ref_s, ref_i = sdc_search_backend(cq, cd, inv, n_levels=LEVELS, k=4,
                                      backend="interpret")
    tuned = _tune(cache_dir=str(tmp_path))
    for plan in (tuned.plan, BlockPlan("scan", 8, 256, "tuned")):
        s, i = sdc_search_backend(cq, cd, inv, n_levels=LEVELS, k=4,
                                  backend="interpret", block_plan=plan)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(ref_s))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))


def test_plan_is_bit_identical_through_flat_index():
    cd, cq = _codes(seed=3)
    index = FlatSDC.build(cd, n_levels=LEVELS, backend="interpret")
    ref_s, ref_i = index.search(cq, 4)
    s, i = index.search(cq, 4,
                        block_plan=BlockPlan("scan", 8, 128, "tuned"))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(ref_s))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))


def test_rerank_geometry_is_fixed(tmp_path):
    # The rerank scores one survivor list per query on the gather
    # kernel: nothing to sweep, nothing written to the cache.
    assert autotune.candidate_grid("rerank", code_dim=M, n_shard=N,
                                   packed=False, k=4) == [
        default_plan("rerank").blocks()]
    tp = _tune("rerank", cache_dir=str(tmp_path))
    assert tp.plan.source == "fixed-geometry" and tp.path is None
    assert not list(tmp_path.iterdir())


def test_plan_for_selects_by_kind():
    scan = BlockPlan("scan", 8, 256, "tuned")
    rerank = BlockPlan("rerank", 1, 8, "tuned")
    assert plan_for(None, "scan") is None
    assert plan_for(scan, "scan") is scan
    assert plan_for(scan, "rerank") is None  # single plan, other kind
    mapping = {"scan": scan, "rerank": rerank}
    assert plan_for(mapping, "rerank") is rerank
    assert plan_for(mapping, "gather") is None
    with pytest.raises(ValueError, match="kind"):
        plan_for({"scan": rerank}, "scan")  # mislabeled entry


def test_shape_errors_carry_the_offending_shapes():
    cd, cq = _codes()
    inv = jnp.ones(N, jnp.float32)
    with pytest.raises(ValueError, match=r"code dim"):
        # packed flag promised half-width codes but got full-width ones
        sdc_search_backend(cq, cd, inv, n_levels=LEVELS, k=4,
                           backend="interpret", packed=True)
