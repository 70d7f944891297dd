"""Persistent block-plan autotuner for the SDC kernel family.

Every Pallas scan launch used to run with the hand-picked
tiles from ``kernels/sdc/defaults.py`` regardless of the live corpus
shape. This module closes that gap: on first use of a kernel signature
``(kind, code_dim, n_shard, packed, k, backend)`` it sweeps a small
candidate grid of ``(block_q, block_n)`` launch shapes, times each one
on synthetic operands of the live shapes (block choices never change
scores — only launch geometry — so random codes time exactly like real
ones), and persists the winner in a digest-keyed cache file.

The cache follows ``launch/binarizer_cache.py`` exactly: one file per
digest under ``--tune-cache`` / ``$REPRO_BEBR_CACHE`` /
``~/.cache/repro-bebr``, written atomically (tmp + rename) so a crashed
run never leaves a half-written plan, and validated on load — a corrupt
or stale entry (unreadable JSON, signature drift, non-integer blocks)
is re-tuned, never trusted. Replicas and repeat launches that share a
cache directory therefore share one tuned plan: the first toucher pays
the sweep, everyone else loads its winner.

Two signatures are never swept:

  * the "xla" backend has no tiles — blocks are inert, so the default
    plan comes back immediately (source "inert-backend");
  * kinds "gather" and "rerank" have layout-fixed geometry (one probed
    list per grid step; the list length is the tile — an inverted list,
    or one query's survivors), so there is nothing to sweep (source
    "fixed-geometry").

The sweep always times the default plan alongside the candidates and
keeps it unless a candidate is strictly faster, so a tuned plan can
only tie or beat the table on the shapes it was tuned for. A default
plan that fails to compile raises (it is what serves untuned); a
refused non-default candidate is skipped and counted (``skipped``).
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sdc.defaults import BlockPlan, default_plan
from repro.kernels.sdc.ops import resolve_backend, sdc_search_backend
from repro.launch.binarizer_cache import CACHE_ENV, resolve_cache_dir

__all__ = [
    "CACHE_ENV",
    "TunedPlan",
    "candidate_grid",
    "plan_digest",
    "resolve_cache_dir",
    "tuned_block_plan",
]

# 2: the packed scan's corpus is [D//2, N], documents along lanes; a plan
# tuned on the [N, D//2] layout of schema 1 does not reload.
_SCHEMA = 2

# Kernel kinds whose launch geometry the list layout fixes.
FIXED_GEOMETRY = ("gather", "rerank")


class TunedPlan(NamedTuple):
    """A block plan, plus where it came from.

    ``tuned`` is False when the plan was loaded from the cache (or the
    signature is un-sweepable); mirrors
    ``binarizer_cache.BinarizerCheckpoint.trained``.
    """

    plan: BlockPlan
    digest: str
    path: str | None
    tuned: bool
    skipped: int = 0  # non-default candidates the compiler refused


def plan_digest(
    kind: str, *, code_dim: int, n_shard: int, packed: bool, k: int,
    backend: str,
) -> str:
    """Digest of everything that determines the winning plan."""
    h = hashlib.sha1()
    h.update(str(("tuneplan", _SCHEMA)).encode())
    h.update(str((kind, code_dim, n_shard, bool(packed), k, backend)).encode())
    return h.hexdigest()[:20]


def candidate_grid(
    kind: str, *, code_dim: int, n_shard: int, packed: bool, k: int,
) -> list[tuple[int, int]]:
    """The (block_q, block_n) sweep for a signature, default plan first.

    Deliberately small — the sweep runs on a live serving path. Scan
    candidates stay sublane/lane aligned (block_q multiple of 8,
    block_n multiple of 128) and never exceed the padded corpus, so
    every candidate is a legal launch. Fixed-geometry kinds have only
    the default.
    """
    base = default_plan(kind)
    if kind in FIXED_GEOMETRY:
        return [base.blocks()]
    # kind == "scan"
    cands: list[tuple[int, int]] = [base.blocks()]
    for bq in (8, 32, 128):
        for bn in (256, 512, 1024):
            if bn < 128 or (bq, bn) in cands:
                continue
            cands.append((bq, bn))
    return cands


def _time_call(fn, *, reps: int) -> float:
    """Median wall-clock seconds of ``fn`` after one untimed warmup."""
    jax.block_until_ready(fn())  # compile + warm caches
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples))


def _sweep_operands(
    kind: str, *, code_dim: int, n_shard: int, packed: bool, k: int,
    sample_q: int, n_levels: int, seed: int = 0,
):
    """Synthetic live-shape operands (values are irrelevant to timing)."""
    rng = np.random.default_rng(seed)
    hi = 2 ** n_levels
    q = jnp.asarray(
        rng.integers(0, hi, size=(sample_q, code_dim)).astype(np.int8)
    )
    if packed:
        # The packed flat corpus lies documents along lanes.
        d = rng.integers(0, 256, size=(code_dim // 2, n_shard))
        d = jnp.asarray(d.astype(np.uint8))
    else:
        d = jnp.asarray(
            rng.integers(0, hi, size=(n_shard, code_dim)).astype(np.int8)
        )
    inv = jnp.asarray(rng.uniform(0.5, 1.0, size=n_shard).astype(np.float32))
    return q, d, inv


def _candidate_timer(
    kind: str, blocks: tuple[int, int], operands, *, n_levels: int, k: int,
    packed: bool, backend: str,
):
    """A zero-arg callable running one launch with the given blocks."""
    q, d, inv = operands
    bq, bn = blocks
    return lambda: sdc_search_backend(
        q, d, inv, n_levels=n_levels, k=k, backend=backend,
        block_q=bq, block_n=bn, packed=packed,
    )


def _plan_path(root: str, digest: str) -> str:
    return os.path.join(root, f"tuneplan-{digest}.json")


def _save_plan(path: str, payload: dict) -> None:
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _load_plan(path: str, kind: str, signature: dict) -> BlockPlan:
    """Load and validate a cached plan; any defect raises ValueError."""
    with open(path) as f:
        payload = json.load(f)
    if payload.get("schema") != _SCHEMA:
        raise ValueError("tune-cache schema mismatch")
    if payload.get("signature") != signature:
        raise ValueError("tune-cache signature drift (stale entry)")
    bq, bn = payload["block_q"], payload["block_n"]
    if not (isinstance(bq, int) and isinstance(bn, int) and bq >= 1 and bn >= 0):
        raise ValueError(f"tune-cache blocks corrupt: {(bq, bn)!r}")
    return BlockPlan(kind, bq, bn, "cache")


def tuned_block_plan(
    kind: str,
    *,
    code_dim: int,
    n_shard: int,
    packed: bool = False,
    k: int = 10,
    n_levels: int = 4,
    backend: str = "auto",
    cache_dir: str | None = None,
    sample_q: int = 8,
    reps: int = 2,
) -> TunedPlan:
    """Tune (or reload) the block plan for one kernel signature.

    Returns a ``TunedPlan``; ``plan`` is always safe to thread through
    the search paths (blocks never change scores). ``tuned`` is True
    only when this call actually ran the sweep; a cache hit reloads the
    winner the first toucher persisted, so all replicas launch with one
    shared plan.
    """
    resolved = resolve_backend(backend)
    base = default_plan(kind)
    if resolved == "xla":
        # No kernel tiles on the jnp path; nothing to sweep or cache.
        return TunedPlan(base._replace(source="inert-backend"), "", None, False)
    if kind in FIXED_GEOMETRY:
        return TunedPlan(base._replace(source="fixed-geometry"), "", None, False)

    signature = {
        "kind": kind, "code_dim": int(code_dim), "n_shard": int(n_shard),
        "packed": bool(packed), "k": int(k), "backend": resolved,
    }
    digest = plan_digest(
        kind, code_dim=code_dim, n_shard=n_shard, packed=packed, k=k,
        backend=resolved,
    )
    root = resolve_cache_dir(cache_dir)
    path = _plan_path(root, digest)
    if os.path.exists(path):
        try:
            return TunedPlan(_load_plan(path, kind, signature), digest, path, False)
        except Exception:
            pass  # fall through to re-tune

    operands = _sweep_operands(
        kind, code_dim=code_dim, n_shard=n_shard, packed=packed, k=k,
        sample_q=sample_q, n_levels=n_levels,
    )
    best_blocks, best_t, default_t = base.blocks(), None, None
    skipped = 0
    for blocks in candidate_grid(
        kind, code_dim=code_dim, n_shard=n_shard, packed=packed, k=k
    ):
        fn = _candidate_timer(
            kind, blocks, operands, n_levels=n_levels, k=k,
            packed=packed, backend=resolved,
        )
        if blocks == base.blocks():
            # The default plan is what serves untuned: if it does not
            # compile, serving cannot either, so that failure is fatal.
            t = default_t = _time_call(fn, reps=reps)
        else:
            try:
                t = _time_call(fn, reps=reps)
            except Exception:
                skipped += 1  # a candidate the compiler refused
                continue
        # Strict improvement required to displace the default plan.
        if best_t is None or t < best_t:
            best_blocks, best_t = blocks, t
    if default_t is not None and best_t is not None and default_t <= best_t:
        best_blocks = base.blocks()

    plan = BlockPlan(kind, int(best_blocks[0]), int(best_blocks[1]), "tuned")
    os.makedirs(root, exist_ok=True)
    _save_plan(path, {
        "schema": _SCHEMA,
        "signature": signature,
        "block_q": plan.block_q,
        "block_n": plan.block_n,
        "default_blocks": list(base.blocks()),
        "default_ms": None if default_t is None else default_t * 1e3,
        "tuned_ms": None if best_t is None else best_t * 1e3,
        "skipped": skipped,
    })
    return TunedPlan(plan, digest, path, True, skipped)
