"""Gather-then-rerank: score coarse-scan survivors on full-level codes.

The bi-granular search mode (PAPERS.md, Xiao et al. 2201.05409) splits a
query into a cheap coarse scan over level-prefix codes (hot tier) and a
sparse fine rerank of the top-k' survivors against the full-level codes
(cold tier). This module is the fine half: given survivor doc ids, score
exactly those rows of the full corpus through the shared
``sdc_affine_epilogue`` and return the true top-k.

Both implementations reuse the gather-then-scan substrate
(``kernels/sdc/gather``): the survivor rows are gathered into one
candidate list per query (k' rows, ascending id) and the identity probe
table points each query at its own list — the kernel that serves the
IVF fine layer scores it in one grid step per query, and the jnp twin
mirrors it for CPU meshes. Because every path folds the identical
integer partial sums through the one shared epilogue, a rerank is
**bit-identical to a
full-level flat scan restricted to the same candidate ids** (including
top-k tie-breaking: candidates are presented in ascending-id order, the
column order of a flat scan).

The cold tier may live on disk: when ``fine_codes`` is a numpy array
(including ``np.memmap``), ``sdc_rerank_backend`` gathers only the
survivor rows host-side — per query, k' rows leave the cold tier, never
the corpus — before scoring the gathered block on device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sdc.gather import (
    gather_list_len,
    sdc_gather_topk,
    sdc_gather_topk_xla,
)
from repro.kernels.sdc.ops import resolve_backend

_INT32_MAX = np.iinfo(np.int32).max


def fine_inv_norms(codes, n_levels: int, chunk: int = 65536):
    """Full-level reciprocal doc norms for a (possibly cold) fine tier.

    Numpy fine codes — including ``np.memmap`` — are streamed in chunks
    so the build never materialises the whole cold tier on device; each
    chunk goes through the same ``doc_inv_norms`` the hot paths use, so
    the values are bit-identical to a single-shot computation. Device
    arrays pass straight through.
    """
    from repro.kernels.sdc.ref import doc_inv_norms

    if not isinstance(codes, np.ndarray):
        return doc_inv_norms(codes, n_levels)
    out = np.empty(codes.shape[0], np.float32)
    for i in range(0, codes.shape[0], chunk):
        block = jnp.asarray(np.asarray(codes[i:i + chunk]))
        out[i:i + chunk] = np.asarray(doc_inv_norms(block, n_levels))
    return out


def _sort_candidates(cand_ids):
    """Ascending-id candidate order, invalid (< 0) slots pushed last.

    A flat scan scores documents in id order, so ``lax.top_k`` breaks
    score ties toward the smaller id; presenting rerank candidates in
    the same order is what makes the rerank bit-identical to a
    restricted flat scan even through ties. Candidate ids must be
    distinct (coarse top-k' guarantees it); invalid slots come back -1.
    Numpy ids (the host-gather path) stay on the host.
    """
    xp = np if isinstance(cand_ids, np.ndarray) else jnp
    ids = xp.asarray(cand_ids, xp.int32)
    key = xp.sort(xp.where(ids < 0, _INT32_MAX, ids), axis=-1)
    return xp.where(key == _INT32_MAX, -1, key)


def _gathered_lists(fine_codes, fine_inv_norm, cand):
    """Survivor rows as one candidate list per query.

    ``cand`` is the sorted [Q, k'] table from ``_sort_candidates``.
    Returns (codes [Q, L, D(/2)], inv [Q, L], ids [Q, L], probes [Q, 1])
    with L = ``gather_list_len(k')``: query q probes list q, whose slots
    are its survivors in ascending-id order; empty and padding slots
    carry inv 0 and id -1, so every scoring path excludes them. Device
    arrays are gathered on the device; a numpy (or memory-mapped) fine
    tier is gathered on the host, reading only the survivor rows.
    """
    xp = np if isinstance(fine_codes, np.ndarray) else jnp
    Q, kp = cand.shape
    pad = gather_list_len(kp) - kp
    if pad:
        cand = xp.concatenate([cand, xp.full((Q, pad), -1, xp.int32)], axis=1)
    safe = xp.clip(cand, 0, fine_codes.shape[0] - 1)
    inv = xp.where(cand >= 0, fine_inv_norm[safe], 0.0).astype(xp.float32)
    probes = xp.arange(Q, dtype=xp.int32)[:, None]
    return fine_codes[safe], inv, cand, probes


@functools.partial(
    jax.jit, static_argnames=("n_levels", "k", "interpret", "packed")
)
def sdc_rerank(
    q_codes: jax.Array,
    fine_codes: jax.Array,
    fine_inv_norm: jax.Array,
    cand_ids: jax.Array,
    *,
    n_levels: int,
    k: int,
    interpret: bool = False,
    packed: bool = False,
):
    """Rerank survivor ids against full-level codes (Pallas kernel path).

    Args:
      q_codes: [Q, D] int8 full-level query codes (unpacked).
      fine_codes: [N, D] int8 full-level corpus codes, or nibble-packed
        uint8 [N, D//2] when ``packed`` (n_levels <= 4).
      fine_inv_norm: [N] f32 reciprocal doc norms at ``n_levels``.
      cand_ids: [Q, k'] int32 survivor doc ids from the coarse scan
        (distinct per query; -1 marks an empty slot). k' may be < k.

    Returns:
      (scores [Q, k], ids [Q, k]); slots beyond the valid survivors are
      (SDC_NEG_INF, -1) — the k' < k degenerate case pads, never reads
      out of range.

    The survivor rows are gathered on device (Q * k' rows, never the
    corpus) into one list per query, which the gather kernel scores in
    a single grid step per query.
    """
    lists = _gathered_lists(fine_codes, fine_inv_norm, _sort_candidates(cand_ids))
    return sdc_gather_topk(
        q_codes, *lists, n_levels=n_levels, k=k, interpret=interpret,
        packed=packed,
    )


@functools.partial(jax.jit, static_argnames=("n_levels", "k", "packed"))
def sdc_rerank_xla(
    q_codes: jax.Array,
    fine_codes: jax.Array,
    fine_inv_norm: jax.Array,
    cand_ids: jax.Array,
    *,
    n_levels: int,
    k: int,
    packed: bool = False,
):
    """jnp twin of ``sdc_rerank`` (the "xla" backend fallback).

    Same contract, same scores: identical integer partial sums through
    the shared epilogue, identical ascending-id candidate order.
    """
    lists = _gathered_lists(fine_codes, fine_inv_norm, _sort_candidates(cand_ids))
    return sdc_gather_topk_xla(
        q_codes, *lists, n_levels=n_levels, k=k, packed=packed,
    )


def sdc_rerank_gathered(
    q_codes,
    fine_codes: np.ndarray,
    fine_inv_norm: np.ndarray,
    cand_ids,
    *,
    n_levels: int,
    k: int,
    packed: bool = False,
    backend: str = "auto",
):
    """Cold-tier rerank: host-gather the survivor rows, score on device.

    For a memory-mapped fine tier (``np.memmap``), this is the only
    path that touches k' rows per query instead of paging the whole
    corpus through ``jnp.asarray``. The gathered rows form the same one
    list per query as ``sdc_rerank`` / ``sdc_rerank_xla`` build on the
    device (``_gathered_lists``), so the float op order — and therefore
    every score and tie-break — matches theirs exactly.
    """
    cand = _sort_candidates(np.asarray(cand_ids, np.int32))
    lists = _gathered_lists(fine_codes, np.asarray(fine_inv_norm), cand)
    args = (jnp.asarray(q_codes), *(jnp.asarray(a) for a in lists))
    backend = resolve_backend(backend)
    if backend in ("pallas", "interpret"):
        return sdc_gather_topk(
            *args, n_levels=n_levels, k=k,
            interpret=(backend == "interpret"), packed=packed,
        )
    return sdc_gather_topk_xla(*args, n_levels=n_levels, k=k, packed=packed)


def sdc_rerank_backend(
    q_codes,
    fine_codes,
    fine_inv_norm,
    cand_ids,
    *,
    n_levels: int,
    k: int,
    backend: str = "auto",
    packed: bool = False,
):
    """Dispatch a fine rerank to the resolved backend.

    A numpy fine tier (the cold, possibly memory-mapped layout) always
    takes the host-gather path regardless of backend — moving the whole
    corpus on device would defeat the tiering. Device-resident fine
    codes are gathered on the device into the same one list per query
    and go through the Pallas gather kernel or its jnp twin.
    """
    backend = resolve_backend(backend)
    if isinstance(fine_codes, np.ndarray):
        return sdc_rerank_gathered(
            q_codes, fine_codes, fine_inv_norm, cand_ids,
            n_levels=n_levels, k=k, packed=packed, backend=backend,
        )
    if backend == "xla":
        return sdc_rerank_xla(
            q_codes, fine_codes, fine_inv_norm, cand_ids,
            n_levels=n_levels, k=k, packed=packed,
        )
    return sdc_rerank(
        q_codes, fine_codes, fine_inv_norm, cand_ids,
        n_levels=n_levels, k=k, interpret=(backend == "interpret"),
        packed=packed,
    )
