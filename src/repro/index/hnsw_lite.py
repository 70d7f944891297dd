"""HNSW-lite: a navigable-small-world graph with SDC distances (Figure 6).

The paper plugs SDC into off-the-shelf HNSW; here we implement a compact
single-layer NSW (the HNSW fine layer) in numpy for index build, with the
query-time distance evaluated through the same affine-identity integer
math as the SDC kernel. Build is host-side (graph construction is
pointer-chasing and belongs on CPU even in production). Two searchers:

  * ``search_hnsw`` — the numpy greedy best-first beam search (reference
    semantics, per-query, per-hop host scoring).
  * ``search_hnsw_batched`` — the production path: a **batched-frontier
    beam search** over fixed-shape device arrays. Each hop expands the
    whole beam's fixed-width neighbor table ([Q, beam, M] ids) into one
    candidate block, dedupes it against a per-query visited bitmap, and
    scores the block in a single ``kernels/sdc`` gather-then-scan call
    (``backend="pallas"/"interpret"``) or its jnp twin (``"xla"``) — so
    graph search rides the same scoring substrate as the flat and IVF
    indexes, including the int4 nibble-packed code layout.

The batched searcher runs as a ``lax.while_loop`` over a fixed hop
budget: pointer-chasing becomes a fixed-shape device pipeline (gather ids
-> dedupe -> score block -> merge running top-ef -> pick next beam), so
it jits, vmaps over the query batch for free, and drops into the
distributed engine's shard_map leaves unchanged (index/engine.py).
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.binarize_lib import (
    SDC_NEG_INF,
    pack_codes_nibbles,
    sdc_affine_epilogue,
)
from repro.kernels.sdc.gather import sdc_gather_topk, sdc_gather_topk_xla
from repro.kernels.sdc.ops import resolve_backend, sdc_search_xla


def _unpack_rows_np(packed: np.ndarray) -> np.ndarray:
    """Nibble-packed uint8 [..., D//2] -> int8 codes [..., D] (numpy).

    Host-side inverse of ``binarize_lib.pack_codes_nibbles`` (byte j =
    dim 2j | dim 2j+1 << 4) for the numpy build/search paths.
    """
    p = packed.astype(np.uint8)
    out = np.empty((*p.shape[:-1], p.shape[-1] * 2), np.int8)
    out[..., 0::2] = (p & 0x0F).astype(np.int8)
    out[..., 1::2] = (p >> 4).astype(np.int8)
    return out


@dataclasses.dataclass
class HNSWLite:
    codes: np.ndarray  # [N, D] int8, or nibble-packed uint8 [N, D//2]
    inv_norm: np.ndarray  # [N] f32
    neighbors: np.ndarray  # [N, M] int32 (-1 padded)
    entry: int
    n_levels: int
    packed: bool = False  # int4 nibble-packed code storage

    @property
    def code_dim(self) -> int:
        m = self.codes.shape[1]
        return 2 * m if self.packed else m

    def unpacked_codes(self) -> np.ndarray:
        return _unpack_rows_np(self.codes) if self.packed else self.codes

    def nbytes(self) -> int:
        """Index bytes as stored: codes + 4B norm per doc + the graph.

        The code term is layout-aware: nibble-packed storage holds 4 bits
        per dim regardless of n_levels, while unpacked storage is counted
        at the ideal n_levels-bits-per-dim serialisation (matching
        FlatSDC.nbytes). The previous formula applied the bit-packing math
        to ``codes.shape[1]`` blindly, undercounting packed indexes by 2x
        (packed rows are already D//2 wide) and ignoring the norms.
        """
        if self.packed:
            code_bytes = self.code_dim // 2  # 2 dims/byte in memory
        else:
            code_bytes = (self.code_dim * self.n_levels + 7) // 8
        return (
            self.codes.shape[0] * (code_bytes + 4) + self.neighbors.size * 4
        )


def _sdc_scores_np(q_code: np.ndarray, codes: np.ndarray, inv_norm: np.ndarray, n_levels: int):
    D = codes.shape[-1]
    dot = codes.astype(np.int32) @ q_code.astype(np.int32)
    sq = int(q_code.astype(np.int32).sum())
    sd = codes.astype(np.int32).sum(-1)
    # shared epilogue is pure arithmetic — stays in numpy on this hot path
    return sdc_affine_epilogue(dot, sq + sd, dim=D, n_levels=n_levels,
                               inv_norm=inv_norm)


def build_hnsw(
    codes: np.ndarray,
    inv_norm: np.ndarray,
    *,
    n_levels: int,
    M: int = 16,
    ef_construction: int = 64,
    seed: int = 0,
    packed: bool = False,
) -> HNSWLite:
    """Incremental NSW build: each point is connected to the M best results
    of a beam search among previously inserted points.

    With ``packed=True`` (n_levels <= 4) the built index stores its codes
    nibble-packed — the graph itself is identical; only storage changes.
    """
    if packed and n_levels > 4:
        raise ValueError(
            f"packed HNSW codes need n_levels <= 4, got {n_levels}"
        )
    rng = np.random.default_rng(seed)
    n = codes.shape[0]
    neighbors = -np.ones((n, M), np.int32)
    order = rng.permutation(n)
    inserted: List[int] = []

    def knn_beam(q_idx: int, ef: int) -> List[int]:
        if not inserted:
            return []
        sub = np.asarray(inserted)
        scores = _sdc_scores_np(codes[q_idx], codes[sub], inv_norm[sub], n_levels)
        top = np.argsort(-scores)[:ef]
        return [int(sub[t]) for t in top]

    for step, idx in enumerate(order):
        if step <= M:
            cands = list(inserted)
        else:
            cands = knn_beam(idx, ef_construction)
        best = cands[:M]
        neighbors[idx, : len(best)] = best
        # Backlinks. The first M//2 slots are immutable once set — they were
        # created while the graph was sparse and act as the long-range
        # "navigable" edges (pruning them to a pure kNN graph traps greedy
        # search inside clusters); only the tail slots are re-ranked.
        for b in best:
            row = neighbors[b]
            free = np.where(row < 0)[0]
            if free.size:
                row[free[0]] = idx
            else:
                head, tail = row[: M // 2], row[M // 2:]
                cand = np.append(tail, idx)
                sc = _sdc_scores_np(codes[b], codes[cand], inv_norm[cand], n_levels)
                keep = np.argsort(-sc)[: len(tail)]
                neighbors[b] = np.concatenate([head, cand[keep]])
        inserted.append(int(idx))

    entry = int(order[0])
    store = codes
    if packed:
        store = np.asarray(pack_codes_nibbles(jnp.asarray(codes)))
    return HNSWLite(
        codes=store, inv_norm=inv_norm, neighbors=neighbors, entry=entry,
        n_levels=n_levels, packed=packed,
    )


def _entry_points(n: int, entry: int, n_entries: int, seed: int) -> np.ndarray:
    """Shared entry-point selection: graph entry + seeded random restarts.

    Both searchers draw from here so the batched-frontier search explores
    from exactly the entry set the numpy reference uses (parity tests
    compare their top-k directly).
    """
    rng = np.random.default_rng(seed)
    return np.unique(
        np.concatenate([[entry], rng.integers(0, n, max(n_entries - 1, 0))])
    ).astype(np.int64)


def search_hnsw(
    index: HNSWLite, q_code: np.ndarray, *, k: int, ef: int = 64,
    n_entries: int = 8, seed: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """Greedy best-first beam search from multiple entry points (numpy
    reference; per-query, host-side scoring).

    Returns (scores [k], ids [k])."""
    codes = index.unpacked_codes()
    n = codes.shape[0]
    entries = _entry_points(n, index.entry, n_entries, seed)
    e_scores = _sdc_scores_np(
        q_code, codes[entries], index.inv_norm[entries], index.n_levels
    )
    visited = set(int(e) for e in entries)
    # max-heap by score via negation
    frontier = [(-float(s), int(e)) for s, e in zip(e_scores, entries)]
    heapq.heapify(frontier)
    results = [(float(s), int(e)) for s, e in zip(e_scores, entries)]

    while frontier:
        neg, node = heapq.heappop(frontier)
        worst = min(results)[0] if len(results) >= ef else -np.inf
        if -neg < worst and len(results) >= ef:
            break
        neigh = index.neighbors[node]
        neigh = neigh[neigh >= 0]
        fresh = [int(x) for x in neigh if int(x) not in visited]
        if not fresh:
            continue
        visited.update(fresh)
        sub = np.asarray(fresh)
        scores = _sdc_scores_np(q_code, codes[sub], index.inv_norm[sub], index.n_levels)
        for s, i in zip(scores, sub):
            if len(results) < ef or s > min(results)[0]:
                heapq.heappush(frontier, (-float(s), int(i)))
                results.append((float(s), int(i)))
                if len(results) > ef:
                    results.remove(min(results))

    results.sort(reverse=True)
    top = results[:k]
    return (
        np.asarray([s for s, _ in top], np.float32),
        np.asarray([i for _, i in top], np.int32),
    )


# ---------------------------------------------------------------------------
# Batched-frontier search on the fused SDC substrate.
#
# The graph is re-laid-out as fixed-width *neighbor blocks*: node i's block
# holds the codes/norms/ids of its M neighbors ([N, M, D], [N, M], [N, M]).
# A search hop then is a block-gather — exactly the access pattern of the
# scalar-prefetched gather-then-scan kernel the IVF fine layer uses, with
# the beam as the probe table. The M-fold code duplication trades HBM bytes
# for DMA-streamable locality (one contiguous block per expanded node
# instead of M scattered row reads); packed int4 storage claws half back.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BatchedHNSW:
    """Device-resident, fixed-shape HNSW tables for the batched searcher."""

    codes: jax.Array  # [N, D] int8 (uint8 [N, D//2] packed) — entry scoring
    inv_norm: jax.Array  # [N] f32
    nbr_codes: jax.Array  # [N, M, D] int8 (uint8 [N, M, D//2] packed)
    nbr_inv: jax.Array  # [N, M] f32 (0 for -1 neighbor slots)
    nbr_ids: jax.Array  # [N, M] int32 (-1 padded)
    entry: int
    n_levels: int
    packed: bool = False

    @property
    def n(self) -> int:
        return self.nbr_ids.shape[0]

    @property
    def m(self) -> int:
        return self.nbr_ids.shape[1]

    def nbytes(self) -> int:
        """Device bytes of the search tables (includes the M-fold
        neighbor-block code duplication — this is the serving footprint,
        distinct from HNSWLite.nbytes which counts the stored index)."""
        return sum(
            int(a.size) * a.dtype.itemsize
            for a in (self.codes, self.inv_norm, self.nbr_codes,
                      self.nbr_inv, self.nbr_ids)
        )


def prepare_batched(
    index: HNSWLite, *, packed: Optional[bool] = None
) -> BatchedHNSW:
    """Expand an HNSWLite graph into gather-kernel-ready neighbor blocks.

    ``packed`` overrides the index's storage layout for the device tables
    (None: inherit). Packing requires n_levels <= 4.
    """
    packed = index.packed if packed is None else packed
    if packed and index.n_levels > 4:
        raise ValueError(
            f"packed HNSW tables need n_levels <= 4, got {index.n_levels}"
        )
    codes = index.unpacked_codes()
    nbr = index.neighbors.astype(np.int32)
    safe = np.where(nbr >= 0, nbr, 0)
    nbr_codes = codes[safe]  # [N, M, D]
    nbr_inv = np.where(
        nbr >= 0, index.inv_norm[safe], 0.0
    ).astype(np.float32)
    flat = codes
    if packed:
        nbr_codes = np.asarray(pack_codes_nibbles(jnp.asarray(nbr_codes)))
        flat = np.asarray(pack_codes_nibbles(jnp.asarray(flat)))
    return BatchedHNSW(
        codes=jnp.asarray(flat),
        inv_norm=jnp.asarray(index.inv_norm, jnp.float32),
        nbr_codes=jnp.asarray(nbr_codes),
        nbr_inv=jnp.asarray(nbr_inv),
        nbr_ids=jnp.asarray(nbr),
        entry=index.entry,
        n_levels=index.n_levels,
        packed=packed,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_levels", "k", "ef", "beam", "max_hops", "backend", "packed",
    ),
)
def hnsw_frontier_search(
    q_codes: jax.Array,
    codes: jax.Array,
    inv_norm: jax.Array,
    nbr_codes: jax.Array,
    nbr_inv: jax.Array,
    nbr_ids: jax.Array,
    entries: jax.Array,
    *,
    n_levels: int,
    k: int,
    ef: int,
    beam: int,
    max_hops: int,
    backend: str,
    packed: bool,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """Batched-frontier beam search over fixed-shape HNSW tables.

    State per query: a running top-``ef`` result list, a visited bitmap
    (scored-once dedupe) and an expanded bitmap (each node's neighbor
    block is streamed at most once). Each ``lax.while_loop`` hop:

      1. beam <- the ``beam`` best unexpanded entries of the result list;
      2. candidate block <- the beam's neighbor tables ([Q, beam, M] ids);
      3. dedupe within the block and against the visited bitmap;
      4. score the whole block in one gather-kernel (or jnp twin) call,
         folding fresh candidates into a per-hop top-ef;
      5. merge into the running results.

    Terminates when every surviving result is expanded (the batched
    analogue of an exhausted best-first frontier) or at ``max_hops``.

    Args:
      q_codes: [Q, D] int8 query codes (unpacked, even when ``packed``).
      codes / inv_norm: flat corpus tables (entry-point scoring only).
      nbr_codes / nbr_inv / nbr_ids: neighbor-block tables ([N, M, ...]).
      entries: [E] int32 entry node ids, -1 padded.

    Returns:
      (scores [Q, k], ids [Q, k], stats) with empty slots (SDC_NEG_INF,
      -1); stats carries per-query ``hops`` and ``scored`` counters.
    """
    Q, D = q_codes.shape
    N, M = nbr_ids.shape
    E = entries.shape[0]
    rows = jnp.arange(Q)[:, None]

    # --- entry scoring (tiny: E docs per query, plain jnp) ---
    e_valid = entries >= 0
    e_ids = jnp.where(e_valid, entries, 0)
    e_inv = jnp.where(e_valid, inv_norm[e_ids], 0.0)
    e_codes = codes[e_ids]
    res_vals, e_pos = sdc_search_xla(
        q_codes, e_codes.T if packed else e_codes, e_inv, n_levels=n_levels,
        k=ef, packed=packed,
    )
    res_ids = jnp.where(
        e_pos >= 0, entries[jnp.clip(e_pos, 0, E - 1)], -1
    ).astype(jnp.int32)

    visited = jnp.zeros((Q, N), jnp.uint8)
    visited = visited.at[:, e_ids].max(
        jnp.broadcast_to(e_valid.astype(jnp.uint8)[None, :], (Q, E))
    )
    expanded = jnp.zeros((Q, N), jnp.uint8)

    def cond(state):
        hop, active, *_ = state
        return (hop < max_hops) & jnp.any(active)

    def body(state):
        hop, active, res_vals, res_ids, visited, expanded, hops, scored = state

        # 1. Beam: best unexpanded results.
        rid_ok = res_ids >= 0
        rid = jnp.where(rid_ok, res_ids, 0)
        already = jnp.take_along_axis(expanded, rid, axis=1) > 0
        frontier = jnp.where(rid_ok & ~already, res_vals, SDC_NEG_INF)
        bvals, bpos = jax.lax.top_k(frontier, beam)
        beam_ids = jnp.where(
            bvals > SDC_NEG_INF / 2,
            jnp.take_along_axis(res_ids, bpos, axis=1),
            -1,
        )
        active = active & jnp.any(beam_ids >= 0, axis=-1)
        beam_ok = (beam_ids >= 0) & active[:, None]
        bclamp = jnp.where(beam_ok, beam_ids, 0)
        expanded = expanded.at[rows, bclamp].max(beam_ok.astype(jnp.uint8))

        # 2. Candidate block: the beam's neighbor ids (codes stay in HBM —
        # only the gather kernel / its jnp twin touches them).
        cand = jnp.where(beam_ok[..., None], nbr_ids[bclamp], -1)  # [Q,B,M]
        flat = cand.reshape(Q, beam * M)
        valid = flat >= 0
        fclamp = jnp.where(valid, flat, 0)

        # 3. Dedupe: first occurrence within the block, then the visited
        # bitmap (sort-based so shapes stay static).
        order = jnp.argsort(flat, axis=-1)
        sorted_ids = jnp.take_along_axis(flat, order, axis=-1)
        first = jnp.concatenate(
            [
                jnp.ones((Q, 1), bool),
                sorted_ids[:, 1:] != sorted_ids[:, :-1],
            ],
            axis=-1,
        )
        keep = jnp.take_along_axis(first, jnp.argsort(order, axis=-1), axis=-1)
        seen = jnp.take_along_axis(visited, fclamp, axis=1) > 0
        fresh = valid & keep & ~seen
        visited = visited.at[rows, fclamp].max(fresh.astype(jnp.uint8))

        # 4. Score the block through the shared SDC substrate.
        mask = fresh.reshape(Q, beam, M).astype(jnp.float32)
        if backend in ("pallas", "interpret"):
            hop_vals, hop_ids = sdc_gather_topk(
                q_codes, nbr_codes, nbr_inv, nbr_ids, bclamp,
                n_levels=n_levels, k=ef,
                interpret=(backend == "interpret"), packed=packed,
                cand_mask=mask,
            )
        else:
            hop_vals, hop_ids = sdc_gather_topk_xla(
                q_codes, nbr_codes, nbr_inv, nbr_ids, bclamp,
                n_levels=n_levels, k=ef, packed=packed, cand_mask=mask,
            )

        # 5. Merge into the running top-ef (fresh-only scoring guarantees
        # no id appears twice across hops).
        cat_v = jnp.concatenate([res_vals, hop_vals], axis=-1)
        cat_i = jnp.concatenate([res_ids, hop_ids], axis=-1)
        res_vals, pos = jax.lax.top_k(cat_v, ef)
        res_ids = jnp.take_along_axis(cat_i, pos, axis=-1)

        hops = hops + active.astype(jnp.int32)
        scored = scored + jnp.sum(fresh, axis=-1).astype(jnp.int32)
        return (
            hop + 1, active, res_vals, res_ids, visited, expanded, hops,
            scored,
        )

    state = (
        jnp.zeros((), jnp.int32),
        jnp.ones((Q,), bool),
        res_vals,
        res_ids,
        visited,
        expanded,
        jnp.zeros((Q,), jnp.int32),
        jnp.zeros((Q,), jnp.int32),
    )
    _, _, res_vals, res_ids, _, _, hops, scored = jax.lax.while_loop(
        cond, body, state
    )
    stats = {"hops": hops, "scored": scored}
    return res_vals[:, :k], res_ids[:, :k], stats


def search_hnsw_batched(
    index: BatchedHNSW,
    q_codes: jax.Array,
    *,
    k: int,
    ef: int = 64,
    beam: int = 8,
    max_hops: int = 64,
    n_entries: int = 8,
    seed: int = 0,
    backend: str = "auto",
    with_stats: bool = False,
):
    """Multi-query HNSW search on the fused SDC substrate.

    Entry points match ``search_hnsw`` for the same (n_entries, seed), so
    the two searchers are directly comparable. ``backend`` follows the
    other indexes: pallas / interpret -> the scalar-prefetched
    gather-then-scan kernel, xla -> jnp twin, auto -> pallas on TPU.

    Returns (scores [Q, k], ids [Q, k]) — plus a stats dict of per-query
    ``hops`` and ``scored`` (candidates folded into the running top-k)
    when ``with_stats`` is set.
    """
    backend = resolve_backend(backend)
    ef = max(ef, k)
    beam = max(1, min(beam, ef))
    ents = _entry_points(index.n, index.entry, n_entries, seed)
    padded = np.full((max(n_entries, 1),), -1, np.int32)
    padded[: len(ents)] = ents[: len(padded)]
    vals, ids, stats = hnsw_frontier_search(
        q_codes,
        index.codes,
        index.inv_norm,
        index.nbr_codes,
        index.nbr_inv,
        index.nbr_ids,
        jnp.asarray(padded),
        n_levels=index.n_levels,
        k=k,
        ef=ef,
        beam=beam,
        max_hops=max_hops,
        backend=backend,
        packed=index.packed,
    )
    if with_stats:
        return vals, ids, stats
    return vals, ids


def hnsw_search_from_snapshot(
    codes,
    n_levels: int = None,
    *,
    k: int,
    M: int = 16,
    ef_construction: int = 64,
    ef: int = 64,
    beam: int = 8,
    max_hops: int = 64,
    seed: int = 0,
    packed: bool = False,
    backend: str = "auto",
    effort=None,
    rerank: dict | None = None,
    block_plan=None,
):
    """Rebuild-from-snapshot entry point (live index lifecycle).

    Rebuilds the NSW graph from a corpus snapshot's unpacked codes
    (host-side, O(N^2) — size swap corpora accordingly) and returns a
    serving ``SearchFn`` closure over the batched-frontier search, for
    the rolling swap (``launch/lifecycle.RollingSwapController``).
    Deterministic: the insertion order derives from ``seed``, so the
    same snapshot + params rebuild bit-identically.

    ``effort`` is an optional shared knob (any object with an int
    ``level`` attribute, 0 = full effort — ``launch.proxy.EffortKnob``)
    read per call: level L serves with ``max(k, ef >> L)`` /
    ``max(1, beam >> L)``, the graph search's cost knobs, so the router
    can degrade recall gracefully under pressure. Level 0 is
    bit-identical to ``effort=None``; each level is its own jit program
    shape (ef/beam are static), so warm the degraded levels too.

    First argument: a ``CorpusSnapshot`` (preferred — carries its own
    ``n_levels``) or raw unpacked codes plus an explicit ``n_levels``
    (legacy form); one convention across every
    ``*_search_from_snapshot`` entry point.

    ``rerank={"coarse_levels": c, "k_coarse": k'}`` switches to
    bi-granular mode: the NSW graph is built and walked over the
    level-prefix codes at ``c`` levels (hot tier, cheaper neighbor
    tables), its top-k' survivors are reranked against the full-level
    codes (cold tier — a numpy / memmapped snapshot stays host-side,
    only survivor rows are read). The closure carries
    ``fn.reranked = True``. Under pressure, ``effort`` first halves
    ``k_coarse`` (floored at k) and only residual levels halve ef/beam.

    ``block_plan`` — a single ``BlockPlan`` or a ``{kind: plan}``
    mapping (``launch/autotune``) — is accepted for signature parity
    and inert: the graph walk and the bi-granular rerank both run on
    the gather substrate, whose geometry is fixed by the
    beam/neighborhood and survivor layouts.
    """
    from repro.index._snapshot import (
        resolve_rerank_args,
        resolve_snapshot_args,
        split_effort,
    )
    from repro.kernels.sdc import ref as _ref  # lazy: ref is build-time only
    from repro.kernels.sdc.defaults import plan_for
    from repro.kernels.sdc.rerank import fine_inv_norms, sdc_rerank_backend

    codes, n_levels = resolve_snapshot_args(codes, n_levels)
    rr = resolve_rerank_args(rerank, n_levels)
    plan_for(block_plan, "rerank")  # validate mapping keys early
    if rr is None:
        codes = np.asarray(codes)
        inv = np.asarray(_ref.doc_inv_norms(jnp.asarray(codes), n_levels))
        graph = build_hnsw(
            codes, inv, n_levels=n_levels, M=M,
            ef_construction=ef_construction, seed=seed, packed=packed,
        )
        tables = prepare_batched(graph)
        if effort is None:
            return lambda q: search_hnsw_batched(
                tables, q, k=k, ef=ef, beam=beam, max_hops=max_hops,
                backend=backend,
            )

        def fn(q):
            level = max(0, int(effort.level))
            return search_hnsw_batched(
                tables, q, k=k, ef=max(k, ef >> level),
                beam=max(1, beam >> level), max_hops=max_hops,
                backend=backend,
            )

        fn.effort = effort
        return fn

    from repro.core.binarize_lib import coarse_codes

    c_levels, k_coarse = rr
    fine_codes = codes  # numpy (possibly memmapped) stays host-side
    codes_c = np.asarray(
        coarse_codes(jnp.asarray(np.asarray(codes)), n_levels, c_levels)
    )
    inv_c = np.asarray(_ref.doc_inv_norms(jnp.asarray(codes_c), c_levels))
    graph = build_hnsw(
        codes_c, inv_c, n_levels=c_levels, M=M,
        ef_construction=ef_construction, seed=seed,
        packed=packed and c_levels <= 4,
    )
    tables = prepare_batched(graph)
    fine_inv = fine_inv_norms(fine_codes, n_levels)
    k_coarse = min(k_coarse, codes_c.shape[0])

    def fn(q):
        kc_eff, residual = (
            split_effort(effort.level, k=k, k_coarse=k_coarse)
            if effort is not None else (k_coarse, 0)
        )
        q = jnp.asarray(q)
        qc = coarse_codes(q, n_levels, c_levels)
        _, cand = search_hnsw_batched(
            tables, qc, k=kc_eff, ef=max(kc_eff, ef >> residual),
            beam=max(1, beam >> residual), max_hops=max_hops,
            backend=backend,
        )
        return sdc_rerank_backend(
            q, fine_codes, fine_inv, cand, n_levels=n_levels, k=k,
            backend=backend,
        )

    if effort is not None:
        fn.effort = effort
    fn.reranked = True
    return fn


# ---------------------------------------------------------------------------
# Sharded build for the distributed engine (index/engine.py): one NSW graph
# per leaf over that leaf's rows; searched leaf-locally under shard_map and
# selection-merged exactly like the flat/IVF engine paths.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardedHNSW:
    """Per-leaf HNSW tables stacked into global arrays (axis 0 shards)."""

    codes: jax.Array  # [N, D(/2)]
    inv_norm: jax.Array  # [N]
    nbr_codes: jax.Array  # [N, M, D(/2)]
    nbr_inv: jax.Array  # [N, M]
    nbr_ids: jax.Array  # [N, M] int32, leaf-local ids
    entries: jax.Array  # [n_leaves, E] int32, leaf-local ids (-1 padded)
    n_levels: int
    packed: bool = False


def build_hnsw_sharded(
    codes: np.ndarray,
    inv_norm: np.ndarray,
    *,
    n_leaves: int,
    n_levels: int,
    M: int = 16,
    ef_construction: int = 64,
    n_entries: int = 8,
    seed: int = 0,
    packed: bool = False,
) -> ShardedHNSW:
    """Build one NSW graph per leaf shard (host-side, embarrassingly
    parallel across leaves) and stack the batched tables for shard_map.

    Neighbor ids stay leaf-local; the engine adds each leaf's shard base
    to returned ids, mirroring ``engine._leaf_scan``.
    """
    n = codes.shape[0]
    if n % n_leaves != 0:
        raise ValueError(f"corpus size {n} not divisible by {n_leaves} leaves")
    shard_n = n // n_leaves
    parts = []
    entries = np.full((n_leaves, n_entries), -1, np.int32)
    for leaf in range(n_leaves):
        lo = leaf * shard_n
        idx = build_hnsw(
            codes[lo : lo + shard_n],
            inv_norm[lo : lo + shard_n],
            n_levels=n_levels,
            M=M,
            ef_construction=ef_construction,
            seed=seed + leaf,
        )
        parts.append(prepare_batched(idx, packed=packed))
        ents = _entry_points(shard_n, idx.entry, n_entries, seed + leaf)
        entries[leaf, : min(len(ents), n_entries)] = ents[:n_entries]
    return ShardedHNSW(
        codes=jnp.concatenate([p.codes for p in parts], axis=0),
        inv_norm=jnp.concatenate([p.inv_norm for p in parts], axis=0),
        nbr_codes=jnp.concatenate([p.nbr_codes for p in parts], axis=0),
        nbr_inv=jnp.concatenate([p.nbr_inv for p in parts], axis=0),
        nbr_ids=jnp.concatenate([p.nbr_ids for p in parts], axis=0),
        entries=jnp.asarray(entries),
        n_levels=n_levels,
        packed=packed,
    )
