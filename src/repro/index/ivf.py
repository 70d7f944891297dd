"""IVF index over recurrent-binary codes with SDC fine scoring (§3.3.3).

Build: k-means over grid values -> inverted lists, padded to a fixed list
length so search is a static-shape gather + masked SDC scan (TPU/XLA
friendly: no ragged shapes at search time).

Both layers use SDC-compatible arithmetic: the coarse layer can score
centroids either in float or through their grid-quantised codes; the fine
layer scores through the shared affine epilogue — either the
gather-then-scan Pallas kernel (``backend="pallas"/"interpret"``), which
streams each probed list through VMEM with a running top-k, or a jnp
fallback (``backend="xla"``) for CPU meshes. Lists can be stored
nibble-packed (``packed=True``, n_levels <= 4) at 2 dims/byte, halving
scan bandwidth with bit-identical scores.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.binarize_lib import (
    codes_to_values,
    pack_codes_nibbles,
    values_to_codes,
)
from repro.index.kmeans import kmeans
from repro.kernels.sdc import ref as sdc_ref
from repro.kernels.sdc.gather import (
    gather_list_len,
    sdc_gather_topk,
    sdc_gather_topk_xla,
)
from repro.kernels.sdc.ops import resolve_backend


@dataclasses.dataclass
class IVFIndex:
    centroids: jax.Array  # [nlist, D] float grid-space centroids
    centroid_codes: jax.Array  # [nlist, D] int8 grid-quantised centroids
    lists_codes: jax.Array  # [nlist, max_len, D] int8 (uint8 [.., D//2] packed)
    lists_inv_norm: jax.Array  # [nlist, max_len] f32 (0 for padding)
    lists_ids: jax.Array  # [nlist, max_len] int32 (-1 for padding)
    n_levels: int
    packed: bool = False  # nibble-packed list storage (2 dims/byte)
    # [nlist] int32 stored entries per list, captured at build time — the
    # occupancy stats the budgeted probe allocator spends against. None on
    # indexes built before this field existed (allocation then degrades to
    # uniform; it is also recoverable as (lists_ids >= 0).sum(-1)).
    list_occupancy: object = None

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]

    @property
    def code_dim(self) -> int:
        D = self.lists_codes.shape[-1]
        return D * 2 if self.packed else D

    def nbytes(self) -> int:
        packed = (self.code_dim * self.n_levels + 7) // 8
        n_eff = int(jnp.sum(self.lists_ids >= 0))
        return n_eff * (packed + 4 + 4) + self.centroids.size * 4


def build_ivf(
    key: jax.Array,
    codes: jax.Array,
    *,
    n_levels: int,
    nlist: int,
    kmeans_iters: int = 20,
    max_len: int | None = None,
    headroom: float = 1.0,
    packed: bool = False,
) -> IVFIndex:
    """Cluster grid values, bucket codes into padded inverted lists.

    Args:
      max_len: fixed inverted-list capacity. Default (None) is the largest
        cluster size, which never drops an entry.
      headroom: multiplier applied to max_len (use > 1 with an explicit
        max_len — e.g. one sized for the *average* list — so balanced
        corpora keep every entry while bounding worst-case padding).
      packed: store lists nibble-packed (requires n_levels <= 4).

    Entries beyond a full list are dropped (they simply lose recall);
    any drop is counted and reported through ``warnings.warn`` with the
    dropped fraction, since a silent drop is invisible at search time.
    """
    if packed and n_levels > 4:
        raise ValueError(
            f"packed IVF lists need codes < 16 (n_levels <= 4), got {n_levels}"
        )

    values = codes_to_values(codes, n_levels)
    cents, assign = kmeans(key, values, k=nlist, iters=kmeans_iters)
    assign = np.asarray(assign)
    n = codes.shape[0]
    counts = np.bincount(assign, minlength=nlist)
    if max_len is None:
        max_len = int(counts.max())
    max_len = gather_list_len(max(1, int(np.ceil(max_len * headroom))))
    D = codes.shape[1]

    dropped = int(np.maximum(counts - max_len, 0).sum())
    if dropped:
        warnings.warn(
            f"build_ivf: {dropped}/{n} entries ({dropped / n:.2%}) dropped by "
            f"list overflow (max_len={max_len}, largest list={counts.max()}); "
            "raise max_len or headroom to keep them",
            stacklevel=2,
        )

    lc = np.zeros((nlist, max_len, D), np.int8)
    ln = np.zeros((nlist, max_len), np.float32)
    li = -np.ones((nlist, max_len), np.int32)
    inv = np.asarray(sdc_ref.doc_inv_norms(codes, n_levels))
    # Each list holds its members in ascending doc id (a stable sort by
    # list), truncated at max_len.
    order = np.argsort(assign, kind="stable")
    lists = assign[order]
    slot = np.arange(n) - np.searchsorted(lists, lists)
    keep = slot < max_len
    order, lists, slot = order[keep], lists[keep], slot[keep]
    lc[lists, slot] = np.asarray(codes)[order]
    ln[lists, slot] = inv[order]
    li[lists, slot] = order
    fill = np.minimum(counts, max_len)

    lists_codes = jnp.asarray(lc)
    if packed:
        lists_codes = pack_codes_nibbles(lists_codes)

    return IVFIndex(
        centroids=cents,
        centroid_codes=values_to_codes(jnp.clip(cents, -2.0, 2.0), n_levels),
        lists_codes=lists_codes,
        lists_inv_norm=jnp.asarray(ln),
        lists_ids=jnp.asarray(li),
        n_levels=n_levels,
        packed=packed,
        list_occupancy=np.asarray(fill, np.int32),
    )


@functools.partial(
    jax.jit,
    static_argnames=("nprobe", "k", "n_levels", "coarse_sdc", "backend", "packed"),
)
def ivf_search(
    index_centroids: jax.Array,
    index_centroid_codes: jax.Array,
    lists_codes: jax.Array,
    lists_inv_norm: jax.Array,
    lists_ids: jax.Array,
    q_codes: jax.Array,
    *,
    nprobe: int,
    k: int,
    n_levels: int,
    coarse_sdc: bool = False,
    backend: str = "auto",
    packed: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Search [Q] queries; returns (scores [Q, k], doc ids [Q, k])."""
    backend = resolve_backend(backend)
    vq = codes_to_values(q_codes, n_levels)  # [Q, D]

    # --- coarse layer ---
    if coarse_sdc:
        cv = codes_to_values(index_centroid_codes, n_levels)
    else:
        cv = index_centroids
    coarse = vq @ cv.T  # [Q, nlist]
    _, probes = jax.lax.top_k(coarse, nprobe)  # [Q, nprobe]

    # --- fine layer ---
    if backend in ("pallas", "interpret"):
        # Gather-then-scan kernel: probed lists stream HBM -> VMEM one at a
        # time with a running top-k; nothing [Q, nprobe, L, D]-sized exists.
        return sdc_gather_topk(
            q_codes,
            lists_codes,
            lists_inv_norm,
            lists_ids,
            probes,
            n_levels=n_levels,
            k=k,
            interpret=(backend == "interpret"),
            packed=packed,
        )

    # jnp fallback: gather candidate lists, score via the shared epilogue
    # (one implementation shared with HNSW's batched-frontier hop scoring).
    return sdc_gather_topk_xla(
        q_codes,
        lists_codes,
        lists_inv_norm,
        lists_ids,
        probes,
        n_levels=n_levels,
        k=k,
        packed=packed,
    )


def probe_rank_thresholds(
    occupancy,
    *,
    probe_budget: int,
    nlist: int,
    weighted: bool = True,
):
    """Per-centroid coarse-rank thresholds spending ``probe_budget``.

    The budget is a total of per-centroid rank slots: a query probes
    list ``c`` iff ``c`` sits within that query's top-``r[c]`` coarse
    scores, so ``sum(r) == probe_budget`` and the *average* number of
    lists scanned per query is ``probe_budget / nlist`` (the coarse
    ranking is a permutation). Flat nprobe is the uniform special case
    ``r[c] == nprobe`` for all c, i.e. ``probe_budget == nprobe *
    nlist``.

    Allocation: every centroid gets the uniform floor ``probe_budget //
    nlist`` (the flat part), and the surplus ``probe_budget % nlist``
    rank slots are apportioned by largest remainder — proportional to
    list occupancy when ``weighted`` (heavy lists stay probed deeper
    into the coarse ranking, where the corpus mass actually sits), over
    equal weights otherwise (+1 to the lowest-index centroids: the
    equal-budget flat comparator). A budget that is an exact multiple
    of ``nlist`` therefore has zero surplus and reproduces flat nprobe
    exactly, occupancy-weighted or not — that is the parity case the
    tests pin. Thresholds are clipped to ``nlist`` (a rank past the end
    of the ranking buys nothing), which can strand surplus only when a
    single list's share exceeds the whole rank range.
    """
    B = int(probe_budget)
    n = int(nlist)
    if B < 1:
        raise ValueError(f"probe_budget must be >= 1, got {probe_budget}")
    base, surplus = divmod(B, n)
    r = np.full(n, min(base, n), np.int64)
    if surplus and base < n:
        if weighted and occupancy is not None:
            occ = np.asarray(occupancy, np.float64).reshape(-1)
            if occ.shape[0] != n:
                raise ValueError(
                    f"occupancy has {occ.shape[0]} entries for nlist={n}"
                )
            if occ.sum() <= 0:
                occ = np.ones(n)
        else:
            occ = np.ones(n)
        quota = surplus * occ / occ.sum()
        fl = np.floor(quota).astype(np.int64)
        r += fl
        rem = surplus - int(fl.sum())
        if rem > 0:
            # Largest fractional part first; ties break to the lower index
            # so the allocation is deterministic across replicas.
            order = np.lexsort((np.arange(n), -(quota - fl)))
            r[order[:rem]] += 1
    return np.minimum(r, n).astype(np.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "nprobe_max", "k", "n_levels", "coarse_sdc", "backend", "packed",
    ),
)
def ivf_search_budget(
    index_centroids: jax.Array,
    index_centroid_codes: jax.Array,
    lists_codes: jax.Array,
    lists_inv_norm: jax.Array,
    lists_ids: jax.Array,
    rank_limits: jax.Array,
    q_codes: jax.Array,
    *,
    nprobe_max: int,
    k: int,
    n_levels: int,
    coarse_sdc: bool = False,
    backend: str = "auto",
    packed: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Budgeted probe search: per-centroid coarse-rank thresholds.

    ``rank_limits`` is the [nlist] int32 threshold vector from
    ``probe_rank_thresholds``; ``nprobe_max`` must equal its max (it
    sizes the static probe table). Probe column j of query q is live
    iff ``j < rank_limits[probes[q, j]]`` — dead columns ride the
    gather kernel's candidate mask, exactly like HNSW's visited-set
    exclusion, so their lists never enter the running top-k.
    """
    backend = resolve_backend(backend)
    vq = codes_to_values(q_codes, n_levels)
    if coarse_sdc:
        cv = codes_to_values(index_centroid_codes, n_levels)
    else:
        cv = index_centroids
    coarse = vq @ cv.T
    _, probes = jax.lax.top_k(coarse, nprobe_max)  # [Q, nprobe_max]
    limits = jnp.asarray(rank_limits, jnp.int32)
    live = jnp.arange(nprobe_max, dtype=jnp.int32)[None, :] < limits[probes]
    L = lists_ids.shape[1]
    mask = jnp.broadcast_to(
        live[:, :, None], probes.shape + (L,)
    ).astype(jnp.float32)
    if backend in ("pallas", "interpret"):
        return sdc_gather_topk(
            q_codes, lists_codes, lists_inv_norm, lists_ids, probes,
            n_levels=n_levels, k=k, interpret=(backend == "interpret"),
            packed=packed, cand_mask=mask,
        )
    return sdc_gather_topk_xla(
        q_codes, lists_codes, lists_inv_norm, lists_ids, probes,
        n_levels=n_levels, k=k, packed=packed, cand_mask=mask,
    )


def search_budget(
    index: IVFIndex,
    q_codes: jax.Array,
    *,
    probe_budget: int,
    k: int,
    weighted: bool = True,
    coarse_sdc: bool = False,
    backend: str = "auto",
):
    """Search under a global probe budget instead of a flat nprobe.

    Uniform thresholds (every exact-multiple budget, or uniform
    occupancy) delegate to the flat ``search`` path with ``nprobe =
    probe_budget // nlist`` — the same jit program, so ``probe_budget
    == nprobe * nlist`` is bit-identical to flat nprobe by
    construction. Non-uniform thresholds take the masked-probe path.
    """
    r = probe_rank_thresholds(
        index.list_occupancy if weighted else None,
        probe_budget=probe_budget, nlist=index.nlist, weighted=weighted,
    )
    lo, hi = int(r.min()), int(r.max())
    if lo == hi:
        return search(
            index, q_codes, nprobe=max(1, lo), k=k, coarse_sdc=coarse_sdc,
            backend=backend,
        )
    return ivf_search_budget(
        index.centroids,
        index.centroid_codes,
        index.lists_codes,
        index.lists_inv_norm,
        index.lists_ids,
        jnp.asarray(r),
        q_codes,
        nprobe_max=hi,
        k=k,
        n_levels=index.n_levels,
        coarse_sdc=coarse_sdc,
        backend=resolve_backend(backend),
        packed=index.packed,
    )


def ivf_search_from_snapshot(
    codes,
    n_levels: int = None,
    *,
    k: int,
    nlist: int,
    nprobe: int,
    seed: int = 0,
    kmeans_iters: int = 20,
    max_len: int | None = None,
    headroom: float = 1.0,
    packed: bool = False,
    backend: str = "auto",
    coarse_sdc: bool = False,
    effort=None,
    rerank: dict | None = None,
    probe_budget: int | None = None,
    block_plan=None,
):
    """Rebuild-from-snapshot entry point (live index lifecycle).

    Re-clusters a corpus snapshot's codes into a fresh IVF index and
    returns a serving ``SearchFn`` closure for the rolling swap
    (``launch/lifecycle.RollingSwapController``). Deterministic: the
    k-means key derives from ``seed``, so the same snapshot + params
    rebuild bit-identically.

    First argument: a ``CorpusSnapshot`` (preferred — carries its own
    ``n_levels``) or raw unpacked codes plus an explicit ``n_levels``
    (legacy form); one convention across every
    ``*_search_from_snapshot`` entry point.

    ``effort`` is an optional shared knob (any object with an int
    ``level`` attribute, 0 = full effort — ``launch.proxy.EffortKnob``)
    read per call: level L serves with ``max(1, nprobe >> L)`` probes,
    so the router can trade recall for latency under pressure without
    touching the closure. Level 0 is bit-identical to ``effort=None``.
    Each distinct effective nprobe is its own jit program (nprobe is
    static): warm the degraded levels or the first degraded batch pays
    a compile.

    ``rerank={"coarse_levels": c, "k_coarse": k'}`` switches to
    bi-granular mode: the IVF is clustered and scanned over the
    level-prefix codes at ``c`` levels (hot tier), its top-k' survivors
    are reranked against the full-level codes (cold tier — a numpy /
    memmapped snapshot stays host-side and only survivor rows are
    read). The closure carries ``fn.reranked = True``. Under pressure,
    ``effort`` first halves ``k_coarse`` (floored at k — the cheap
    axis) and only residual levels halve nprobe.

    ``probe_budget`` switches probe selection from flat nprobe to the
    occupancy-weighted budget allocator (``search_budget``): the
    build-time list-occupancy stats decide how deep into each query's
    coarse ranking every centroid stays probed, spending ``probe_budget
    / nlist`` lists per query on average. ``effort`` then halves the
    *budget* per level (``max(1, probe_budget >> level)``) instead of
    per-level nprobe; ``probe_budget == nprobe * nlist`` serves
    bit-identically to the flat path it replaces. ``nprobe`` is ignored
    while a budget is set.

    ``block_plan`` (``kernels.sdc.defaults.BlockPlan``, e.g. from
    ``launch/autotune``) is accepted for signature parity with the other
    entry points: the IVF scan and the bi-granular rerank both run on
    the gather substrate, whose geometry is fixed by the list layout.
    """
    from repro.index._snapshot import (
        resolve_rerank_args,
        resolve_snapshot_args,
        split_effort,
    )
    from repro.kernels.sdc.rerank import fine_inv_norms, sdc_rerank_backend

    codes, n_levels = resolve_snapshot_args(codes, n_levels)
    rr = resolve_rerank_args(rerank, n_levels)
    if rr is None:
        index = build_ivf(
            jax.random.PRNGKey(seed), jnp.asarray(codes), n_levels=n_levels,
            nlist=nlist, kmeans_iters=kmeans_iters, max_len=max_len,
            headroom=headroom, packed=packed,
        )
        if probe_budget is not None:
            if effort is None:
                return lambda q: search_budget(
                    index, q, probe_budget=probe_budget, k=k,
                    coarse_sdc=coarse_sdc, backend=backend,
                )

            def fn(q):
                level = max(0, int(effort.level))
                return search_budget(
                    index, q, probe_budget=max(1, probe_budget >> level),
                    k=k, coarse_sdc=coarse_sdc, backend=backend,
                )

            fn.effort = effort
            return fn
        if effort is None:
            return lambda q: search(
                index, q, nprobe=nprobe, k=k, coarse_sdc=coarse_sdc,
                backend=backend,
            )

        def fn(q):
            level = max(0, int(effort.level))
            return search(
                index, q, nprobe=max(1, nprobe >> level), k=k,
                coarse_sdc=coarse_sdc, backend=backend,
            )

        fn.effort = effort
        return fn

    from repro.core.binarize_lib import coarse_codes

    c_levels, k_coarse = rr
    host = isinstance(codes, np.ndarray)
    c_src = jnp.asarray(np.asarray(codes)) if host else codes
    index = build_ivf(
        jax.random.PRNGKey(seed), coarse_codes(c_src, n_levels, c_levels),
        n_levels=c_levels, nlist=nlist, kmeans_iters=kmeans_iters,
        max_len=max_len, headroom=headroom,
        packed=packed and c_levels <= 4,
    )
    fine_inv = fine_inv_norms(codes, n_levels)
    k_coarse = min(k_coarse, c_src.shape[0])

    def fn(q):
        kc_eff, residual = (
            split_effort(effort.level, k=k, k_coarse=k_coarse)
            if effort is not None else (k_coarse, 0)
        )
        q = jnp.asarray(q)
        qc = coarse_codes(q, n_levels, c_levels)
        if probe_budget is not None:
            _, cand = search_budget(
                index, qc, probe_budget=max(1, probe_budget >> residual),
                k=kc_eff, coarse_sdc=coarse_sdc, backend=backend,
            )
        else:
            _, cand = search(
                index, qc, nprobe=max(1, nprobe >> residual), k=kc_eff,
                coarse_sdc=coarse_sdc, backend=backend,
            )
        return sdc_rerank_backend(
            q, codes, fine_inv, cand, n_levels=n_levels, k=k,
            backend=backend,
        )

    if effort is not None:
        fn.effort = effort
    fn.reranked = True
    return fn


def search(
    index: IVFIndex,
    q_codes: jax.Array,
    *,
    nprobe: int,
    k: int,
    coarse_sdc=False,
    backend: str = "auto",
):
    return ivf_search(
        index.centroids,
        index.centroid_codes,
        index.lists_codes,
        index.lists_inv_norm,
        index.lists_ids,
        q_codes,
        nprobe=nprobe,
        k=k,
        n_levels=index.n_levels,
        coarse_sdc=coarse_sdc,
        backend=resolve_backend(backend),
        packed=index.packed,
    )
