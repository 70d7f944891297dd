"""Distributed BEBR search engine (paper Figure 5: proxy -> leaf -> merge).

The corpus codes are sharded across every device of the mesh ("leaves");
queries are replicated ("proxy dispatch"); each leaf runs a local SDC scan
+ top-k; a single all_gather of the per-leaf top-k (k << shard size) plus a
local merge yields the global top-k ("selection merge").

Communication = Q * k * 8 bytes * n_leaves — independent of corpus size,
which is what lets one engine span tens of billions of documents. Built on
shard_map so the same code drives the 256-chip pod and the 512-chip
multi-pod mesh in launch/dryrun.py.

Leaves score through ``kernels.sdc.ops`` — the same substrate as FlatSDC
and IVF. ``backend="pallas"`` runs the fused scan+top-k Pallas kernel on
each leaf (no [Q, shard_N] score matrix in HBM); ``backend="xla"`` is the
jnp fallback for CPU meshes (identical scores, shared epilogue);
``backend="interpret"`` exercises the kernel under the Pallas interpreter
in tests. ``packed=True`` shards a nibble-packed uint8 [D//2, N] corpus
(documents along lanes, ``ops.pack_scan_corpus``) on its document axis,
the last, halving per-leaf scan bandwidth.

Three first-class leaf index types share the proxy/merge skeleton:
  * flat  — exhaustive leaf scan (``make_distributed_search``);
  * flat + failover mask (``make_failover_search``);
  * hnsw  — batched-frontier graph search per leaf
    (``make_hnsw_search``), one NSW graph per shard built host-side by
    ``hnsw_lite.build_hnsw_sharded``; each leaf walks its local graph
    with the same gather-kernel scoring, so sublinear leaf scans ride
    the identical selection-merge.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.index.hnsw_lite import ShardedHNSW, hnsw_frontier_search
from repro.kernels.sdc.defaults import BLOCK_N, plan_for
from repro.kernels.sdc.ops import (
    pack_scan_corpus,
    resolve_backend,
    sdc_search,
    sdc_search_xla,
)


def _leaf_scan(
    q_codes: jax.Array,
    shard_codes: jax.Array,
    shard_inv: jax.Array,
    shard_base: jax.Array,
    *,
    n_levels: int,
    k: int,
    backend: str = "auto",
    packed: bool = False,
    block_q: int | None = None,
    block_n: int = BLOCK_N,
) -> Tuple[jax.Array, jax.Array]:
    """Local exhaustive SDC scan + top-k on one leaf.

    Dispatches to the fused Pallas kernel (no [Q, shard_N] score matrix
    materialised) or the jnp fallback; both treat shard_inv == 0 entries
    as excluded (drained docs) and surface empty slots as -inf. The
    kernel's query tile follows the request's row count
    (``defaults.scan_block_q``) unless ``block_q`` is given.
    """
    backend = resolve_backend(backend)
    if backend in ("pallas", "interpret"):
        vals, idx = sdc_search(
            q_codes,
            shard_codes,
            shard_inv,
            n_levels=n_levels,
            k=k,
            block_q=block_q,
            block_n=block_n,
            interpret=(backend == "interpret"),
            fused=True,
            packed=packed,
        )
    else:
        vals, idx = sdc_search_xla(
            q_codes, shard_codes, shard_inv, n_levels=n_levels, k=k,
            packed=packed,
        )
    # Downstream merges expect strict -inf for empty slots, and global ids;
    # the -1 empty-slot sentinel must not be shifted into a neighbour
    # shard's id range.
    vals = jnp.where(idx >= 0, vals, -jnp.inf)
    return vals, jnp.where(idx >= 0, idx + shard_base, -1)


def _codes_spec(axes: Tuple[str, ...], packed: bool) -> P:
    """The corpus shards on its document axis: the first of an unpacked
    [N, D] corpus, the last of a packed [D//2, N] one."""
    return P(None, axes) if packed else P(axes)


def _make_search(
    mesh: Mesh,
    *,
    n_levels: int,
    k: int,
    shard_axes: Tuple[str, ...],
    backend: str,
    packed: bool,
    block_q: int | None,
    block_n: int,
    failover: bool,
):
    """Common builder for the plain and failover engines."""
    axes = shard_axes
    backend = resolve_backend(backend)

    def search(q_codes, d_codes, d_inv, *rest):
        shard_n = d_inv.shape[0]  # per-leaf documents under shard_map
        # Leaf rank: linearised index over the sharded axes.
        rank = jnp.zeros((), jnp.int32)
        for ax in axes:
            rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
        base = rank * shard_n
        vals, ids = _leaf_scan(
            q_codes, d_codes, d_inv, shard_base=base,
            n_levels=n_levels, k=k, backend=backend, packed=packed,
            block_q=block_q, block_n=block_n,
        )
        if failover:
            (leaf_alive,) = rest
            # A dead/drained leaf contributes -inf scores; the merge
            # proceeds from the survivors (paper §3.3.3 proxy timeout).
            vals = jnp.where(leaf_alive[rank], vals, -jnp.inf)

        # selection merge: gather every leaf's top-k, re-rank locally.
        all_vals, all_ids = vals, ids
        for ax in axes:
            all_vals = jax.lax.all_gather(all_vals, ax, axis=1, tiled=True)
            all_ids = jax.lax.all_gather(all_ids, ax, axis=1, tiled=True)
        merged_vals, pos = jax.lax.top_k(all_vals, k)
        merged_ids = jnp.take_along_axis(all_ids, pos, axis=-1)
        return merged_vals, merged_ids

    in_specs = (P(), _codes_spec(axes, packed), P(axes))
    in_specs += (P(),) if failover else ()
    fn = shard_map(
        search, mesh=mesh, in_specs=in_specs, out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def make_distributed_search(
    mesh: Mesh,
    *,
    n_levels: int,
    k: int,
    shard_axes: Tuple[str, ...] = ("data", "model"),
    backend: str = "auto",
    packed: bool = False,
    block_q: int | None = None,
    block_n: int = BLOCK_N,
    block_plan=None,
):
    """Build a pjit-able global search fn over a mesh.

    Inputs (global shapes):
      q_codes [Q, D] int8 (replicated), d_codes [N, D] int8 sharded on
      axis 0 across shard_axes — or with ``packed=True`` nibble-packed
      uint8 [D//2, N] (``ops.pack_scan_corpus``) sharded on axis 1 —
      and d_inv [N] f32 sharded as the documents are
      (``engine_input_shardings``).
    Output: (scores [Q, k], global ids [Q, k]) replicated.

    ``block_q`` None sizes every leaf's query tile from the request
    (``defaults.scan_block_q``). ``block_plan`` (kind "scan", from
    ``launch/autotune``) overrides ``block_q``/``block_n`` for every
    leaf's fused scan — tuned once for the per-leaf shard size, applied
    mesh-wide.
    """
    plan = plan_for(block_plan, "scan")
    if plan is not None:
        block_q, block_n = plan.block_q, plan.block_n
    return _make_search(
        mesh, n_levels=n_levels, k=k, shard_axes=shard_axes,
        backend=backend, packed=packed, block_q=block_q, block_n=block_n,
        failover=False,
    )


def engine_input_shardings(mesh: Mesh, shard_axes=("data", "model"),
                           packed: bool = False):
    """NamedShardings matching make_distributed_search's expectations."""
    return (
        NamedSharding(mesh, P()),
        NamedSharding(mesh, _codes_spec(shard_axes, packed)),
        NamedSharding(mesh, P(shard_axes)),
    )


def make_failover_search(
    mesh: Mesh,
    *,
    n_levels: int,
    k: int,
    shard_axes: Tuple[str, ...] = ("data", "model"),
    backend: str = "auto",
    packed: bool = False,
    block_q: int | None = None,
    block_n: int = BLOCK_N,
    block_plan=None,
):
    """Distributed search with leaf failover (straggler/failure tolerance).

    Production leaves time out (paper §3.3.3's proxy drops late leaves and
    merges what arrived). SPMD can't drop a device mid-step, so the same
    contract is expressed as a ``leaf_alive`` mask: a dead/drained leaf
    contributes -inf scores and the merge proceeds from the survivors.
    The orchestrator flips the mask between steps (no recompile — the mask
    is a runtime input), giving graceful degradation instead of a stalled
    query: recall drops by ~|dead|/|leaves| of the corpus, latency does not.
    """
    plan = plan_for(block_plan, "scan")
    if plan is not None:
        block_q, block_n = plan.block_q, plan.block_n
    return _make_search(
        mesh, n_levels=n_levels, k=k, shard_axes=shard_axes,
        backend=backend, packed=packed, block_q=block_q, block_n=block_n,
        failover=True,
    )


def make_hnsw_search(
    mesh: Mesh,
    *,
    n_levels: int,
    k: int,
    ef: int = 64,
    beam: int = 8,
    max_hops: int = 64,
    shard_axes: Tuple[str, ...] = ("data", "model"),
    backend: str = "auto",
    packed: bool = False,
):
    """Distributed HNSW engine: batched-frontier graph search per leaf.

    Same proxy/leaf/merge skeleton as ``make_distributed_search``, but each
    leaf walks its local NSW graph (built by ``build_hnsw_sharded``)
    instead of scanning its whole shard — the leaf cost is
    O(hops * beam * M) candidates instead of O(shard_n), scored through
    the identical gather-kernel substrate.

    Inputs (global shapes, see ``hnsw_engine_shardings``):
      q_codes [Q, D] replicated; codes [N, D(/2)], inv_norm [N],
      nbr_codes [N, M, D(/2)], nbr_inv [N, M], nbr_ids [N, M] (leaf-local
      ids) and entries [n_leaves, E] (leaf-local ids) sharded on axis 0.
    Output: (scores [Q, k], global ids [Q, k]) replicated.
    """
    axes = shard_axes
    backend = resolve_backend(backend)
    ef_eff = max(ef, k)
    beam_eff = max(1, min(beam, ef_eff))

    def search(q_codes, codes, inv, nbr_codes, nbr_inv, nbr_ids, entries):
        shard_n = codes.shape[0]
        # One graph per leaf: a build_hnsw_sharded(n_leaves=...) that
        # doesn't match the mesh would alias leaf-local neighbor ids
        # across sub-graphs and silently corrupt global ids — fail loudly
        # at trace time instead.
        if entries.shape[0] != 1:
            raise ValueError(
                f"build_hnsw_sharded n_leaves must equal the mesh's "
                f"sharded device count (each leaf got {entries.shape[0]} "
                "entry rows, expected 1)"
            )
        rank = jnp.zeros((), jnp.int32)
        for ax in axes:
            rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
        base = rank * shard_n
        vals, ids, _ = hnsw_frontier_search(
            q_codes, codes, inv, nbr_codes, nbr_inv, nbr_ids,
            entries.reshape(-1),
            n_levels=n_levels, k=k, ef=ef_eff, beam=beam_eff,
            max_hops=max_hops, backend=backend, packed=packed,
        )
        vals = jnp.where(ids >= 0, vals, -jnp.inf)
        all_vals = vals
        all_ids = jnp.where(ids >= 0, ids + base, -1)
        for ax in axes:
            all_vals = jax.lax.all_gather(all_vals, ax, axis=1, tiled=True)
            all_ids = jax.lax.all_gather(all_ids, ax, axis=1, tiled=True)
        merged_vals, pos = jax.lax.top_k(all_vals, k)
        merged_ids = jnp.take_along_axis(all_ids, pos, axis=-1)
        return merged_vals, merged_ids

    in_specs = (P(),) + (P(axes),) * 6
    fn = shard_map(
        search, mesh=mesh, in_specs=in_specs, out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def hnsw_engine_shardings(mesh: Mesh, shard_axes=("data", "model")):
    """NamedShardings for ``make_hnsw_search``'s seven inputs (queries
    replicated, every table sharded on axis 0)."""
    rep = NamedSharding(mesh, P())
    sh = NamedSharding(mesh, P(shard_axes))
    return (rep,) + (sh,) * 6


def hnsw_engine_inputs(index: ShardedHNSW):
    """The sharded input arrays of ``make_hnsw_search``, in order."""
    return (
        index.codes, index.inv_norm, index.nbr_codes, index.nbr_inv,
        index.nbr_ids, index.entries,
    )


# ---------------------------------------------------------------------------
# Rebuild-from-snapshot entry points (live index lifecycle).
#
# The engine's normal API hands back a bare shard_map program and leaves
# device placement to the caller; the rolling swap wants the whole thing —
# "here is a corpus snapshot, give me a serving SearchFn over this
# replica's submesh" — so these wrap program construction + device_put
# into one closure a drained replica can hot-swap in
# (launch/lifecycle.RollingSwapController).
# ---------------------------------------------------------------------------


def flat_engine_inputs_from_snapshot(
    codes: jax.Array,
    n_levels: int,
    *,
    packed: bool = False,
    coarse_levels: int = None,
) -> Tuple[jax.Array, jax.Array]:
    """Host-side shared flat-engine inputs from a snapshot's unpacked
    codes: (codes [nibble-packed [D//2, N] when ``packed``], inverse doc
    norms).
    Replica-independent, so a rolling swap computes them once per
    snapshot and reuses them for every replica's device placement
    (``launch/lifecycle.EngineBuilder``). With ``coarse_levels`` the
    inputs are the hot coarse tier of a bi-granular engine: level-prefix
    codes and their inverse norms at ``coarse_levels`` levels."""
    from repro.core.binarize_lib import coarse_codes
    from repro.kernels.sdc import ref as _ref

    codes = jnp.asarray(codes)
    if coarse_levels is not None:
        codes = coarse_codes(codes, n_levels, coarse_levels)
        n_levels = coarse_levels
    inv = _ref.doc_inv_norms(codes, n_levels)
    if packed:
        codes = pack_scan_corpus(codes)
    return codes, inv


def engine_search_from_snapshot(
    mesh: Mesh,
    codes,
    n_levels: int = None,
    *,
    k: int,
    shard_axes: Tuple[str, ...] = ("data", "model"),
    backend: str = "auto",
    packed: bool = False,
    block_q: int | None = None,
    block_n: int = BLOCK_N,
    prepared: Tuple[jax.Array, jax.Array] = None,
    rerank: dict | None = None,
    effort=None,
    block_plan=None,
):
    """Fresh flat engine over ``mesh`` from a snapshot's unpacked codes.

    Shards the codes (nibble-packing them first when ``packed``) and
    inverse norms over the mesh's leaves and returns
    ``q_codes -> (scores, ids)`` — queries are placed replicated inside
    the closure, so it is a drop-in serving ``SearchFn``. Pass
    ``prepared`` (from ``flat_engine_inputs_from_snapshot``) to skip the
    per-replica host recompute.

    ``codes`` may be a ``CorpusSnapshot`` (preferred — carries its own
    ``n_levels``) or raw unpacked codes plus an explicit ``n_levels``
    (legacy form); one convention across every
    ``*_search_from_snapshot`` entry point.

    ``rerank={"coarse_levels": c, "k_coarse": k'}`` switches to
    bi-granular mode: the engine leaves scan the level-prefix codes at
    ``c`` levels and the cross-leaf merge produces the global coarse
    top-k' survivors, which are then reranked *post-merge* against the
    full-level codes (one fine gather over the whole corpus's cold tier
    — a numpy / memmapped snapshot stays host-side, only survivor rows
    are read). ``prepared`` must then come from
    ``flat_engine_inputs_from_snapshot(..., coarse_levels=c)``. The
    closure carries ``fn.reranked = True``. ``effort`` (int ``level``
    attribute, 0 = full) narrows the rerank by slicing the merged
    top-k' down to its top-``k_coarse >> level`` prefix (floored at k)
    — an exact prefix of a sorted top-k, so no re-jit per level.

    ``block_plan`` — a single ``BlockPlan`` or a ``{kind: plan}``
    mapping (``launch/autotune``) — sets the per-leaf scan tiles
    (kind "scan" overrides ``block_q``/``block_n``); in bi-granular
    mode they are the coarse scan's tiles. Plans never change scores,
    only launch shapes.
    """
    from repro.index._snapshot import (
        resolve_rerank_args,
        resolve_snapshot_args,
        split_effort,
    )

    codes, n_levels = resolve_snapshot_args(codes, n_levels)
    rr = resolve_rerank_args(rerank, n_levels)
    scan_plan = plan_for(block_plan, "scan")
    if scan_plan is not None:
        block_q, block_n = scan_plan.block_q, scan_plan.block_n
    if rr is None:
        if prepared is None:
            prepared = flat_engine_inputs_from_snapshot(codes, n_levels,
                                                        packed=packed)
        search = make_distributed_search(
            mesh, n_levels=n_levels, k=k, shard_axes=shard_axes,
            backend=backend, packed=packed, block_q=block_q, block_n=block_n,
        )
        qspec, *in_specs = engine_input_shardings(mesh, shard_axes, packed)
        ins = [jax.device_put(a, s) for a, s in zip(prepared, in_specs)]

        def snapshot_search(q_codes):
            return search(jax.device_put(q_codes, qspec), *ins)

        return snapshot_search

    import numpy as np

    from repro.core.binarize_lib import coarse_codes
    from repro.kernels.sdc.rerank import fine_inv_norms, sdc_rerank_backend

    c_levels, k_coarse = rr
    k_coarse = min(k_coarse, codes.shape[0])
    packed_c = packed and c_levels <= 4
    if prepared is None:
        prepared = flat_engine_inputs_from_snapshot(
            codes, n_levels, packed=packed_c, coarse_levels=c_levels,
        )
    search = make_distributed_search(
        mesh, n_levels=c_levels, k=k_coarse, shard_axes=shard_axes,
        backend=backend, packed=packed_c, block_q=block_q, block_n=block_n,
    )
    qspec, *in_specs = engine_input_shardings(mesh, shard_axes, packed_c)
    ins = [jax.device_put(a, s) for a, s in zip(prepared, in_specs)]
    fine_codes = codes if isinstance(codes, np.ndarray) else jnp.asarray(codes)
    fine_inv = fine_inv_norms(fine_codes, n_levels)

    def snapshot_search(q_codes):
        q = jnp.asarray(q_codes)
        qc = coarse_codes(q, n_levels, c_levels)
        _, cand = search(jax.device_put(qc, qspec), *ins)
        if effort is not None:
            kc_eff, _ = split_effort(effort.level, k=k, k_coarse=k_coarse)
            cand = cand[:, :kc_eff]
        return sdc_rerank_backend(
            q, fine_codes, fine_inv, cand, n_levels=n_levels, k=k,
            backend=backend,
        )

    if effort is not None:
        snapshot_search.effort = effort
    snapshot_search.reranked = True
    return snapshot_search


def sharded_graph_from_snapshot(
    codes,
    n_levels: int,
    *,
    n_leaves: int,
    M: int = 16,
    ef_construction: int = 64,
    seed: int = 0,
    packed: bool = False,
) -> ShardedHNSW:
    """Host-side per-leaf NSW graphs from a snapshot's unpacked codes:
    the single copy of the inv-norms + ``build_hnsw_sharded`` recipe,
    shared by ``hnsw_engine_search_from_snapshot`` and the lifecycle
    ``EngineBuilder``'s per-digest cache (any drift between two copies
    would silently break the swap's bit-identity guarantee)."""
    import numpy as np

    from repro.index.hnsw_lite import build_hnsw_sharded
    from repro.kernels.sdc import ref as _ref

    codes = np.asarray(codes)
    inv = np.asarray(_ref.doc_inv_norms(jnp.asarray(codes), n_levels))
    return build_hnsw_sharded(
        codes, inv, n_leaves=n_leaves, n_levels=n_levels, M=M,
        ef_construction=ef_construction, seed=seed, packed=packed,
    )


def hnsw_engine_search_from_snapshot(
    mesh: Mesh,
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
    shard_axes: Tuple[str, ...] = ("data", "model"),
    backend: str = "auto",
    packed: bool = False,
    sharded: ShardedHNSW = None,
    block_plan=None,
):
    """Fresh HNSW engine over ``mesh`` from a snapshot's unpacked codes.

    Rebuilds one NSW graph per leaf (``sharded_graph_from_snapshot``,
    deterministic for the same snapshot + seed) unless a prebuilt
    ``sharded`` graph is passed — replicas share the leaf layout, so a
    rolling swap builds the graph once and reuses it for every replica's
    device placement (see ``launch/lifecycle.EngineBuilder``).

    ``codes`` may be a ``CorpusSnapshot`` (preferred — carries its own
    ``n_levels``) or raw unpacked codes plus an explicit ``n_levels``
    (legacy form); one convention across every
    ``*_search_from_snapshot`` entry point.

    ``block_plan`` is accepted for signature parity with the other
    entry points but inert here: the graph walk's gather geometry is
    fixed by the beam/neighborhood layout (kind "gather"), so there is
    no tunable tile. A mapping containing only inert kinds is fine; a
    plan is never an error.
    """
    plan_for(block_plan, "gather")  # validate mapping keys early
    from repro.index._snapshot import resolve_snapshot_args

    codes, n_levels = resolve_snapshot_args(codes, n_levels)
    n_leaves = 1
    for ax in shard_axes:
        n_leaves *= mesh.shape[ax]
    if sharded is None:
        sharded = sharded_graph_from_snapshot(
            codes, n_levels, n_leaves=n_leaves, M=M,
            ef_construction=ef_construction, seed=seed, packed=packed,
        )
    if sharded.entries.shape[0] != n_leaves:
        raise ValueError(
            f"prebuilt sharded graph has {sharded.entries.shape[0]} leaves, "
            f"mesh wants {n_leaves}"
        )
    search = make_hnsw_search(
        mesh, n_levels=n_levels, k=k, ef=ef, beam=beam, max_hops=max_hops,
        shard_axes=shard_axes, backend=backend, packed=packed,
    )
    qspec, *in_specs = hnsw_engine_shardings(mesh, shard_axes)
    ins = [jax.device_put(a, s)
           for a, s in zip(hnsw_engine_inputs(sharded), in_specs)]

    def snapshot_search(q_codes):
        return search(jax.device_put(q_codes, qspec), *ins)

    return snapshot_search
