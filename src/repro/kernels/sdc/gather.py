"""Gather-then-scan Pallas kernel for the IVF fine layer (§3.3.3).

The jnp IVF fine path gathers every probed inverted list into one
[Q, nprobe, L, D] HBM tensor before scoring — for Q=256, nprobe=32,
L=4096, D=128 that is 4 GiB of traffic for 32 MiB of useful codes. This
kernel instead streams the probed lists through VMEM one (query, probe)
step at a time with a running top-k accumulator, so nothing bigger than
one inverted list ever leaves HBM.

Mechanics: the probe table [Q, nprobe] is a scalar-prefetch argument
(``pltpu.PrefetchScalarGridSpec``) so the BlockSpec index maps can DMA
list ``probes[q, p]`` directly from the [nlist, L, ...] list arrays —
a data-dependent gather performed by the DMA engine, not by a giant
XLA gather. The output blocks of every step of one query tile
(``GATHER_BLOCK_Q`` rows) map to the same slot, giving the same
VMEM-resident running-top-k pattern as ``sdc_topk``.

Supports the nibble-packed int4 list layout (``packed=True``) with the
same bit-identical guarantee as the flat kernels: scores come from the
shared ``sdc_affine_epilogue`` over exact integer partial sums.

Beyond IVF, the same kernel scores HNSW neighbor blocks (index/hnsw_lite):
there "lists" are per-node fixed-width neighbor tables [N, M, ...] and
"probes" are the search beam. Graph search needs one extra ingredient the
IVF path does not: a per-(query, probe, slot) candidate mask
(``cand_mask``) so already-visited nodes can be excluded from the running
top-k without touching the streamed tables. The mask is a small [Q,
nprobe, L] input streamed alongside each block; masked slots score
SDC_NEG_INF exactly like list padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.binarize_lib import (
    SDC_NEG_INF,
    sdc_affine_epilogue,
    unpack_nibble_planes,
)
from repro.kernels.sdc.defaults import GATHER_BLOCK_Q, GATHER_CHUNK
from repro.kernels.sdc.sdc import (
    KEY_EMPTY,
    LANES,
    _check_code_dim,
    _split_queries,
    _tile_scores,
    _tile_scores_packed,
    any_above_kth,
    lane_pad,
    select_topk,
)


def gather_list_len(n: int) -> int:
    """The list length that holds ``n`` slots in the kernel's layout.

    Up to GATHER_CHUNK slots a list is scored in one piece and keeps its
    length; a longer list is padded to whole 128-lane tiles, so that it
    splits into lane-aligned chunks (``list_chunk``). Builders of list
    tables size them with this and pad the extra slots with id -1.
    """
    return n if n <= GATHER_CHUNK else -(-n // LANES) * LANES


def list_chunk(L: int) -> int:
    """Slots of one probed list scored per in-kernel step.

    A list is streamed into VMEM whole, but scored in chunks of at most
    GATHER_CHUNK lane-aligned slots so the temporaries (unpacked nibble
    planes, the score tile) stay bounded whatever the list length.
    """
    if L != gather_list_len(L):
        raise ValueError(
            f"list length {L} exceeds GATHER_CHUNK={GATHER_CHUNK} and is "
            f"not a multiple of {LANES}; size lists with gather_list_len"
        )
    if L <= GATHER_CHUNK:
        return L
    tiles = L // LANES
    return LANES * max(
        d for d in range(1, GATHER_CHUNK // LANES + 1) if tiles % d == 0
    )


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    pad = rows - x.shape[0]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))


@functools.partial(
    jax.jit, static_argnames=("n_levels", "k", "interpret", "packed")
)
def sdc_gather_topk(
    q_codes: jax.Array,
    lists_codes: jax.Array,
    lists_inv_norm: jax.Array,
    lists_ids: jax.Array,
    probes: jax.Array,
    *,
    n_levels: int,
    k: int,
    interpret: bool = False,
    packed: bool = False,
    cand_mask: jax.Array | None = None,
):
    """Block-gather search: stream probed blocks, running top-k per query.

    Args:
      q_codes: [Q, D] int8 query codes (unpacked, even with packed lists).
      lists_codes: [nlist, L, D] int8, or [nlist, L, D//2] uint8 if packed.
      lists_inv_norm: [nlist, L] f32 reciprocal doc norms (0 for padding).
      lists_ids: [nlist, L] int32 global doc ids (-1 for padding).
      probes: [Q, nprobe] int32 list ids to scan per query (clamped into
        range, so callers with invalid slots must also zero ``cand_mask``).
      cand_mask: optional [Q, nprobe, L] per-slot inclusion mask (> 0 keeps
        the slot). Used by HNSW's batched-frontier search to drop visited
        nodes without touching the streamed tables; IVF leaves it None.

    Returns:
      (scores [Q, k], doc ids [Q, k]); empty slots are (SDC_NEG_INF, -1).
      Ties keep the earlier candidate in (probe, slot) order, the order
      in which the jnp twin flattens them.

    Grid step (q, p) scores list ``probes[q, p]`` against the
    GATHER_BLOCK_Q-row query tile holding q (one MXU pass costs the same
    for 1 row or 8) and folds only row q into the tile's running top-k,
    which stays resident in VMEM while q walks through its tile.
    """
    Q, D = q_codes.shape
    nlist, L = lists_ids.shape
    nprobe = probes.shape[1]
    Dc = lists_codes.shape[-1]
    _check_code_dim(lists_codes, D, packed)
    tq = GATHER_BLOCK_Q
    Qp = -(-Q // tq) * tq
    kp = lane_pad(k)
    probes = _pad_rows(jnp.clip(probes.astype(jnp.int32), 0, nlist - 1), Qp)
    q_codes = _pad_rows(q_codes, Qp)
    has_mask = cand_mask is not None

    if packed:
        qe, qo = _split_queries(q_codes)
        q_args = (qe, qo)
        q_specs = [
            pl.BlockSpec((tq, D // 2), lambda q, p, pr: (q // tq, 0)),
            pl.BlockSpec((tq, D // 2), lambda q, p, pr: (q // tq, 0)),
        ]
    else:
        q_args = (q_codes,)
        q_specs = [pl.BlockSpec((tq, D), lambda q, p, pr: (q // tq, 0))]

    mask_args = ()
    mask_specs = []
    if has_mask:
        # [nprobe, Qp, L]: a (1, tq, L) block holds the mask rows of the
        # whole query tile at probe p, a legal (8, 128)-rule block.
        mask = _pad_rows(cand_mask.astype(jnp.float32), Qp)
        mask_args = (jnp.transpose(mask, (1, 0, 2)),)
        mask_specs = [pl.BlockSpec((1, tq, L), lambda q, p, pr: (p, q // tq, 0))]

    chunk = list_chunk(L)

    def kernel(probes_ref, *refs):
        del probes_ref  # consumed by the BlockSpec index maps
        q = pl.program_id(0)
        p = pl.program_id(1)
        if packed:
            qe_ref, qo_ref, codes_ref, inv_ref, ids_ref, *rest = refs
        else:
            q_ref, codes_ref, inv_ref, ids_ref, *rest = refs
        if has_mask:
            mask_ref, vals_ref, ids_out, keys_ref = rest
        else:
            vals_ref, ids_out, keys_ref = rest

        @pl.when((q % tq == 0) & (p == 0))
        def _init():
            vals_ref[...] = jnp.full(vals_ref.shape, -jnp.inf, jnp.float32)
            ids_out[...] = jnp.full(ids_out.shape, KEY_EMPTY, jnp.int32)
            keys_ref[...] = jnp.full(keys_ref.shape, KEY_EMPTY, jnp.int32)

        def fold(start, size):
            """Score list slots [start, start + size) and fold them in."""
            rows = pl.ds(start, size)
            inv = inv_ref[0, :, rows]
            if packed:
                scores = _tile_scores_packed(
                    qe_ref[...], qo_ref[...], codes_ref[0, rows, :], inv,
                    n_levels=n_levels, dim=D,
                )  # [tq, size]
            else:
                scores = _tile_scores(
                    q_ref[...], codes_ref[0, rows, :], inv,
                    n_levels=n_levels, dim=D,
                )
            ids = jnp.broadcast_to(ids_ref[0, :, rows], scores.shape)
            # List padding carries ids == -1 (and inv == 0, already NEG_INF).
            scores = jnp.where(ids >= 0, scores, SDC_NEG_INF)
            if has_mask:
                # Caller-supplied per-slot exclusion (HNSW visited bitmap).
                scores = jnp.where(mask_ref[0, :, rows] > 0, scores,
                                   SDC_NEG_INF)
            # Only row q of the query tile probes this list.
            row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            mine = row == q % tq
            scores = jnp.where(mine, scores, -jnp.inf)
            slot = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            keys = jnp.where(mine, p * L + start + slot, KEY_EMPTY)

            @pl.when(any_above_kth(vals_ref[...], scores, k))
            def _merge():
                v, key, ident = select_topk(
                    [(vals_ref[...], keys_ref[...], ids_out[...]),
                     (scores, keys, ids)], k,
                )
                vals_ref[...] = v
                keys_ref[...] = key
                ids_out[...] = ident

        if chunk == L:
            fold(0, L)
        else:
            def body(c, carry):
                fold(pl.multiple_of(c * chunk, chunk), chunk)
                return carry

            jax.lax.fori_loop(0, L // chunk, body, 0)

    meta = lambda x: x.reshape(nlist, 1, L)  # noqa: E731  (1, 1, L) blocks
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Qp, nprobe),
        in_specs=[
            *q_specs,
            pl.BlockSpec((1, L, Dc), lambda q, p, pr: (pr[q, p], 0, 0)),
            pl.BlockSpec((1, 1, L), lambda q, p, pr: (pr[q, p], 0, 0)),
            pl.BlockSpec((1, 1, L), lambda q, p, pr: (pr[q, p], 0, 0)),
            *mask_specs,
        ],
        out_specs=[
            pl.BlockSpec((tq, kp), lambda q, p, pr: (q // tq, 0)),
            pl.BlockSpec((tq, kp), lambda q, p, pr: (q // tq, 0)),
            pl.BlockSpec((tq, kp), lambda q, p, pr: (q // tq, 0)),
        ],
    )
    vals, ids, _ = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Qp, kp), jnp.float32),
            jax.ShapeDtypeStruct((Qp, kp), jnp.int32),
            jax.ShapeDtypeStruct((Qp, kp), jnp.int32),
        ],
        interpret=interpret,
    )(
        probes, *q_args, lists_codes, meta(lists_inv_norm), meta(lists_ids),
        *mask_args,
    )
    vals, ids = vals[:Q, :k], ids[:Q, :k]
    live = vals > SDC_NEG_INF / 2
    return jnp.where(live, vals, SDC_NEG_INF), jnp.where(live, ids, -1)


@functools.partial(jax.jit, static_argnames=("n_levels", "k", "packed"))
def sdc_gather_topk_xla(
    q_codes: jax.Array,
    lists_codes: jax.Array,
    lists_inv_norm: jax.Array,
    lists_ids: jax.Array,
    probes: jax.Array,
    *,
    n_levels: int,
    k: int,
    packed: bool = False,
    cand_mask: jax.Array | None = None,
):
    """jnp twin of ``sdc_gather_topk`` (the "xla" backend).

    Gathers every probed block into one [Q, nprobe, L, D] tensor and scores
    it through the shared epilogue — fine on CPU meshes, where the kernel's
    HBM-streaming argument does not apply. Same contract, same scores
    (bit-identical: identical integer partial sums and float op order).
    Shared by the IVF fine layer and HNSW's batched-frontier hop scoring.
    """
    D = q_codes.shape[-1]
    nlist = lists_ids.shape[0]
    probes = jnp.clip(probes.astype(jnp.int32), 0, nlist - 1)
    cand_codes = lists_codes[probes]  # [Q, nprobe, L, D(/2)]
    cand_inv = lists_inv_norm[probes]  # [Q, nprobe, L]
    cand_ids = lists_ids[probes]  # [Q, nprobe, L]

    cq = q_codes.astype(jnp.int32)
    if packed:
        lo, hi = unpack_nibble_planes(cand_codes)
        dot = jnp.einsum("qd,qpld->qpl", cq[:, 0::2], lo) + jnp.einsum(
            "qd,qpld->qpl", cq[:, 1::2], hi
        )
        sd = jnp.sum(lo, -1) + jnp.sum(hi, -1)
    else:
        cd = cand_codes.astype(jnp.int32)
        dot = jnp.einsum("qd,qpld->qpl", cq, cd)
        sd = jnp.sum(cd, -1)
    sq = jnp.sum(cq, -1)[:, None, None]
    scores = sdc_affine_epilogue(
        dot, sq + sd, dim=D, n_levels=n_levels, inv_norm=cand_inv
    )
    scores = jnp.where(cand_ids >= 0, scores, SDC_NEG_INF)
    if cand_mask is not None:
        scores = jnp.where(cand_mask > 0, scores, SDC_NEG_INF)

    Q = q_codes.shape[0]
    flat_scores = scores.reshape(Q, -1)
    flat_ids = cand_ids.reshape(Q, -1)
    if k > flat_scores.shape[1]:
        pad = jnp.full(
            (Q, k - flat_scores.shape[1]), SDC_NEG_INF, flat_scores.dtype
        )
        flat_scores = jnp.concatenate([flat_scores, pad], axis=1)
        flat_ids = jnp.concatenate(
            [flat_ids, jnp.full((Q, k - flat_ids.shape[1]), -1, jnp.int32)],
            axis=1,
        )
    vals, pos = jax.lax.top_k(flat_scores, k)
    ids = jnp.take_along_axis(flat_ids, pos, axis=-1)
    return vals, jnp.where(vals > SDC_NEG_INF / 2, ids, -1)
