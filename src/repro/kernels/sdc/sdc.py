"""Pallas TPU kernel for Symmetric Distance Calculation (SDC).

TPU-native adaptation of the paper's SIMD-LUT scan (DESIGN.md §2): the
recurrent-binary grid value is affine in the packed integer code
(v = a*c + beta), so the whole scan becomes an int8 x int8 -> int32 MXU
matmul over the code matrices plus rank-1 affine corrections and a
reciprocal-norm epilogue on the VPU. The epilogue itself lives in
``repro.core.binarize_lib.sdc_affine_epilogue`` — the single copy shared
with every jnp fallback, so all scoring paths are bit-identical.

Layout/tiling:
  * codes stream HBM -> VMEM at 8 bits/dim (4 meaningful), documents tiled
    along N, queries tiled along Q; the code dim D stays whole (D <= 2048
    in all BEBR deployments => a (512, D) int8 tile is <= 1 MiB of VMEM).
  * MXU tiles want multiples of (128, 128); the query tile follows the
    request (``defaults.scan_block_q``), the document tile is
    ``defaults.BLOCK_N``.
  * reciprocal norms travel as a [1, N] row so their (1, TN) blocks obey
    the (8, 128) block rule and broadcast over the (TQ, TN) score tile.
  * int32 accumulation is exact — unlike the paper's saturating int8/16
    adds, the TPU path introduces zero quantisation error.
  * documents with a zero reciprocal norm are "excluded" (padding, drained
    shards): every kernel masks them to SDC_NEG_INF before any top-k.

int4 packed code streaming (``packed=True``):
  * for n_levels <= 4 each code is 4 bits, so document codes are stored
    nibble-packed (2 dims/byte; byte j = dim 2j | dim 2j+1 << 4, see
    ``binarize_lib.pack_codes_nibbles``), halving HBM traffic per scanned
    document — the scan is memory-bound, so this is ~2x effective speedup.
  * the flat scan's packed corpus lies documents along lanes: uint8
    [D//2, N], D//2 = 64 sublanes by N lanes at the web
    widths, so every vreg of a (D//2, TN) tile is full. A [N, D//2] corpus
    would fill half of each 128-lane row; XLA then relayouts the whole
    corpus to 128 lanes in front of every call, and each step would
    transpose its document planes for an NT matmul.
  * in-kernel unpack is shift+mask on the VPU in int32; the two nibble
    planes stack along sublanes into one [D, TN] int8 plane (even dims,
    then odd), and the queries (tiny) are permuted to match outside the
    kernel (``_interleave_queries``), so the scan is one NN int8 MXU
    matmul with no transpose:
        c_q . c_d = [q_even | q_odd] . [lo(d_packed); hi(d_packed)].
    The document code sums are a sublane sum, which lands as the (1, TN)
    row the epilogue broadcasts.
  * integer partial sums are identical to the int8 path, so packed scores
    are bit-identical to unpacked scores.
  * lists that the gather kernel reads one row at a time (IVF, HNSW,
    rerank) stay [L, D//2] (``_tile_scores_packed``).

Top-k inside a kernel (``select_topk``): Mosaic has no sort or top_k, so
the running top-k is k rounds of "row max, lowest key among the maxima,
mask it out" over the running set and the fresh tile. A tile whose every
score is at or below the running k-th value cannot change the result
(ties go to the lower key, and the running set always holds the lower
keys), so such tiles skip the rounds entirely.

Backend selection lives one level up (``ops.resolve_backend``): "pallas"
(compiled kernel, real TPU), "interpret" (this kernel under the Pallas
interpreter — tests), "xla" (pure-jnp fallback for CPU meshes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.binarize_lib import (
    SDC_NEG_INF,
    sdc_affine_epilogue,
    unpack_nibble_planes,
)
from repro.kernels.sdc.defaults import BLOCK_N, BLOCK_Q

# Key of an empty running slot: larger than any real key, so a real
# candidate always wins a tie against it.
KEY_EMPTY = 2**31 - 1
LANES = 128


def lane_pad(k: int) -> int:
    """Running top-k width: k rounded up to whole 128-lane vregs."""
    return -(-k // LANES) * LANES


def _check_code_dim(d_codes, D: int, packed: bool) -> None:
    want = D // 2 if packed else D
    if d_codes.shape[-1] != want:
        raise ValueError(
            f"document code dim {d_codes.shape[-1]} (shape {d_codes.shape}) "
            f"!= expected {want} for query dim D={D}, packed={packed}"
        )


def _check_packed_corpus(d_codes, D: int) -> None:
    if d_codes.shape[0] != D // 2:
        raise ValueError(
            f"packed corpus code dim {d_codes.shape[0]} (shape "
            f"{d_codes.shape}) != expected {D // 2} for query dim D={D}: "
            "the flat scan's packed corpus is [D//2, N], documents along "
            "lanes (ops.pack_scan_corpus)"
        )


def _check_block_tiling(Q: int, N: int, block_q: int, block_n: int) -> None:
    if Q % block_q != 0 or N % block_n != 0:
        raise ValueError(
            f"grid does not tile: Q={Q} % block_q={block_q} = {Q % block_q}, "
            f"N={N} % block_n={block_n} = {N % block_n}; pad Q/N to the "
            "block multiples (ops.sdc_search does) or pick dividing blocks"
        )


def _int8_dot(x: jax.Array, y: jax.Array) -> jax.Array:
    """[TQ, D] x [TN, D] -> [TQ, TN] int32 (MXU int8 path, exact)."""
    return jax.lax.dot_general(
        x,
        y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _epilogue(dot, sq, sd, inv, *, n_levels: int, dim: int) -> jax.Array:
    """Affine epilogue + exclusion of inv == 0 documents.

    sq: [TQ, 1] query code sums; sd: [TN, 1] document code sums;
    inv: [1, TN] reciprocal norms.
    """
    scores = sdc_affine_epilogue(
        dot, sq + sd.T, dim=dim, n_levels=n_levels, inv_norm=inv
    )
    return jnp.where(inv > 0, scores, SDC_NEG_INF)


def _tile_scores(q, d, inv, *, n_levels: int, dim: int) -> jax.Array:
    """SDC scores for one (TQ, TN) tile of unpacked int8 codes.

    inv is the [1, TN] row of reciprocal norms; excluded documents
    (inv == 0) come out as SDC_NEG_INF.
    """
    sq = jnp.sum(q.astype(jnp.int32), axis=-1, keepdims=True)
    sd = jnp.sum(d.astype(jnp.int32), axis=-1, keepdims=True)
    return _epilogue(_int8_dot(q, d), sq, sd, inv, n_levels=n_levels, dim=dim)


def _tile_scores_packed(qe, qo, p, inv, *, n_levels: int, dim: int) -> jax.Array:
    """Same as _tile_scores but for nibble-packed document codes.

    qe/qo: [TQ, D//2] int8 query codes at even/odd dims.
    p:     [TN, D//2] uint8 packed document codes.
    The integer partial sums equal the unpacked ones exactly, so scores are
    bit-identical to the int8 path.
    """
    lo, hi = unpack_nibble_planes(p)  # int32 planes in [0, 16)
    dot = _int8_dot(qe, lo.astype(jnp.int8)) + _int8_dot(qo, hi.astype(jnp.int8))
    sq = jnp.sum(qe.astype(jnp.int32), -1, keepdims=True) + jnp.sum(
        qo.astype(jnp.int32), -1, keepdims=True
    )
    sd = jnp.sum(lo, -1, keepdims=True) + jnp.sum(hi, -1, keepdims=True)
    return _epilogue(dot, sq, sd, inv, n_levels=n_levels, dim=dim)


def _tile_scores_lanes(q, p, inv, *, n_levels: int, dim: int) -> jax.Array:
    """Same scores for a tile of the flat scan's packed corpus, which lies
    documents along lanes.

    q: [TQ, D] int8 query codes, even dims then odd
       (``_interleave_queries``).
    p: [D//2, TN] uint8 packed document codes, one document a lane.
    The nibble planes stack along sublanes in the same even-then-odd
    order, so one (TQ, D) @ (D, TN) matmul forms the dot with no
    transpose, and the document sums are a sublane sum that is already
    the (1, TN) row the epilogue broadcasts. The integers equal the
    unpacked path's, so the scores are bit-identical to it.
    """
    planes = jnp.concatenate(unpack_nibble_planes(p), axis=0)  # [D, TN]
    dot = jax.lax.dot_general(
        q, planes.astype(jnp.int8),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    sq = jnp.sum(q.astype(jnp.int32), axis=-1, keepdims=True)
    sd = jnp.sum(planes, axis=0, keepdims=True)
    scores = sdc_affine_epilogue(
        dot, sq + sd, dim=dim, n_levels=n_levels, inv_norm=inv
    )
    return jnp.where(inv > 0, scores, SDC_NEG_INF)


def _sdc_kernel(q_ref, d_ref, dnorm_ref, out_ref, *, score, n_levels: int,
                dim: int):
    """One (TQ, TN) score tile."""
    out_ref[...] = score(
        q_ref[...], d_ref[...], dnorm_ref[...], n_levels=n_levels, dim=dim
    )


def _split_queries(q_codes: jax.Array):
    """[Q, D] int8 -> even/odd dim halves matching the nibble layout."""
    return q_codes[:, 0::2], q_codes[:, 1::2]


def _interleave_queries(q_codes: jax.Array) -> jax.Array:
    """[Q, D] int8 -> [Q, D], even dims then odd: the order in which
    ``_tile_scores_lanes`` stacks a packed tile's nibble planes."""
    return jnp.concatenate(_split_queries(q_codes), axis=1)


def _doc_specs(block_n: int, Dc: int):
    """BlockSpecs of the document codes and their [1, N] norm row."""
    return [
        pl.BlockSpec((block_n, Dc), lambda i, j: (j, 0)),
        pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
    ]


def _scan_operands(q_codes, d_codes, *, packed: bool, block_n: int):
    """(queries, N, tile scorer, document BlockSpecs) of a flat scan.

    An unpacked corpus is [N, D] int8, a document a row. A packed one is
    [D//2, N] uint8, a document a lane: its tiles are (D//2, block_n) at
    block (0, j), and the queries are permuted to its plane order.
    """
    D = q_codes.shape[1]
    norm_spec = pl.BlockSpec((1, block_n), lambda i, j: (0, j))
    if packed:
        _check_packed_corpus(d_codes, D)
        spec = pl.BlockSpec((D // 2, block_n), lambda i, j: (0, j))
        return (_interleave_queries(q_codes), d_codes.shape[1],
                _tile_scores_lanes, [spec, norm_spec])
    _check_code_dim(d_codes, D, False)
    return q_codes, d_codes.shape[0], _tile_scores, _doc_specs(block_n, D)


@functools.partial(
    jax.jit, static_argnames=("n_levels", "block_q", "block_n", "interpret", "packed")
)
def sdc_scores(
    q_codes: jax.Array,
    d_codes: jax.Array,
    d_inv_norm: jax.Array,
    *,
    n_levels: int,
    block_q: int = BLOCK_Q,
    block_n: int = BLOCK_N,
    interpret: bool = False,
    packed: bool = False,
) -> jax.Array:
    """SDC score matrix [Q, N] = <v(q), v(d)> / ||v(d)||.

    Q and N must be multiples of block_q / block_n (callers pad; see
    ops.sdc_search which handles padding + top-k). With ``packed=True``,
    d_codes is the nibble-packed uint8 [D//2, N] corpus, documents along
    lanes. Documents with d_inv_norm == 0 score SDC_NEG_INF (excluded).
    """
    q, N, score, d_specs = _scan_operands(q_codes, d_codes, packed=packed,
                                          block_n=block_n)
    Q, D = q_codes.shape
    _check_block_tiling(Q, N, block_q, block_n)
    return pl.pallas_call(
        functools.partial(_sdc_kernel, score=score, n_levels=n_levels, dim=D),
        grid=(Q // block_q, N // block_n),
        in_specs=[pl.BlockSpec((block_q, D), lambda i, j: (i, 0)), *d_specs],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Q, N), jnp.float32),
        interpret=interpret,
    )(q, d_codes, d_inv_norm.reshape(1, N))


def select_topk(parts, k: int):
    """Top-k by (value descending, key ascending) over several arrays.

    ``parts`` is a sequence of ``(values, keys, ids)`` triples of equal
    row count; ``ids`` may be None, in which case the key is the id.
    Runs k rounds of row max -> lowest key among the maxima -> mask it
    out, all with ops Mosaic lowers. Keys must be unique within a row
    among the entries that can win (empty slots carry ``KEY_EMPTY`` and
    -inf). Returns (values, keys, ids) as [rows, lane_pad(k)] arrays
    whose first k lanes are sorted; ids is None when the parts had none.
    """
    rows = parts[0][0].shape[0]
    kp = lane_pad(k)
    with_ids = parts[0][2] is not None
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, kp), 1)
    keys = [p[1] for p in parts]
    ids = [p[2] for p in parts]

    def row_max(xs):
        return functools.reduce(
            jnp.maximum, [jnp.max(x, axis=1, keepdims=True) for x in xs]
        )

    def row_min(xs):
        return functools.reduce(
            jnp.minimum, [jnp.min(x, axis=1, keepdims=True) for x in xs]
        )

    def body(r, carry):
        vals, out_v, out_k, out_i = carry
        m = row_max(vals)
        hit = [v == m for v in vals]
        key = row_min([jnp.where(h, kk, KEY_EMPTY) for h, kk in zip(hit, keys)])
        sel = [h & (kk == key) for h, kk in zip(hit, keys)]
        vals = tuple(jnp.where(s, -jnp.inf, v) for s, v in zip(sel, vals))
        out_v = jnp.where(lane == r, m, out_v)
        out_k = jnp.where(lane == r, key, out_k)
        if with_ids:
            ident = row_min([jnp.where(s, i, KEY_EMPTY) for s, i in zip(sel, ids)])
            out_i = jnp.where(lane == r, ident, out_i)
        return vals, out_v, out_k, out_i

    init = (
        tuple(p[0] for p in parts),
        jnp.full((rows, kp), -jnp.inf, jnp.float32),
        jnp.full((rows, kp), KEY_EMPTY, jnp.int32),
        jnp.full((rows, kp), KEY_EMPTY, jnp.int32),
    )
    _, out_v, out_k, out_i = jax.lax.fori_loop(0, k, body, init)
    return out_v, out_k, (out_i if with_ids else None)


def any_above_kth(run_vals: jax.Array, cand_vals: jax.Array, k: int):
    """Scalar: does any candidate beat its row's running k-th value?"""
    lane = jax.lax.broadcasted_iota(jnp.int32, run_vals.shape, 1)
    kth = jnp.min(jnp.where(lane < k, run_vals, jnp.inf), axis=1, keepdims=True)
    above = jnp.where(cand_vals > kth, 1.0, 0.0)
    return jnp.max(jnp.max(above, axis=1, keepdims=True), axis=0, keepdims=True)[0, 0] > 0


def _fold_tile(vals_ref, idx_ref, scores, *, j, k: int, block_n: int):
    """Fold one (TQ, TN) score tile into the running top-k out blocks.

    The out blocks map to the same (i, 0) slot for every inner grid step,
    so they persist in VMEM across the reduction. Keys are global doc
    indices, so ties keep the lowest index, matching a stable top-k over
    the full score row.
    """

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full(vals_ref.shape, -jnp.inf, jnp.float32)
        idx_ref[...] = jnp.full(idx_ref.shape, KEY_EMPTY, jnp.int32)

    keys = j * block_n + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)

    @pl.when(any_above_kth(vals_ref[...], scores, k))
    def _merge():
        v, key, _ = select_topk(
            [(vals_ref[...], idx_ref[...], None), (scores, keys, None)], k
        )
        vals_ref[...] = v
        idx_ref[...] = key


def _sdc_topk_kernel(
    q_ref, d_ref, dnorm_ref, vals_ref, idx_ref, *, score, n_levels, dim, k,
    block_n,
):
    """Fused scan + running top-k (streaming reduction over the N grid)."""
    scores = score(
        q_ref[...], d_ref[...], dnorm_ref[...], n_levels=n_levels, dim=dim
    )
    _fold_tile(vals_ref, idx_ref, scores, j=pl.program_id(1), k=k,
               block_n=block_n)


@functools.partial(
    jax.jit,
    static_argnames=("n_levels", "k", "block_q", "block_n", "interpret", "packed"),
)
def sdc_topk(
    q_codes: jax.Array,
    d_codes: jax.Array,
    d_inv_norm: jax.Array,
    *,
    n_levels: int,
    k: int,
    block_q: int = BLOCK_Q,
    block_n: int = BLOCK_N,
    interpret: bool = False,
    packed: bool = False,
):
    """Fused SDC scan + top-k: returns (values [Q, k], indices [Q, k]).

    Avoids materialising the [Q, N] score matrix in HBM — the dominant
    memory term of the naive pipeline (hillclimbed in EXPERIMENTS.md §Perf).
    Excluded documents (inv norm 0) surface as SDC_NEG_INF values. The
    corpus is laid out as for ``sdc_scores``.
    """
    q, N, score, d_specs = _scan_operands(q_codes, d_codes, packed=packed,
                                          block_n=block_n)
    Q, D = q_codes.shape
    _check_block_tiling(Q, N, block_q, block_n)
    if k > block_n:
        raise ValueError(
            f"fused top-k needs k <= block_n, got k={k}, block_n={block_n} "
            "(ops.sdc_search widens the effective block for large k)"
        )
    kp = lane_pad(k)
    out_spec = pl.BlockSpec((block_q, kp), lambda i, j: (i, 0))
    # Named by its query tile, so a device trace shows the tile it ran:
    # the corpus streams Q // block_q times a call.
    vals, idx = pl.pallas_call(
        functools.partial(_sdc_topk_kernel, score=score, n_levels=n_levels,
                          dim=D, k=k, block_n=block_n),
        grid=(Q // block_q, N // block_n),
        name=f"sdc_topk_q{block_q}",
        in_specs=[pl.BlockSpec((block_q, D), lambda i, j: (i, 0)), *d_specs],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((Q, kp), jnp.float32),
            jax.ShapeDtypeStruct((Q, kp), jnp.int32),
        ],
        interpret=interpret,
    )(q, d_codes, d_inv_norm.reshape(1, N))
    return vals[:, :k], idx[:, :k]
