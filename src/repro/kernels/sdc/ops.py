"""jit'd public wrappers around the SDC kernels: padding, top-k search,
backend selection.

Every index type (FlatSDC, IVFIndex, the distributed engine) scores
through this module, so the affine epilogue and its exclusion semantics
live in exactly one place. Backends:

  * "pallas"    — compiled Pallas kernel (real TPU).
  * "interpret" — the same kernel under the Pallas interpreter (tests).
  * "xla"       — pure-jnp fallback for CPU meshes; same shared epilogue,
                  so scores are bit-identical to the kernel path.
  * "auto"      — "pallas" on TPU, "xla" otherwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.binarize_lib import (
    SDC_NEG_INF,
    pack_codes_nibbles,
    sdc_affine_epilogue,
    unpack_nibble_planes,
)
from repro.kernels.sdc import ref as sdc_ref_mod
from repro.kernels.sdc.defaults import BLOCK_N, BlockPlan, scan_block_q
from repro.kernels.sdc.sdc import sdc_scores, sdc_topk

NEG_INF = SDC_NEG_INF

SDC_BACKENDS = ("auto", "pallas", "interpret", "xla")


def resolve_backend(backend: str = "auto") -> str:
    """Resolve the scoring backend flag to a concrete implementation."""
    if backend not in SDC_BACKENDS:
        raise ValueError(f"unknown SDC backend {backend!r}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


@jax.jit
def pack_scan_corpus(codes: jax.Array) -> jax.Array:
    """Integer codes [N, D] (values < 16) -> the flat scan's packed corpus:
    nibble-packed uint8 [D//2, N], documents along lanes. Done once, when
    an index is built; every packed flat scan reads this layout."""
    return pack_codes_nibbles(codes).T


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    """``x`` zero-padded along ``axis`` to a multiple; as it is if whole."""
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _ceil_mult(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_levels", "k", "block_q", "block_n", "interpret", "fused", "packed",
    ),
)
def sdc_search(
    q_codes: jax.Array,
    d_codes: jax.Array,
    d_inv_norm: jax.Array,
    *,
    n_levels: int,
    k: int,
    block_q: int | None = None,
    block_n: int = BLOCK_N,
    interpret: bool = False,
    fused: bool = True,
    packed: bool = False,
):
    """Top-k SDC search of queries against a code corpus.

    Args:
      q_codes: [Q, D] int8 recurrent-binary codes of queries.
      d_codes: [N, D] int8 codes of documents, or with ``packed=True`` the
        nibble-packed uint8 [D//2, N] corpus, documents along lanes
        (``pack_scan_corpus``).
      d_inv_norm: [N] f32 reciprocal doc-value norms (0 => excluded).
      block_q: query tile; None derives it from Q
        (``defaults.scan_block_q``).
      fused: use the fused scan+top-k kernel (no [Q, N] materialisation).

    Returns:
      (scores [Q, k], indices [Q, k]); slots with no valid candidate
      (padding, excluded docs, k > N) come back as (SDC_NEG_INF, -1).
    """
    Q0 = q_codes.shape[0]
    if block_q is None:
        block_q = scan_block_q(Q0)
    # The fused kernel tiles the running top-k against its N block, so the
    # effective block must hold k entries; keep it a multiple of block_n so
    # lane alignment survives. N is padded against the same effective block
    # (this also guarantees padded N >= k for the final top_k). Padded
    # documents get a zero inverse norm, which every kernel excludes; a
    # corpus that is already a whole number of blocks is passed as it is.
    eff_bn = _ceil_mult(max(k, block_n), block_n)
    q_codes = _pad_to(q_codes, 0, block_q)
    d_codes = _pad_to(d_codes, 1 if packed else 0, eff_bn)
    d_inv_norm = _pad_to(d_inv_norm, 0, eff_bn)

    if fused:
        vals, idx = sdc_topk(
            q_codes,
            d_codes,
            d_inv_norm,
            n_levels=n_levels,
            k=k,
            block_q=block_q,
            block_n=eff_bn,
            interpret=interpret,
            packed=packed,
        )
    else:
        scores = sdc_scores(
            q_codes,
            d_codes,
            d_inv_norm,
            n_levels=n_levels,
            block_q=block_q,
            block_n=block_n,
            interpret=interpret,
            packed=packed,
        )
        vals, idx = jax.lax.top_k(scores, k)
    # Normalise empty slots: excluded/padded docs surface as NEG_INF values
    # whose indices are meaningless — report them as -1.
    idx = jnp.where(vals > NEG_INF / 2, idx, -1)
    return vals[:Q0], idx[:Q0]


@functools.partial(jax.jit, static_argnames=("n_levels", "k", "packed"))
def sdc_search_xla(
    q_codes: jax.Array,
    d_codes: jax.Array,
    d_inv_norm: jax.Array,
    *,
    n_levels: int,
    k: int,
    packed: bool = False,
):
    """Pure-jnp top-k SDC search (the "xla" backend).

    Same contract as ``sdc_search``; XLA fuses the affine epilogue into the
    int32 matmul so CPU meshes get one matmul + top-k without the Pallas
    interpreter's Python overhead. Packed corpora are scored through the
    same even/odd half-matmul decomposition as the kernel (the packed
    corpus is [D//2, N], documents along lanes, as the kernel reads it),
    so scores stay bit-identical to the unpacked path.
    """
    D = q_codes.shape[-1]
    cq = q_codes.astype(jnp.int32)
    if packed:
        lo, hi = unpack_nibble_planes(d_codes)
        dot = cq[:, 0::2] @ lo + cq[:, 1::2] @ hi
        sd = (jnp.sum(lo, 0) + jnp.sum(hi, 0))[None, :]
    else:
        cd = d_codes.astype(jnp.int32)
        dot = cq @ cd.T
        sd = jnp.sum(cd, -1)[None, :]
    sq = jnp.sum(cq, -1, keepdims=True)
    scores = sdc_affine_epilogue(
        dot, sq + sd, dim=D, n_levels=n_levels, inv_norm=d_inv_norm[None, :]
    )
    scores = jnp.where(d_inv_norm[None, :] > 0, scores, NEG_INF)
    if k > scores.shape[1]:
        pad = jnp.full((scores.shape[0], k - scores.shape[1]), NEG_INF,
                       scores.dtype)
        scores = jnp.concatenate([scores, pad], axis=1)
    vals, idx = jax.lax.top_k(scores, k)
    idx = jnp.where(vals > NEG_INF / 2, idx, -1)
    return vals, idx


def sdc_search_backend(
    q_codes, d_codes, d_inv_norm, *, n_levels, k, backend="auto",
    block_q=None, block_n=BLOCK_N, packed=False,
    block_plan: BlockPlan | None = None,
):
    """Dispatch a top-k SDC search to the resolved backend.

    ``block_q`` None sizes the query tile from the request
    (``defaults.scan_block_q``). ``block_plan`` (a ``defaults.BlockPlan``,
    e.g. from the ``launch/autotune`` sweep) overrides
    ``block_q``/``block_n`` when given. Blocks only shape the kernel
    launch — scores and ids are bit-identical across every block choice —
    so a plan is always safe to apply. The "xla" backend has no tiles;
    plans are inert there.
    """
    backend = resolve_backend(backend)
    if block_plan is not None:
        block_q, block_n = block_plan.block_q, block_plan.block_n
    if backend == "xla":
        return sdc_search_xla(
            q_codes, d_codes, d_inv_norm, n_levels=n_levels, k=k, packed=packed
        )
    return sdc_search(
        q_codes, d_codes, d_inv_norm, n_levels=n_levels, k=k,
        block_q=block_q, block_n=block_n,
        interpret=(backend == "interpret"), fused=True, packed=packed,
    )


def sdc_search_ref(q_codes, d_codes, n_levels: int, k: int):
    """Oracle top-k via the exact reference (for tests/benchmarks)."""
    scores = sdc_ref_mod.sdc_ref(q_codes, d_codes, n_levels)
    return jax.lax.top_k(scores, k)
