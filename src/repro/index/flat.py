"""Flat (exhaustive) indexes over three embedding forms.

Mirrors the paper's Table 5 contenders:
  * FlatFloat   — full-precision cosine (the "float / flat" row).
  * FlatBitwise — recurrent binary, xor+popcount (Shan et al. [44] on CPU).
  * FlatSDC     — recurrent binary, SDC kernel (ours).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.binarize_lib import coarse_codes, pack_bitplanes, unpack_codes
from repro.kernels.binary_dot.ops import binary_dot_search
from repro.kernels.sdc import ref as sdc_ref
from repro.kernels.sdc.defaults import BLOCK_N, BlockPlan, plan_for
from repro.kernels.sdc.ops import pack_scan_corpus, sdc_search_backend
from repro.kernels.sdc.rerank import fine_inv_norms, sdc_rerank_backend


@dataclasses.dataclass
class FlatFloat:
    emb: jax.Array  # [N, D] float, L2-normalised at build

    @staticmethod
    def build(emb: jax.Array) -> "FlatFloat":
        emb = emb * jax.lax.rsqrt(jnp.sum(emb * emb, -1, keepdims=True) + 1e-12)
        return FlatFloat(emb=emb)

    def search(self, q: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-12)
        scores = q @ self.emb.T
        return jax.lax.top_k(scores, k)

    def nbytes(self) -> int:
        return self.emb.size * self.emb.dtype.itemsize


@dataclasses.dataclass
class FlatSDC:
    # [N, m] int8; if packed, nibble-packed uint8 [m//2, N], documents
    # along lanes (ops.pack_scan_corpus), as the scan streams it
    codes: jax.Array
    inv_norm: jax.Array  # [N] f32
    n_levels: int
    packed: bool = False  # int4 code streaming (2 dims/byte in HBM)
    backend: str = "auto"  # ops.resolve_backend: Pallas on TPU, jnp elsewhere

    @staticmethod
    def build(
        codes: jax.Array, n_levels: int, packed: bool = False,
        backend: str = "auto",
    ) -> "FlatSDC":
        inv = sdc_ref.doc_inv_norms(codes, n_levels)
        if packed:
            if n_levels > 4:
                raise ValueError(
                    f"packed codes need n_levels <= 4, got {n_levels}"
                )
            codes = pack_scan_corpus(codes)
        return FlatSDC(codes=codes, inv_norm=inv, n_levels=n_levels,
                       packed=packed, backend=backend)

    @property
    def code_dim(self) -> int:
        return self.codes.shape[0] * 2 if self.packed else self.codes.shape[1]

    def search(
        self, q_codes: jax.Array, k: int, block_n: int = BLOCK_N,
        block_q: int | None = None, block_plan: BlockPlan | None = None,
    ):
        """Top-k of ``q_codes`` over the corpus. The query tile follows
        the request's row count (``defaults.scan_block_q``) unless
        ``block_q`` or a scan ``block_plan`` sets it."""
        return sdc_search_backend(
            q_codes,
            self.codes,
            self.inv_norm,
            n_levels=self.n_levels,
            k=k,
            backend=self.backend,
            block_q=block_q,
            block_n=block_n,
            packed=self.packed,
            block_plan=block_plan,
        )

    def nbytes(self) -> int:
        # 4-bit codes pack two dims per byte on disk; +4B quantised norm.
        packed_codes = (self.code_dim * self.n_levels + 7) // 8
        return self.inv_norm.shape[0] * (packed_codes + 4)


@dataclasses.dataclass
class BiGranularFlat:
    """Two-tier exhaustive index: hot coarse scan, cold fine rerank.

    The coarse tier is a plain ``FlatSDC`` over the level-prefix codes
    (first ``coarse_levels`` residual levels — a right shift, no
    retraining; nibble-packed when ``coarse_levels <= 4`` and
    ``packed``). The fine tier keeps the full-level codes exactly as
    given: a numpy array (including ``np.memmap``) stays host-side and
    only the per-query top-``k_coarse`` survivor rows are ever read
    from it, so the fine tier may exceed RAM.

    The rerank is bit-identical to a full-level flat scan restricted to
    the survivors (``kernels/sdc/rerank``), so ``k_coarse >= N``
    degenerates to exactly ``FlatSDC.search`` at full levels.
    """

    coarse: FlatSDC
    fine_codes: Any  # [N, D] int8 full-level codes; numpy stays host-side
    fine_inv_norm: Any  # [N] f32
    n_levels: int
    coarse_levels: int
    k_coarse: int
    backend: str = "auto"

    @staticmethod
    def build(
        codes: Any,
        n_levels: int,
        *,
        coarse_levels: int,
        k_coarse: int,
        packed: bool = False,
        backend: str = "auto",
    ) -> "BiGranularFlat":
        host = isinstance(codes, np.ndarray)
        c_src = jnp.asarray(np.asarray(codes)) if host else codes
        coarse = FlatSDC.build(
            coarse_codes(c_src, n_levels, coarse_levels), coarse_levels,
            packed=packed and coarse_levels <= 4, backend=backend,
        )
        fine_inv = fine_inv_norms(codes, n_levels)
        return BiGranularFlat(
            coarse=coarse, fine_codes=codes, fine_inv_norm=fine_inv,
            n_levels=n_levels, coarse_levels=coarse_levels,
            k_coarse=k_coarse, backend=backend,
        )

    def search(
        self, q_codes: jax.Array, k: int, block_n: int = BLOCK_N,
        k_coarse: int | None = None,
        scan_plan: BlockPlan | None = None,
    ) -> Tuple[jax.Array, jax.Array]:
        kc = self.k_coarse if k_coarse is None else k_coarse
        kc = min(kc, self.fine_codes.shape[0])
        q = jnp.asarray(q_codes)
        qc = coarse_codes(q, self.n_levels, self.coarse_levels)
        _, cand = self.coarse.search(qc, kc, block_n=block_n,
                                     block_plan=scan_plan)
        return sdc_rerank_backend(
            q, self.fine_codes, self.fine_inv_norm, cand,
            n_levels=self.n_levels, k=k, backend=self.backend,
        )

    def coarse_nbytes(self) -> int:
        return self.coarse.nbytes()

    def nbytes(self) -> int:
        fine = self.fine_codes.shape[0] * (
            (self.fine_codes.shape[1] * self.n_levels + 7) // 8 + 4
        )
        return self.coarse.nbytes() + fine


def flat_search_from_snapshot(
    codes,
    n_levels: int = None,
    *,
    k: int,
    packed: bool = False,
    backend: str = "auto",
    block_n: int = BLOCK_N,
    rerank: dict | None = None,
    effort=None,
    block_plan=None,
):
    """Rebuild-from-snapshot entry point (live index lifecycle).

    Builds a fresh exhaustive index from a corpus snapshot's unpacked
    codes and returns a serving ``SearchFn`` closure
    (``codes -> (scores, ids)``), ready to be hot-swapped into a
    drained replica by ``launch/lifecycle.RollingSwapController``.
    Deterministic: the same snapshot + params always yields a
    bit-identical index.

    First argument: a ``CorpusSnapshot`` (preferred — carries its own
    ``n_levels``) or raw unpacked codes plus an explicit ``n_levels``
    (legacy form). Same convention across every
    ``*_search_from_snapshot`` entry point.

    ``rerank={"coarse_levels": c, "k_coarse": k'}`` switches the
    closure to bi-granular mode (``BiGranularFlat``): packed hot coarse
    scan at ``c`` levels for k' survivors, full-level fine rerank of
    exactly those rows. The closure carries ``fn.reranked = True`` so
    the serving tier can stamp result provenance. A numpy / memmapped
    snapshot keeps its fine tier host-side (cold). ``effort`` (any
    object with an int ``level`` attribute, 0 = full —
    ``launch.proxy.EffortKnob``) is read per call and shrinks
    ``k_coarse`` by halving (floored at k); level 0 is bit-identical to
    ``effort=None``. A flat index has no other cost knob, so ``effort``
    without ``rerank`` is ignored.

    ``block_plan`` — a single ``BlockPlan`` or a ``{kind: plan}``
    mapping (``launch/autotune``) — sets the scan tiles (the coarse scan
    in bi-granular mode). Plans never change scores, only launch shapes.
    """
    from repro.index._snapshot import (
        resolve_rerank_args,
        resolve_snapshot_args,
        split_effort,
    )

    codes, n_levels = resolve_snapshot_args(codes, n_levels)
    rr = resolve_rerank_args(rerank, n_levels)
    scan_plan = plan_for(block_plan, "scan")
    if rr is None:
        index = FlatSDC.build(
            jnp.asarray(codes), n_levels, packed=packed, backend=backend
        )
        return lambda q: index.search(q, k, block_n=block_n,
                                      block_plan=scan_plan)

    c_levels, k_coarse = rr
    bigr = BiGranularFlat.build(
        codes, n_levels, coarse_levels=c_levels, k_coarse=k_coarse,
        packed=packed, backend=backend,
    )
    if effort is None:
        fn = lambda q: bigr.search(  # noqa: E731
            q, k, block_n=block_n, scan_plan=scan_plan,
        )
    else:
        def fn(q):
            kc_eff, _ = split_effort(effort.level, k=k, k_coarse=k_coarse)
            return bigr.search(
                q, k, block_n=block_n, k_coarse=kc_eff,
                scan_plan=scan_plan,
            )

        fn.effort = effort
    fn.reranked = True
    return fn


@dataclasses.dataclass
class FlatBitwise:
    packed: jax.Array  # [N, n_levels, m/32] uint32
    m: int
    n_levels: int
    interpret: bool = True

    @staticmethod
    def build(codes: jax.Array, n_levels: int, interpret: bool = True) -> "FlatBitwise":
        bits = unpack_codes(codes, n_levels)
        return FlatBitwise(
            packed=pack_bitplanes(bits), m=codes.shape[1], n_levels=n_levels,
            interpret=interpret,
        )

    def search(self, q_codes: jax.Array, k: int):
        q_bits = unpack_codes(q_codes, self.n_levels)
        q_packed = pack_bitplanes(q_bits)
        return binary_dot_search(
            q_packed, self.packed, m=self.m, k=k, interpret=self.interpret
        )

    def nbytes(self) -> int:
        return self.packed.size * 4
