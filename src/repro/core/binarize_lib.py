"""Recurrent binarization module (BEBR §3.2.1).

The module phi maps a full-precision embedding f in R^d to a recurrent
binary embedding with ``m * n_levels`` bits (paper: n_levels = u + 1):

    b_0   = sign(W_0(f))                         # base binarization
    f̂_t   = normalize(R_t(b_t))                  # reconstruction
    r_t   = sign(W_{t+1}(f - f̂_t))               # residual binarization
    b_t+1 = b_t + 2^{-(t+1)} r_t

``W_*`` and ``R_*`` are MLPs (linear -> batchnorm -> ReLU -> linear),
richer than the plain linear maps of Shan et al. [44]. ``sign`` uses a
straight-through estimator so the module is trainable end to end.

Everything is a pure function over an explicit parameter pytree so it
composes with pjit/shard_map without framework baggage.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class BinarizerConfig:
    """Configuration of the recurrent binarization module.

    Attributes:
      input_dim: dimension d of the incoming float embeddings.
      code_dim: m, output dimension of each binarization block.
      n_levels: u + 1 total binary vectors (base + u residual loops).
      hidden_dim: width of the MLP hidden layer (0 => single linear).
      bn_momentum: batch-norm running-stat momentum.
    """

    input_dim: int
    code_dim: int
    n_levels: int = 4
    hidden_dim: int = 0
    bn_momentum: float = 0.9
    # learnable input-alignment map (identity-initialised). Used by
    # backward-compatible training: fold a stage-1 cross-space alignment
    # into P and refine it jointly with L_BC (RBT-style transformation).
    input_map: bool = False

    @property
    def total_bits(self) -> int:
        return self.code_dim * self.n_levels

    @property
    def u(self) -> int:
        return self.n_levels - 1


# ---------------------------------------------------------------------------
# Straight-through sign.
# ---------------------------------------------------------------------------


@jax.custom_vjp
def ste_sign(x: jax.Array) -> jax.Array:
    """sign(x) in {-1, +1}; gradient is identity clipped to |x| <= 1."""
    return jnp.where(x > 0, 1.0, -1.0).astype(x.dtype)


def _ste_fwd(x):
    return ste_sign(x), x


def _ste_bwd(x, g):
    return (jnp.where(jnp.abs(x) <= 1.0, g, 0.0),)


ste_sign.defvjp(_ste_fwd, _ste_bwd)


# ---------------------------------------------------------------------------
# MLP block: linear -> BN -> ReLU -> linear (hidden_dim=0 => single linear).
# ---------------------------------------------------------------------------


def _init_linear(key, d_in, d_out, dtype=jnp.float32):
    kw, _ = jax.random.split(key)
    scale = jnp.sqrt(2.0 / d_in).astype(dtype)
    return {
        "w": jax.random.normal(kw, (d_in, d_out), dtype) * scale,
        "b": jnp.zeros((d_out,), dtype),
    }


def _init_mlp(key, d_in, d_hidden, d_out, dtype=jnp.float32):
    if d_hidden <= 0:
        return {"out": _init_linear(key, d_in, d_out, dtype)}
    k1, k2 = jax.random.split(key)
    return {
        "in": _init_linear(k1, d_in, d_hidden, dtype),
        "bn_scale": jnp.ones((d_hidden,), dtype),
        "bn_bias": jnp.zeros((d_hidden,), dtype),
        "out": _init_linear(k2, d_hidden, d_out, dtype),
    }


def _init_mlp_state(d_hidden, dtype=jnp.float32):
    if d_hidden <= 0:
        return {}
    return {
        "bn_mean": jnp.zeros((d_hidden,), dtype),
        "bn_var": jnp.ones((d_hidden,), dtype),
    }


def _apply_mlp(params, state, x, *, train: bool, momentum: float):
    """Returns (y, new_state)."""
    if "in" not in params:
        y = x @ params["out"]["w"] + params["out"]["b"]
        return y, state
    h = x @ params["in"]["w"] + params["in"]["b"]
    if train:
        mean = jnp.mean(h, axis=0)
        var = jnp.var(h, axis=0)
        new_state = {
            "bn_mean": momentum * state["bn_mean"] + (1 - momentum) * mean,
            "bn_var": momentum * state["bn_var"] + (1 - momentum) * var,
        }
    else:
        mean, var = state["bn_mean"], state["bn_var"]
        new_state = state
    h = (h - mean) * jax.lax.rsqrt(var + 1e-5)
    h = h * params["bn_scale"] + params["bn_bias"]
    h = jax.nn.relu(h)
    y = h @ params["out"]["w"] + params["out"]["b"]
    return y, new_state


# ---------------------------------------------------------------------------
# Recurrent binarizer.
# ---------------------------------------------------------------------------


def init_binarizer(key: jax.Array, cfg: BinarizerConfig, dtype=jnp.float32) -> Tuple[Params, Params]:
    """Initialise (params, state) for the recurrent binarizer.

    params["W"][t]: binarization MLP t (d -> m), t in [0, n_levels)
    params["R"][t]: reconstruction MLP t (m -> d), t in [0, n_levels - 1)
    """
    n = cfg.n_levels
    keys = jax.random.split(key, 2 * n)
    h = cfg.hidden_dim
    params = {
        "W": [_init_mlp(keys[t], cfg.input_dim, h, cfg.code_dim, dtype) for t in range(n)],
        "R": [_init_mlp(keys[n + t], cfg.code_dim, h, cfg.input_dim, dtype) for t in range(n - 1)],
    }
    if cfg.input_map:
        params["P"] = jnp.eye(cfg.input_dim, dtype=dtype)
    state = {
        "W": [_init_mlp_state(h, dtype) for _ in range(n)],
        "R": [_init_mlp_state(h, dtype) for _ in range(n - 1)],
    }
    return params, state


def _l2norm(x, axis=-1, eps=1e-12):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)


def binarize(
    params: Params,
    state: Params,
    f: jax.Array,
    cfg: BinarizerConfig,
    *,
    train: bool = False,
) -> Tuple[jax.Array, jax.Array, Params]:
    """Run recurrent binarization.

    Args:
      f: [batch, input_dim] float embeddings.

    Returns:
      bits:  [batch, n_levels, code_dim] in {-1, +1} — level t holds the
             t-th binary vector (b_0, r_0, ..., r_{u-1}).
      b_u:   [batch, code_dim] the recurrent binary embedding (grid values).
      new_state: updated BN running stats (== state when train=False).
    """
    n = cfg.n_levels
    new_state = {"W": list(state["W"]), "R": list(state["R"])}
    levels: List[jax.Array] = []

    if cfg.input_map and "P" in params:
        f = _l2norm(f @ params["P"])

    h, new_state["W"][0] = _apply_mlp(
        params["W"][0], state["W"][0], f, train=train, momentum=cfg.bn_momentum
    )
    b = ste_sign(h)
    levels.append(b)
    acc = b
    for t in range(n - 1):
        recon, new_state["R"][t] = _apply_mlp(
            params["R"][t], state["R"][t], acc, train=train, momentum=cfg.bn_momentum
        )
        recon = _l2norm(recon)
        resid = _l2norm(f) - recon
        h, new_state["W"][t + 1] = _apply_mlp(
            params["W"][t + 1], state["W"][t + 1], resid, train=train, momentum=cfg.bn_momentum
        )
        r = ste_sign(h)
        levels.append(r)
        acc = acc + (2.0 ** -(t + 1)) * r
    bits = jnp.stack(levels, axis=-2)  # [batch, n_levels, m]
    return bits, acc, new_state


def binarize_eval(params, state, f, cfg: BinarizerConfig) -> jax.Array:
    """Inference helper: returns only the recurrent binary embedding b_u."""
    _, b_u, _ = binarize(params, state, f, cfg, train=False)
    return b_u


# ---------------------------------------------------------------------------
# Code packing.
#
# bits[-1/+1] per level  <->  integer codes in [0, 2^n_levels)  <->  values.
#
# Identity (DESIGN.md §2): value = a * code + beta with
#   a = 2^(2 - n_levels),  beta = -(2 - 2^(1 - n_levels))
# (in terms of u = n_levels - 1: a = 2^(1-u), beta = -(2 - 2^-u)).
# ---------------------------------------------------------------------------


def code_affine_constants(n_levels: int) -> Tuple[float, float]:
    u = n_levels - 1
    a = 2.0 ** (1 - u)
    beta = -(2.0 - 2.0 ** (-u))
    return a, beta


# Sentinel for "excluded from ranking" — shared by every SDC scoring path
# (kernel tiles, jnp fallbacks, the distributed engine's failover mask).
SDC_NEG_INF = -1e30


def sdc_affine_epilogue(dot, code_sums, *, dim: int, n_levels: int, inv_norm=None):
    """The SDC affine epilogue: integer-code partial sums -> scores.

        <v(q), v(d)> = a^2 (c_q . c_d) + a*beta*(sum c_q + sum c_d) + D*beta^2

    This is the single implementation of the identity used by the Pallas
    kernels, the jnp fallbacks, the IVF fine layer, the distributed engine
    and the HNSW graph walker. Keeping one copy guarantees every path is
    bit-identical (same float op order) — the packed-int4 and int8 scans
    produce the same dot/code_sums integers, hence the same scores.

    Args:
      dot: int32 code dot products, any shape.
      code_sums: sum(c_q) + sum(c_d), already broadcast against ``dot``.
      dim: D, the (unpacked) code dimension.
      n_levels: grid levels (u + 1).
      inv_norm: optional reciprocal document norms broadcast against ``dot``;
        when given, scores are scaled by it. Entries with inv_norm == 0 are
        conventionally "excluded" — callers mask them to SDC_NEG_INF.

    Pure arithmetic (no jnp.* calls), so it works on numpy arrays just as
    well as on traced jax values — including inside a Pallas kernel body.
    (``dot`` and ``code_sums`` must be arrays: ``.astype`` is required.)
    """
    a, beta = code_affine_constants(n_levels)
    scores = (
        (a * a) * dot.astype(jnp.float32)
        + (a * beta) * code_sums.astype(jnp.float32)
        + dim * (beta * beta)
    )
    if inv_norm is not None:
        scores = scores * inv_norm
    return scores


def pack_codes(bits: jax.Array) -> jax.Array:
    """[-1,+1] bits [..., n_levels, m] -> integer codes [..., m] (int8).

    Level 0 (the base vector) is the MSB so that the affine identity holds.
    """
    n = bits.shape[-2]
    weights = (2 ** jnp.arange(n - 1, -1, -1, dtype=jnp.int32))  # [n]
    zo = ((bits + 1.0) * 0.5).astype(jnp.int32)  # {0,1}
    codes = jnp.tensordot(zo.swapaxes(-1, -2), weights, axes=([-1], [0]))
    return codes.astype(jnp.int8)


def make_encode_fn(params, state, cfg: "BinarizerConfig"):
    """Serving ``EncodeFn`` from trained binarizer weights.

    The one canonical closure (jit'd eval-mode binarize -> per-dim
    packed int codes) that ``launch/serve.py``, the examples, the
    benchmarks, and the version-compat machinery all previously
    hand-rolled: float embeddings [B, dim] -> packed codes [B, code_dim]
    int8. Accepts numpy or jax inputs (``jnp.asarray`` outside the jit
    boundary keeps retracing off the hot path). Distinct weights give a
    distinct jit cache entry, so a ``CompatibilityMatrix`` can register
    one of these per (query_version, index_version) pair.
    """
    @jax.jit
    def _encode(e):
        return pack_codes(binarize(params, state, e, cfg)[0])

    return lambda e: _encode(jnp.asarray(e))


def coarse_codes(codes, n_levels: int, coarse_levels: int):
    """Level-prefix truncation: keep the first ``coarse_levels`` residual
    levels of an ``n_levels`` integer code.

    ``pack_codes`` makes level 0 (the base vector) the MSB, so dropping
    the trailing ``n_levels - coarse_levels`` residual levels is a right
    shift — the result is a *valid* integer code at ``coarse_levels``
    levels, scoreable through the same affine epilogue with no
    re-encoding. This is what makes the bi-granular memory hierarchy
    free at build time: the hot coarse tier is a bit-shift view of the
    cold full-level codes. Works on numpy and jax arrays alike.
    """
    if not 1 <= coarse_levels <= n_levels:
        raise ValueError(
            f"coarse_levels must be in [1, {n_levels}], got {coarse_levels}"
        )
    shift = n_levels - coarse_levels
    if shift == 0:
        return codes
    return (codes >> shift).astype(codes.dtype)


def unpack_codes(codes: jax.Array, n_levels: int) -> jax.Array:
    """Integer codes [..., m] -> bits [..., n_levels, m] in {-1, +1}."""
    c = codes.astype(jnp.int32)
    shifts = jnp.arange(n_levels - 1, -1, -1, dtype=jnp.int32)  # level t -> shift n-1-t
    planes = (c[..., None, :] >> shifts[:, None]) & 1  # [..., n_levels, m]
    return (planes * 2 - 1).astype(jnp.float32)


def codes_to_values(codes: jax.Array, n_levels: int) -> jax.Array:
    """Integer codes -> recurrent binary grid values b_u (float32)."""
    a, beta = code_affine_constants(n_levels)
    return codes.astype(jnp.float32) * a + beta


def values_to_codes(values: jax.Array, n_levels: int) -> jax.Array:
    """Grid values b_u -> integer codes (exact for on-grid values)."""
    a, beta = code_affine_constants(n_levels)
    return jnp.round((values - beta) / a).astype(jnp.int8)


def pack_bitplanes(bits: jax.Array) -> jax.Array:
    """[-1,+1] bits [..., n_levels, m] -> packed uint32 [..., n_levels, m/32].

    Used by the xor+popcount baseline (kernels/binary_dot). m must be a
    multiple of 32. Bit j of word w holds dimension w*32 + j.
    """
    *lead, n, m = bits.shape
    assert m % 32 == 0, f"code_dim {m} must be a multiple of 32"
    zo = ((bits + 1.0) * 0.5).astype(jnp.uint32).reshape(*lead, n, m // 32, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(zo << shifts, axis=-1).astype(jnp.uint32)


def unpack_bitplanes(packed: jax.Array, m: int) -> jax.Array:
    """Packed uint32 [..., n_levels, m/32] -> bits [..., n_levels, m]."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    zo = (packed[..., None] >> shifts) & jnp.uint32(1)
    *lead, n, words, _ = zo.shape
    return (zo.reshape(*lead, n, words * 32)[..., :m].astype(jnp.float32) * 2 - 1)


# ---------------------------------------------------------------------------
# int4 nibble packing: 2 code dims per byte.
#
# For n_levels <= 4 every integer code fits in 4 bits, so the serving-time
# storage halves: byte j of the packed row holds dim 2j in its low nibble
# and dim 2j + 1 in its high nibble. The SDC kernels consume this layout
# directly (shift+mask unpack on the VPU, two half-width int8 MXU matmuls),
# halving HBM traffic per scanned document.
# ---------------------------------------------------------------------------


def pack_codes_nibbles(codes: jax.Array) -> jax.Array:
    """Integer codes [..., D] (values < 16, D even) -> packed uint8 [..., D//2].

    Requires n_levels <= 4 (codes in [0, 16)); values are not range-checked
    here (that would force a host sync) — index builders validate n_levels.
    """
    D = codes.shape[-1]
    if D % 2 != 0:
        raise ValueError(f"code dim {D} must be even to nibble-pack")
    c = codes.astype(jnp.uint8)
    return (c[..., 0::2] | (c[..., 1::2] << 4)).astype(jnp.uint8)


def unpack_nibble_planes(packed: jax.Array):
    """Packed uint8 [..., D//2] -> (lo, hi) int32 planes in [0, 16).

    ``lo`` holds the even dims (0, 2, ...), ``hi`` the odd dims — the
    layout-critical inverse of ``pack_codes_nibbles``. Every packed scoring
    path (Pallas tiles, jnp fallbacks, IVF gather) unpacks through this one
    helper so the nibble layout cannot silently diverge between backends.
    The shifts run on int32: Mosaic cannot legalize an 8-bit shift.
    """
    p = packed.astype(jnp.uint8).astype(jnp.int32)
    return p & 0xF, (p >> 4) & 0xF


def unpack_codes_nibbles(packed: jax.Array) -> jax.Array:
    """Packed uint8 [..., D//2] -> integer codes [..., D] (int8)."""
    lo, hi = unpack_nibble_planes(packed)
    out = jnp.stack([lo, hi], axis=-1).astype(jnp.int8)
    return out.reshape(*packed.shape[:-1], packed.shape[-1] * 2)
