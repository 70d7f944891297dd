"""The plain reference: what a served answer must say, in straightforward
numpy and jax.numpy.

It follows the published semantics of BEBR (arXiv:2302.08714, §3.2.1 and
§3.3) and imports nothing of the program under test:

* the recurrent binarizer's forward pass (linear -> batch norm in eval
  mode -> ReLU -> linear per block; sign; normalised reconstruction;
  residual), teacher-forced on the bits a served code holds, in float64
  on the host;
* the integer code of a recurrent binary vector (level 0 is the most
  significant bit) and its grid value ``v = a * c + beta``;
* exact symmetric distance scores ``<v(q), v(d)> / ||v(d)||`` and the
  exact top-k over a whole corpus, on the device in blocks of rows;
* the comparison of every checked answer with those, reduced to the
  numbers that decide ``correct``.

An index family whose promise is not the whole corpus's exact top-k
(a two-tier index reranks only its coarse scan's survivors) states its
own in ``refs/<family>.py``, built on the functions here.

The corpus codes are data the benchmark makes from the seed (``data.py``
encodes them with ``encode`` below), never codes the program made.
"""

from __future__ import annotations

import numpy as np

NEG = -np.inf


# ---------------------------------------------------------------------------
# codes
# ---------------------------------------------------------------------------


def affine(n_levels: int):
    """(a, beta) of the grid value ``a * code + beta`` (BEBR §3.2.1)."""
    return 2.0 ** (2 - n_levels), -(2.0 - 2.0 ** (1 - n_levels))


def code_values(codes, n_levels: int, xp=np):
    """Integer codes -> recurrent binary grid values."""
    a, beta = affine(n_levels)
    return codes.astype(xp.float32) * a + beta


def code_bits(codes: np.ndarray, n_levels: int) -> np.ndarray:
    """Integer codes [..., m] -> bits [..., n_levels, m] in {-1, +1}
    (level 0 is the most significant bit of the code)."""
    c = np.asarray(codes).astype(np.int64)
    shifts = np.arange(n_levels - 1, -1, -1)
    return ((c[..., None, :] >> shifts[:, None]) & 1) * 2.0 - 1.0


def bits_to_codes(bits, xp=np):
    """Bits [..., n_levels, m] in {-1, +1} -> integer codes [..., m] int8."""
    n = bits.shape[-2]
    weights = 2 ** xp.arange(n - 1, -1, -1)
    zo = ((bits + 1) // 2).astype(xp.int32)
    return xp.sum(zo * weights[:, None], axis=-2).astype(xp.int8)


# ---------------------------------------------------------------------------
# the binarizer
# ---------------------------------------------------------------------------


def _mlp(p, s, x, dot, xp):
    h = dot(x, p["in"]["w"]) + p["in"]["b"]
    h = (h - s["bn_mean"]) / xp.sqrt(s["bn_var"] + 1e-5)
    h = xp.maximum(h * p["bn_scale"] + p["bn_bias"], 0.0)
    return dot(h, p["out"]["w"]) + p["out"]["b"]


def _unit(x, xp):
    return x / xp.sqrt(xp.sum(x * x, axis=-1, keepdims=True) + 1e-12)


def encode(params, state, x, *, precision="highest"):
    """Float embeddings [B, d] -> integer codes [B, m] on the device.

    The forward pass of the recurrent binarizer in jax.numpy at the given
    matmul precision. ``data.py`` encodes the corpus with it, and the
    lower-precision control of ``control.py`` calls it at ``"high"``.
    """
    import jax
    import jax.numpy as jnp

    def dot(a, w):
        return jnp.dot(a, w, precision=precision)

    n = len(params["W"])
    f = _unit(x, jnp)
    h = _mlp(params["W"][0], state["W"][0], x, dot, jnp)
    b = jnp.where(h > 0, 1.0, -1.0)
    levels, acc = [b], b
    for t in range(n - 1):
        rec = _unit(_mlp(params["R"][t], state["R"][t], acc, dot, jnp), jnp)
        h = _mlp(params["W"][t + 1], state["W"][t + 1], f - rec, dot, jnp)
        r = jnp.where(h > 0, 1.0, -1.0)
        levels.append(r)
        acc = acc + 2.0 ** -(t + 1) * r
    return bits_to_codes(jnp.stack(levels, axis=-2).astype(jnp.int32), jnp)


def teacher_forced_preactivations(params, state, x, bits) -> np.ndarray:
    """Pre-sign activations [B, n_levels, m] of every level, in float64,
    with the reconstruction fed by the bits that were served (as a served
    model's tokens are fed back to its reference)."""
    p64 = _to64(params)
    s64 = _to64(state)

    def dot(a, w):
        return a @ w

    x = np.asarray(x, np.float64)
    n = len(p64["W"])
    f = _unit(x, np)
    hs = [_mlp(p64["W"][0], s64["W"][0], x, dot, np)]
    acc = bits[:, 0]
    for t in range(n - 1):
        rec = _unit(_mlp(p64["R"][t], s64["R"][t], acc, dot, np), np)
        hs.append(_mlp(p64["W"][t + 1], s64["W"][t + 1], f - rec, dot, np))
        acc = acc + 2.0 ** -(t + 1) * bits[:, t + 1]
    return np.stack(hs, axis=1)


def _to64(tree):
    if isinstance(tree, dict):
        return {k: _to64(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_to64(v) for v in tree]
    return np.asarray(tree, np.float64)


def code_margin(params, state, queries, served_codes, n_levels: int) -> float:
    """The widest margin by which a served code bit lies on the wrong side
    of zero: max over bits whose sign disagrees with the reference's
    teacher-forced pre-activation of |pre-activation| / RMS of its level.
    0 when every bit agrees."""
    bits = code_bits(served_codes, n_levels)
    h = teacher_forced_preactivations(params, state, queries, bits)
    rms = np.sqrt(np.mean(h * h, axis=(0, 2), keepdims=True)) + 1e-30
    wrong = np.where(h > 0, 1.0, -1.0) != bits
    if not wrong.any():
        return 0.0
    return float(np.max((np.abs(h) / rms)[wrong]))


# ---------------------------------------------------------------------------
# exact search
# ---------------------------------------------------------------------------


def _top_k(s, k: int, block: int = 1024):
    """Exact top-k along the last axis in two stages: the top-k of every
    ``block`` columns, then the top-k of those (every member of the top-k
    is in its block's top-k). Ties keep the lower column."""
    import jax
    import jax.numpy as jnp

    rows, n = s.shape
    if n <= block or n % block or k > block:
        return jax.lax.top_k(s, k)
    v, i = jax.lax.top_k(s.reshape(rows, n // block, block), k)
    i = i + (jnp.arange(n // block) * block)[None, :, None]
    v, j = jax.lax.top_k(v.reshape(rows, -1), k)
    return v, jnp.take_along_axis(i.reshape(rows, -1), j, axis=1)


def _round_to_bfloat16(x):
    """float32 -> nearest bfloat16 value (ties to even), in float32.

    Done on the bits: a convert to bfloat16 and back may be dropped by
    the compiler, which is allowed to keep excess precision."""
    import jax
    import jax.numpy as jnp

    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    one, low, high = (jnp.uint32(v) for v in (1, 0x7FFF, 0xFFFF0000))
    b = (b + low + ((b >> 16) & one)) & high
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def exact_search(q_codes, served_ids, corpus_chunks, *, n_levels, k,
                 n_docs, block_q=128, round_bf16=False):
    """Exact SDC scores of every query against the whole corpus.

    ``corpus_chunks`` yields (start row, codes [rows, m] on the device).
    Returns (top-k scores [Q, k] descending, top-k ids [Q, k], the exact
    score of each served id [Q, k'] with -inf where the id is not a row).
    ``round_bf16`` rounds the scores to bfloat16 before ranking (the
    lower-precision control).
    """
    import jax
    import jax.numpy as jnp

    q_codes = np.asarray(q_codes)
    served_ids = np.asarray(served_ids)
    Q = q_codes.shape[0]
    pad = (-Q) % block_q
    qv = code_values(np.pad(q_codes, ((0, pad), (0, 0))), n_levels)
    sid = np.pad(served_ids, ((0, pad), (0, 0)), constant_values=-1)
    blocks = [(jnp.asarray(qv[i:i + block_q]),
               jnp.asarray(sid[i:i + block_q].astype(np.int32)))
              for i in range(0, Q + pad, block_q)]

    @jax.jit
    def block_scores(qv_b, sid_b, dv, start):
        # Grid values are multiples of 1/8 below 2 in magnitude: exact in
        # bfloat16, so the MXU sums them exactly in float32.
        dot = jnp.dot(qv_b.astype(jnp.bfloat16), dv.astype(jnp.bfloat16).T,
                      preferred_element_type=jnp.float32)
        norm = jnp.sqrt(jnp.sum(dv * dv, axis=-1))
        s = dot / norm[None, :]
        if round_bf16:
            s = _round_to_bfloat16(s)
        top_v, top_i = _top_k(s, k)
        local = sid_b - start
        inside = (local >= 0) & (local < dv.shape[0])
        got = jnp.take_along_axis(s, jnp.clip(local, 0, dv.shape[0] - 1),
                                  axis=1)
        return top_v, top_i + start, jnp.where(inside, got, -jnp.inf)

    top_v = [np.full((block_q, 0), NEG, np.float32) for _ in blocks]
    top_i = [np.zeros((block_q, 0), np.int64) for _ in blocks]
    served = [np.full((block_q, sid.shape[1]), NEG, np.float32)
              for _ in blocks]
    for start, codes in corpus_chunks:
        dv = code_values(codes, n_levels, jnp)
        outs = [block_scores(qb, sb, dv, jnp.int32(start))
                for qb, sb in blocks]
        for j, (v, i, g) in enumerate(jax.device_get(outs)):
            top_v[j] = np.concatenate([top_v[j], v], 1)
            top_i[j] = np.concatenate([top_i[j], i], 1)
            served[j] = np.maximum(served[j], g)
    tv = np.concatenate(top_v)[:Q]
    ti = np.concatenate(top_i)[:Q]
    # Highest score first, lowest id first among equal scores.
    order = np.lexsort((ti, -tv), axis=1)[:, :k]
    tv = np.take_along_axis(tv, order, 1)
    ti = np.take_along_axis(ti, order, 1)
    sv = np.concatenate(served)[:Q]
    sv[(served_ids < 0) | (served_ids >= n_docs)] = NEG
    return tv, ti, sv


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------


def compare(served_scores, served_ids, ref_top, ref_served, *,
            rank: bool, order: bool, promised=None):
    """The numbers compared with their limits, from one set of answers.

    served_scores / served_ids [Q, k]: what the program answered.
    ref_top [Q, k]: the reference's exact top-k scores, descending.
    ref_served [Q, k]: the reference's exact score of each served id.
    rank: the configuration promises a top-k (``rank_gap``); order: it
    promises exact scores in descending order (``order_gap``).
    promised [Q, k]: the exact scores, descending, of the top-k that the
    index family promises (its ``refs/<family>.py``), where that is not
    the whole corpus's; ``rank_gap`` is then taken against it.

    Returns {name: value}; every value is a share of the query's best
    reference score, so it does not depend on the scale of the scores.
    An answer that is missing or names no row reads 1.0 or more.
    """
    served_scores = np.asarray(served_scores, np.float64)
    scale = np.maximum(np.abs(ref_top[:, :1]), 1e-6)
    bad = ~np.isfinite(ref_served)
    dup = np.zeros_like(bad)
    ids = np.asarray(served_ids)
    srt = np.sort(ids, axis=1)
    dup[:, 1:] = srt[:, 1:] == srt[:, :-1]
    # Wrong, missing and repeated ids all count as a whole score off.
    err = np.where(bad | dup, 1.0,
                   np.abs(served_scores - np.where(bad, 0.0, ref_served))
                   / scale)
    out = {"score_err": float(err.max())}
    rs = np.where(bad, -np.inf, ref_served)
    if rank:
        if promised is None:
            # Nothing lies above the whole corpus's top-k: an answer can
            # only fall short of it.
            gap = (ref_top[:, -1] - np.min(rs, axis=1)) / scale[:, 0]
        else:
            # A family's top-k is taken over part of the corpus (the
            # survivors of a coarse scan): an answer from outside that
            # part breaks the promise as much as one below it, so the
            # served scores must be the promised ones, rank by rank.
            got = -np.sort(-rs, axis=1)
            want = np.asarray(promised, np.float64)
            off = np.where(got == want, 0.0, np.abs(got - want))
            gap = np.max(off, axis=1) / scale[:, 0]
        gap = np.where(np.isfinite(gap), gap, 1.0)
        out["rank_gap"] = max(0.0, float(np.max(gap)))
    if order:
        step = rs[:, 1:] - rs[:, :-1]
        step = np.where(np.isfinite(step), step, 1.0)
        out["order_gap"] = max(0.0, float(np.max(step / scale)))
    return out
