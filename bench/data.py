"""Inputs made from ``--seed``: binarizer weights, the corpus, queries.

Everything is drawn on the device. The corpus is the generative model of
the web product (``configs/*.json`` ``product``): unit vectors around a
fixed set of cluster centres, with an anisotropic scale and a random
rotation, as ``chip_smoke.web_corpus_on_device`` draws it. Each chunk of
rows is drawn from its own key and encoded at once, so no float corpus
ever exists whole, and the same chunk can be drawn again after the window
for the reference. Queries are fresh draws of the same model plus query
noise: new embeddings of the corpus's distribution, not copies of rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


def prng_key(seed: int, salt: int = 0):
    """A jax PRNG key for any non-negative seed: the low and high 32 bits
    are folded in separately, so seeds past 2**32 stay distinct."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), salt)


def binarizer_weights(seed: int, b: dict):
    """Seeded, untrained recurrent-binarizer weights, in the pytree layout
    the served encoder takes (``params["W"][t]``, ``params["R"][t]``,
    eval batch-norm statistics in ``state``), float32 on the device.

    Drawn in one jitted call. Weights are He-scaled normals; biases and
    the batch-norm statistics are random too, so every term of the
    forward pass takes part in the comparison.
    """
    d, h, m, n = b["input_dim"], b["hidden_dim"], b["code_dim"], b["n_levels"]
    blocks = [("W", d, m, 1.0)] * n + [("R", m, d, 1.33 * m)] * (n - 1)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(blocks))
        params, state = {"W": [], "R": []}, {"W": [], "R": []}
        for kk, (kind, d_in, d_out, in_sq) in zip(keys, blocks):
            k = jax.random.split(kk, 8)
            var_h = 2.0 / d_in * in_sq
            params[kind].append({
                "in": {"w": jax.random.normal(k[0], (d_in, h)) * jnp.sqrt(2.0 / d_in),
                       "b": 0.01 * jax.random.normal(k[1], (h,))},
                "bn_scale": jax.random.uniform(k[2], (h,), minval=0.5, maxval=1.5),
                "bn_bias": 0.1 * jax.random.normal(k[3], (h,)),
                "out": {"w": jax.random.normal(k[4], (h, d_out)) * jnp.sqrt(2.0 / h),
                        "b": 0.01 * jax.random.normal(k[5], (d_out,))},
            })
            state[kind].append({
                "bn_mean": 0.1 * jnp.sqrt(var_h) * jax.random.normal(k[6], (h,)),
                "bn_var": var_h * jax.random.uniform(k[7], (h,), minval=0.5,
                                                     maxval=2.0),
            })
        return params, state

    return draw(prng_key(seed, 1))


class Corpus:
    """The seeded corpus and query pool of one configuration.

    ``codes(i)`` gives rows [i * chunk, (i + 1) * chunk) as int8 codes on
    the device, the same on every call; ``chunks()`` walks them all. The
    jitted draws take every array as an argument (a closed-over array
    would be baked into the program, and each seed would compile anew).
    """

    def __init__(self, seed: int, cfg: dict, params, state):
        prod = cfg["product"]
        self.n_docs = int(cfg["n_docs"])
        self.chunk = min(int(cfg["corpus_chunk"]), self.n_docs)
        if self.n_docs % self.chunk:
            raise ValueError(f"n_docs {self.n_docs} is not a multiple of "
                             f"corpus_chunk {self.chunk}")
        dim = prod["dim"]
        rng = np.random.default_rng(seed)
        self.model = (
            jnp.asarray(rng.normal(size=(prod["clusters"], dim)), jnp.float32),
            jnp.asarray(1.0 / (1.0 + np.arange(dim)) ** prod["spectrum"],
                        jnp.float32),
            jnp.asarray(np.linalg.qr(rng.normal(size=(dim, dim)))[0],
                        jnp.float32),
        )
        self.weights = (params, state)
        self.key = prng_key(seed, 2)
        self.static = dict(noise=float(prod["noise"]),
                           qnoise=float(prod["qnoise"]),
                           precision=cfg["binarizer"]["matmul_precision"])

    @property
    def n_chunks(self) -> int:
        return self.n_docs // self.chunk

    def codes(self, i: int):
        return _chunk_codes(jax.random.fold_in(self.key, i), self.model,
                            self.weights, rows=self.chunk,
                            noise=self.static["noise"],
                            precision=self.static["precision"])

    def chunks(self):
        for i in range(self.n_chunks):
            yield i * self.chunk, self.codes(i)

    def all_codes(self):
        """The whole corpus's codes [n_docs, m] int8 on one device."""
        return jnp.concatenate([c for _, c in self.chunks()], axis=0)

    def queries(self, n: int, seed: int) -> np.ndarray:
        """A pool of ``n`` query embeddings [n, dim] float32 (host), drawn
        from ``seed`` out of the corpus's distribution."""
        key = prng_key(seed, 3)
        return np.asarray(_draw(key, self.model, rows=n,
                                noise=self.static["noise"],
                                qnoise=self.static["qnoise"]))


@functools.partial(jax.jit, static_argnames=("rows", "noise", "qnoise"))
def _draw(key, model, *, rows, noise, qnoise=0.0):
    """``rows`` unit embeddings of the product's generative model."""
    centers, scales, rot = model
    ka, kn, kq = jax.random.split(key, 3)
    dim = centers.shape[1]
    assign = jax.random.randint(ka, (rows,), 0, centers.shape[0])
    raw = centers[assign] + noise * jax.random.normal(kn, (rows, dim))
    if qnoise:
        raw = raw + qnoise * jax.random.normal(kq, (rows, dim))
    x = jnp.dot(raw * scales, rot, precision=jax.lax.Precision.HIGHEST)
    return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-12)


@functools.partial(jax.jit, static_argnames=("rows", "noise", "precision"))
def _chunk_codes(key, model, weights, *, rows, noise, precision):
    x = _draw(key, model, rows=rows, noise=noise)
    return reference.encode(*weights, x, precision=precision)
