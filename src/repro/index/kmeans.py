"""jit-compiled k-means (Lloyd) with k-means++ seeding.

Used as the IVF coarse quantiser (paper §3.3.3: "the coarse layer quantizes
embedding vectors into the coarse cluster typically through the K-means
algorithm"). Operates on float vectors or on recurrent-binary grid values.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


def _pairwise_sqdist(x: jax.Array, c: jax.Array) -> jax.Array:
    """[N, D] x [K, D] -> [N, K] squared euclidean distances."""
    x2 = jnp.sum(x * x, -1, keepdims=True)
    c2 = jnp.sum(c * c, -1)
    return x2 + c2[None, :] - 2.0 * (x @ c.T)


def _nearest(x: jax.Array, c: jax.Array, chunk: int = 65536) -> jax.Array:
    """[N] index of each row's nearest centroid, over row chunks, so the
    [N, K] distance matrix never exists whole (a 2M x 1024 corpus would
    need 8 GiB for it)."""
    n, d = x.shape
    if n <= chunk:
        return jnp.argmin(_pairwise_sqdist(x, c), axis=-1)
    xp = jnp.pad(x, ((0, (-n) % chunk), (0, 0))).reshape(-1, chunk, d)
    nearest = jax.lax.map(
        lambda xb: jnp.argmin(_pairwise_sqdist(xb, c), axis=-1), xp
    )
    return nearest.reshape(-1)[:n]


def kmeans_pp_init(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    """k-means++ seeding (sequential, scan over k picks)."""
    n = x.shape[0]
    k0, key = jax.random.split(key)
    first = jax.random.randint(k0, (), 0, n)
    centroids0 = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    d0 = jnp.sum((x - x[first]) ** 2, -1)

    def pick(carry, i):
        cents, mind, key = carry
        key, kk = jax.random.split(key)
        probs = mind / (jnp.sum(mind) + 1e-12)
        idx = jax.random.choice(kk, n, p=probs)
        cents = cents.at[i].set(x[idx])
        mind = jnp.minimum(mind, jnp.sum((x - x[idx]) ** 2, -1))
        return (cents, mind, key), None

    (cents, _, _), _ = jax.lax.scan(
        pick, (centroids0, d0, key), jnp.arange(1, k)
    )
    return cents


@functools.partial(jax.jit, static_argnames=("k", "iters", "pp_init"))
def kmeans(
    key: jax.Array, x: jax.Array, *, k: int, iters: int = 25, pp_init: bool = True
) -> Tuple[jax.Array, jax.Array]:
    """Returns (centroids [K, D], assignments [N])."""
    if pp_init:
        cents = kmeans_pp_init(key, x, k)
    else:
        idx = jax.random.choice(key, x.shape[0], (k,), replace=False)
        cents = x[idx]

    def step(cents, _):
        assign = _nearest(x, cents)  # [N]
        sums = jax.ops.segment_sum(x, assign, num_segments=k)
        counts = jax.ops.segment_sum(
            jnp.ones((x.shape[0],), x.dtype), assign, num_segments=k
        )
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        # Keep empty clusters where they were (avoids NaN drift).
        new = jnp.where(counts[:, None] > 0, new, cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    assign = _nearest(x, cents)
    return cents, assign
