"""Index layer: k-means, IVF, HNSW-lite, flat parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BinarizerConfig, binarize, init_binarizer, pack_codes
from repro.data.synthetic import clustered_corpus
from repro.index import ivf as ivf_lib
from repro.index.flat import FlatBitwise, FlatFloat, FlatSDC
from repro.index.hnsw_lite import build_hnsw, search_hnsw
from repro.index.kmeans import kmeans
from repro.kernels.sdc import ref as R


def _codes_from_corpus(n=2000, q=32, dim=64, n_levels=4, seed=0):
    docs, queries, gt = clustered_corpus(seed, n, q, dim, n_clusters=16)
    cfg = BinarizerConfig(input_dim=dim, code_dim=dim, n_levels=n_levels,
                          hidden_dim=0)
    p, s = init_binarizer(jax.random.PRNGKey(seed), cfg)
    bits_d, _, _ = binarize(p, s, jnp.asarray(docs), cfg)
    bits_q, _, _ = binarize(p, s, jnp.asarray(queries), cfg)
    return pack_codes(bits_d), pack_codes(bits_q), gt


def test_kmeans_reduces_quantisation_error():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (512, 8))
    c1, a1 = kmeans(key, x, k=16, iters=1, pp_init=False)
    c25, a25 = kmeans(key, x, k=16, iters=25, pp_init=False)

    def err(c, a):
        return float(jnp.mean(jnp.sum((x - c[a]) ** 2, -1)))

    assert err(c25, a25) <= err(c1, a1)
    assert int(a25.max()) < 16


def test_kmeans_chunked_assignment_equals_whole():
    """Row-chunked nearest-centroid search (a ragged last chunk included)
    equals the argmin over the whole distance matrix."""
    from repro.index.kmeans import _nearest, _pairwise_sqdist

    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (100, 8))
    c = jax.random.normal(jax.random.fold_in(key, 1), (16, 8))
    want = jnp.argmin(_pairwise_sqdist(x, c), axis=-1)
    np.testing.assert_array_equal(np.asarray(_nearest(x, c, chunk=32)),
                                  np.asarray(want))


def test_ivf_exact_when_probing_all_lists():
    d_codes, q_codes, _ = _codes_from_corpus()
    index = ivf_lib.build_ivf(jax.random.PRNGKey(1), d_codes, n_levels=4,
                              nlist=8)
    vals, ids = ivf_lib.search(index, q_codes, nprobe=8, k=10)
    ev, ei = jax.lax.top_k(R.sdc_ref(q_codes, d_codes, 4), 10)
    # probing every list must equal exhaustive SDC search
    overlap = np.mean([
        len(set(np.asarray(ids[i])) & set(np.asarray(ei[i]))) / 10
        for i in range(ids.shape[0])
    ])
    assert overlap > 0.99


def test_ivf_partial_probe_recall_reasonable():
    d_codes, q_codes, _ = _codes_from_corpus()
    index = ivf_lib.build_ivf(jax.random.PRNGKey(1), d_codes, n_levels=4,
                              nlist=32)
    _, ids = ivf_lib.search(index, q_codes, nprobe=8, k=10)
    ev, ei = jax.lax.top_k(R.sdc_ref(q_codes, d_codes, 4), 10)
    overlap = np.mean([
        len(set(np.asarray(ids[i])) & set(np.asarray(ei[i]))) / 10
        for i in range(ids.shape[0])
    ])
    assert overlap > 0.5  # clustered corpus => coarse layer is informative


def test_flat_sdc_equals_flat_bitwise_ranking():
    d_codes, q_codes, _ = _codes_from_corpus(n=500, q=8)
    sdc = FlatSDC.build(d_codes, 4, backend="interpret")
    bitw = FlatBitwise.build(d_codes, 4)
    _, ids_s = sdc.search(q_codes, 5)
    _, ids_b = bitw.search(q_codes, 5)
    # bitwise is unnormalised (no doc-norm divide) => top-1 usually agrees
    # on clustered data; require strong overlap rather than equality.
    overlap = np.mean([
        len(set(np.asarray(ids_s[i])) & set(np.asarray(ids_b[i]))) / 5
        for i in range(ids_s.shape[0])
    ])
    assert overlap > 0.5


def test_index_bytes_compression_vs_float():
    docs, _, _ = clustered_corpus(0, 1000, 8, 256)
    f = FlatFloat.build(jnp.asarray(docs))
    cfg = BinarizerConfig(input_dim=256, code_dim=128, n_levels=4, hidden_dim=0)
    p, s = init_binarizer(jax.random.PRNGKey(0), cfg)
    bits, _, _ = binarize(p, s, jnp.asarray(docs), cfg)
    sdc = FlatSDC.build(pack_codes(bits), 4, backend="interpret")
    # 256 f32 dims = 8192 bits -> 512 bits + norm: ~14x smaller
    assert sdc.nbytes() < f.nbytes() / 10


def test_hnsw_recall_vs_exact():
    d_codes, q_codes, _ = _codes_from_corpus(n=600, q=16)
    inv = np.asarray(R.doc_inv_norms(d_codes, 4))
    index = build_hnsw(np.asarray(d_codes), inv, n_levels=4, M=12,
                       ef_construction=48)
    ev, ei = jax.lax.top_k(R.sdc_ref(q_codes, d_codes, 4), 10)
    recs = []
    for i in range(q_codes.shape[0]):
        _, ids = search_hnsw(index, np.asarray(q_codes[i]), k=10, ef=64)
        recs.append(len(set(ids.tolist()) & set(np.asarray(ei[i]).tolist())) / 10)
    assert float(np.mean(recs)) > 0.6
