"""What a two-tier flat index promises, as a family's own reference.

A two-tier index (BEBR arXiv:2302.08714 §3.2.1, a level
prefix of a code is a code at fewer bits; Xiao et al. arXiv:2201.05409)
scans the corpus at ``coarse_levels`` levels for ``k_coarse`` survivors
and reranks them at full levels: its answer is the full-level top-k of
its own survivors, not of the whole corpus.
"""

from __future__ import annotations

import numpy as np

from bench import reference


def expected(cfg, q_codes, corpus, *, round_bf16=False):
    """(scores [Q, k], ids [Q, k]): the full-level top-k of each query's
    coarse top-``k_coarse``, highest score first, lowest id first among
    equal scores (a flat scan's order)."""
    n = cfg["binarizer"]["n_levels"]
    p = cfg["index"]["params"]
    levels, k_coarse, k = p["coarse_levels"], p["k_coarse"], cfg["k"]
    shift = n - levels
    q_codes = np.asarray(q_codes)
    none = -np.ones((q_codes.shape[0], 1), np.int64)
    # The coarse code is the level prefix: a right shift of the full one.
    coarse = ((start, codes >> shift) for start, codes in corpus.chunks())
    _, survivors, _ = reference.exact_search(
        q_codes >> shift, none, coarse, n_levels=levels, k=k_coarse,
        n_docs=cfg["n_docs"], round_bf16=round_bf16)
    _, _, fine = reference.exact_search(
        q_codes, survivors, corpus.chunks(), n_levels=n, k=k,
        n_docs=cfg["n_docs"], round_bf16=round_bf16)
    order = np.lexsort((survivors, -fine), axis=1)[:, :k]
    return (np.take_along_axis(fine, order, 1),
            np.take_along_axis(survivors, order, 1))
