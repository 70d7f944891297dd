"""Least work of one two-tier (bi-granular) search request.

A two-tier index (BEBR arXiv:2302.08714 §3.2.1, a level prefix of a code
is a code at fewer bits; Xiao et al. arXiv:2201.05409) scans the whole
corpus at ``coarse_levels`` of its ``n_levels`` levels for ``k_coarse``
survivors a query, and reranks those at full levels.

Coarse part: every query against every document, 2 * Q * N * m int8
operations; every document's coarse code, ``coarse_levels`` bits a
dimension, and its 4-byte reciprocal norm read once per request, however
wide the program stores the coarse tier. Rerank part: every query
against its own survivors, 2 * Q * min(k', N) * m int8 operations; each
survivor's full-level code, ``n_levels`` bits a dimension, and its norm
read once per query. The encoder's flops are the family's whole float
work. ``parts`` keeps the two parts apart, so that a kernel's roofline
can be read against its own part.
"""

from __future__ import annotations

from bench.work.common import encoder_flops


def observe(search, q_codes, cfg):
    """Nothing beyond the configuration's shapes is needed."""
    return None


def least(cfg: dict, q_codes, obs) -> dict:
    q, m = q_codes.shape
    n = cfg["n_docs"]
    levels = cfg["binarizer"]["n_levels"]
    p = cfg["index"]["params"]
    survivors = min(p["k_coarse"], n)
    parts = {
        "coarse": {"int8_ops": 2.0 * q * n * m,
                   "bytes": n * (-(-m * p["coarse_levels"] // 8) + 4.0)},
        "rerank": {"int8_ops": 2.0 * q * survivors * m,
                   "bytes": q * survivors * (-(-m * levels // 8) + 4.0)},
    }
    return {
        "int8_ops": sum(w["int8_ops"] for w in parts.values()),
        "flops": encoder_flops(cfg, q),
        "bytes": sum(w["bytes"] for w in parts.values()),
        "parts": parts,
    }
