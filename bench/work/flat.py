"""Least work of one exhaustive SDC search request.

Every document is scored against every query: 2 * Q * N * m int8
operations; every stored code and its 4-byte reciprocal norm is read
once per request, however many queries it carries.
"""

from __future__ import annotations

from bench.work.common import encoder_flops, row_bytes


def observe(search, q_codes, cfg):
    """Nothing beyond the configuration's shapes is needed."""
    return None


def least(cfg: dict, q_codes, obs) -> dict:
    q, m = q_codes.shape
    n = cfg["n_docs"]
    return {
        "int8_ops": 2.0 * q * n * m,
        "flops": encoder_flops(cfg, q),
        "bytes": n * (row_bytes(cfg) + 4.0),
    }
