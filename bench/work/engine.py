"""Least work of one request to the sharded flat engine: the flat search
of the whole corpus, spread over the configuration's chips, plus the
exchange of every leaf's top-k (ids and scores, 8 bytes an entry)."""

from __future__ import annotations

from bench.work import flat


def observe(search, q_codes, cfg):
    return None


def least(cfg: dict, q_codes, obs) -> dict:
    out = flat.least(cfg, q_codes, obs)
    out["bytes"] += q_codes.shape[0] * cfg["k"] * 8.0 * cfg["chips"]
    return out
