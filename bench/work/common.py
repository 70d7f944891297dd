"""Work the algorithm needs, shared by every index family: the query
encoder's matmuls and the storage of one document."""

from __future__ import annotations


def encoder_flops(cfg: dict, batch: int) -> float:
    """Multiply-add flops of the recurrent binarizer for ``batch`` queries:
    n_levels binarization MLPs (d -> h -> m) and n_levels - 1
    reconstruction MLPs (m -> h -> d)."""
    b = cfg["binarizer"]
    d, h, m, n = b["input_dim"], b["hidden_dim"], b["code_dim"], b["n_levels"]
    per_query = n * (d * h + h * m) + (n - 1) * (m * h + h * d)
    return 2.0 * batch * per_query


def row_bytes(cfg: dict) -> int:
    """Bytes of one stored document code: 4 bits a dimension when the
    configuration packs codes, a byte a dimension when it does not."""
    m = cfg["binarizer"]["code_dim"]
    return m // 2 if cfg["index"]["params"].get("packed") else m
