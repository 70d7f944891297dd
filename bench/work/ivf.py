"""Least work of one IVF search request.

Coarse layer: every query against every centroid (2 * Q * nlist * m
flops, and the float centroid table read once). Fine layer: every query
against the live entries of the lists it probes (2 * m int8 operations
an entry), and each probed list's live entries read once per request
(code, reciprocal norm and 4-byte id), however many of the request's
queries probe it. Padding slots are not work.

Which lists a query probes is read from the index under test: its
centroids and list occupancy (``observe``), with the query's served code.
"""

from __future__ import annotations

import numpy as np

from bench.reference import code_values
from bench.work.common import encoder_flops, row_bytes


def observe(search, q_codes, cfg):
    """Centroids [nlist, m] and live entries per list, from the arrays the
    served search closes over."""
    import jax

    nlist = cfg["index"]["params"]["nlist"]
    m = cfg["binarizer"]["code_dim"]
    cents = occ = None
    for c in jax.make_jaxpr(search)(q_codes).consts:
        shape = getattr(c, "shape", ())
        if shape == (nlist, m) and c.dtype == np.float32:
            cents = np.asarray(c)
        elif len(shape) == 2 and shape[0] == nlist and c.dtype == np.int32:
            occ = np.asarray((np.asarray(c) >= 0).sum(axis=1))
    if cents is None or occ is None:
        raise ValueError("IVF search holds no [nlist, m] centroids or "
                         "[nlist, L] list ids")
    return {"centroids": cents, "occupancy": occ}


def probes(cfg: dict, q_codes, obs) -> np.ndarray:
    """[Q, nprobe] lists each query probes: its top coarse scores."""
    nprobe = cfg["index"]["params"]["nprobe"]
    vq = code_values(np.asarray(q_codes), cfg["binarizer"]["n_levels"])
    coarse = vq.astype(np.float64) @ obs["centroids"].T.astype(np.float64)
    return np.argsort(-coarse, axis=1, kind="stable")[:, :nprobe]


def least(cfg: dict, q_codes, obs) -> dict:
    q, m = q_codes.shape
    nlist = cfg["index"]["params"]["nlist"]
    p = probes(cfg, q_codes, obs)
    occ = obs["occupancy"]
    return {
        "int8_ops": 2.0 * m * float(occ[p].sum()),
        "flops": encoder_flops(cfg, q) + 2.0 * q * nlist * m,
        "bytes": float(occ[np.unique(p)].sum()) * (row_bytes(cfg) + 8.0)
        + nlist * m * 4.0,
    }
