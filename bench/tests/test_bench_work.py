"""The least work of one request, against counts made by hand."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import registry  # noqa: E402

WEB = {"input_dim": 256, "hidden_dim": 512, "code_dim": 128, "n_levels": 4}
# 4 binarization MLPs 256-512-128 and 3 reconstruction MLPs 128-512-256.
WEB_ENCODER_FLOPS_PER_QUERY = 2 * 7 * (256 * 512 + 512 * 128)


def _cfg(family, packed, n_docs=2**23, chips=1, **params):
    return {"binarizer": WEB, "n_docs": n_docs, "k": 10, "chips": chips,
            "index": {"family": family, "params": dict(packed=packed,
                                                       **params)}}


@pytest.mark.parametrize("packed,row", [(True, 64 + 4), (False, 128 + 4)])
def test_flat_reads_every_row_once_and_scores_every_pair(packed, row):
    cfg = _cfg("flat", packed)
    w = registry.work("flat").least(cfg, np.zeros((8, 128), np.int8), None)
    assert w["int8_ops"] == 2 * 8 * 2**23 * 128
    assert w["bytes"] == 2**23 * row
    assert w["flops"] == 8 * WEB_ENCODER_FLOPS_PER_QUERY


def test_the_engine_adds_the_exchange_of_every_leaf_top_k():
    cfg = _cfg("engine", True, chips=4)
    q = np.zeros((8, 128), np.int8)
    flat = registry.work("flat").least(cfg, q, None)
    eng = registry.work("engine").least(cfg, q, None)
    assert eng["int8_ops"] == flat["int8_ops"]
    assert eng["bytes"] == flat["bytes"] + 8 * 10 * 8 * 4


def test_ivf_counts_the_live_entries_of_the_probed_lists():
    b = {"input_dim": 4, "hidden_dim": 8, "code_dim": 2, "n_levels": 4}
    cfg = {"binarizer": b, "n_docs": 100, "k": 1, "chips": 1,
           "index": {"family": "ivf",
                     "params": {"nlist": 4, "nprobe": 2, "packed": False}}}
    obs = {"centroids": np.array([[1, 0], [0, 1], [-1, 0], [0, -1]],
                                 np.float32),
           "occupancy": np.array([10, 20, 30, 40])}
    # Grid values 0.25 * code - 1.875: query 0 is (1.875, 0.125) and
    # probes lists 0 and 1; query 1 is (-0.125, -1.875): lists 3 and 2.
    q = np.array([[15, 8], [7, 0]], np.int8)
    ivf = registry.work("ivf")
    assert ivf.probes(cfg, q, obs).tolist() == [[0, 1], [3, 2]]
    w = ivf.least(cfg, q, obs)
    assert w["int8_ops"] == 2 * 2 * (10 + 20 + 40 + 30)
    encoder = 2 * 2 * 7 * (4 * 8 + 8 * 2)
    assert w["flops"] == encoder + 2 * 2 * 4 * 2
    # Each probed list once per request: code (2 B), norm and id (8 B).
    assert w["bytes"] == 100 * (2 + 8) + 4 * 2 * 4


def test_a_list_probed_by_many_queries_is_read_once():
    b = {"input_dim": 4, "hidden_dim": 8, "code_dim": 2, "n_levels": 4}
    cfg = {"binarizer": b, "n_docs": 100, "k": 1, "chips": 1,
           "index": {"family": "ivf",
                     "params": {"nlist": 2, "nprobe": 1, "packed": True}}}
    obs = {"centroids": np.array([[1, 0], [-1, 0]], np.float32),
           "occupancy": np.array([7, 5])}
    q = np.array([[15, 8]] * 3, np.int8)
    w = registry.work("ivf").least(cfg, q, obs)
    assert w["int8_ops"] == 2 * 2 * 7 * 3
    assert w["bytes"] == 7 * (1 + 8) + 2 * 2 * 4


@pytest.mark.parametrize("packed", [True, False])
def test_bigranular_reads_coarse_bits_and_its_survivors_full_rows(packed):
    # The web product's two tiers: 2 of 4 levels scanned for 100
    # survivors a query, whatever width a program stores the coarse tier.
    cfg = _cfg("bigranular", packed, n_docs=2**24, coarse_levels=2,
               k_coarse=100)
    w = registry.work("bigranular").least(cfg, np.zeros((8, 128), np.int8),
                                          None)
    coarse, rerank = w["parts"]["coarse"], w["parts"]["rerank"]
    assert coarse == {"int8_ops": 2 * 8 * 2**24 * 128,
                      "bytes": 2**24 * (32 + 4)}
    assert rerank == {"int8_ops": 2 * 8 * 100 * 128,
                      "bytes": 8 * 100 * (64 + 4)}
    assert w["int8_ops"] == coarse["int8_ops"] + rerank["int8_ops"]
    assert w["bytes"] == coarse["bytes"] + rerank["bytes"] == 604_034_176
    assert w["flops"] == 8 * WEB_ENCODER_FLOPS_PER_QUERY
    assert w["bytes"] / 819e9 == pytest.approx(0.7375e-3, rel=1e-3)


def test_bigranular_reranks_no_more_survivors_than_documents():
    cfg = _cfg("bigranular", True, n_docs=50, coarse_levels=3, k_coarse=100)
    w = registry.work("bigranular").least(cfg, np.zeros((2, 128), np.int8),
                                          None)
    assert w["parts"]["coarse"]["bytes"] == 50 * (48 + 4)
    assert w["parts"]["rerank"] == {"int8_ops": 2 * 2 * 50 * 128,
                                    "bytes": 2 * 50 * (64 + 4)}
