"""A deployment's check is data: what it promises comes from its
configuration's ``limits``, a family may bring its own reference
(``refs/<family>.py``), and a configuration says where its snapshot
lives (``"snapshot"``). A served search that is not one traceable
program fails the run with no result."""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, reference, registry  # noqa: E402

SMALL = dict(n_docs=4096, corpus_chunk=1024, check_queries=64)
SEED = 2**31 + 21


def _config(name, **over):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(SMALL, **over)
    return cfg


def _cell(cfg):
    return registry.Cell(name="test", chips=1, config=cfg, mix={},
                         end_to_end=[], per_layer=[])


def _inputs(cfg, n_queries=24, seed=SEED):
    """Weights, corpus, queries and their codes of a small configuration."""
    import jax.numpy as jnp

    params, state, corpus = harness.deployment(cfg)
    queries = corpus.queries(n_queries, seed)
    codes = np.asarray(reference.encode(params, state, jnp.asarray(queries)))
    return (params, state), corpus, queries, codes


def _exact(cfg, corpus, codes, k, chunks=None):
    none = -np.ones((codes.shape[0], 1), np.int64)
    top_v, top_i, _ = reference.exact_search(
        codes, none, corpus.chunks() if chunks is None else chunks,
        n_levels=cfg["binarizer"]["n_levels"], k=k, n_docs=cfg["n_docs"])
    return top_v, top_i


def _family_rule(cfg, weights, corpus, queries, codes, scores, ids):
    """The check as the family's name decided it: the exact top-k unless
    the family is IVF, which promises exact scores in descending order
    and is held to its recall."""
    n_levels = cfg["binarizer"]["n_levels"]
    margin = reference.code_margin(*weights, queries, codes, n_levels)
    top_v, top_i, ref_served = reference.exact_search(
        codes, ids, corpus.chunks(), n_levels=n_levels, k=cfg["k"],
        n_docs=cfg["n_docs"])
    served = np.asarray(scores, np.float64)
    scale = np.maximum(np.abs(top_v[:, :1]), 1e-6)
    bad = ~np.isfinite(ref_served)
    dup = np.zeros_like(bad)
    srt = np.sort(ids, axis=1)
    dup[:, 1:] = srt[:, 1:] == srt[:, :-1]
    err = np.where(bad | dup, 1.0,
                   np.abs(served - np.where(bad, 0.0, ref_served)) / scale)
    numbers = {"answer_err": max(margin, float(err.max()))}
    rs = np.where(bad, -np.inf, ref_served)
    if cfg["index"]["family"] != "ivf":
        gap = (top_v[:, -1] - np.min(rs, axis=1)) / scale[:, 0]
        gap = np.where(np.isfinite(gap), gap, 1.0)
        numbers["rank_gap"] = max(0.0, float(np.max(gap)))
    else:
        step = rs[:, 1:] - rs[:, :-1]
        step = np.where(np.isfinite(step), step, 1.0)
        numbers["order_gap"] = max(0.0, float(np.max(step / scale)))
    recall = float(np.mean([len(set(a) & set(b)) / len(a)
                            for a, b in zip(top_i.tolist(), ids.tolist())]))
    if cfg["index"]["family"] == "ivf":
        numbers["recall_miss"] = 1.0 - recall
    return numbers, recall


def _flawed_answers(cfg, corpus, codes):
    """Answers that read above 0 in every number: a later document in
    place of a top one, two answers out of order, and scores off by a
    little."""
    k = cfg["k"]
    top_v, top_i = _exact(cfg, corpus, codes, k + 3)
    ids = top_i[:, :k].copy()
    scores = top_v[:, :k].astype(np.float64).copy()
    ids[0, 3], scores[0, 3] = top_i[0, k + 2], top_v[0, k + 2]
    ids[1, [2, 3]] = ids[1, [3, 2]]
    scores[1, [2, 3]] = scores[1, [3, 2]]
    scores[2] += 1e-5
    return scores, ids


@pytest.mark.parametrize("name", ["web-flat", "web-flat-x4", "web-ivf"])
def test_the_promise_from_the_limits_reads_as_the_family_rule(name):
    cfg = _config(name)
    weights, corpus, queries, codes = _inputs(cfg)
    scores, ids = _flawed_answers(cfg, corpus, codes)
    want, want_recall = _family_rule(cfg, weights, corpus, queries, codes,
                                     scores, ids)
    got, recall, _ = harness.compare(_cell(cfg), weights, corpus, queries,
                                     codes, scores, ids)
    assert got == want
    assert recall == want_recall
    assert set(got) == set(cfg["limits"]) - {"unanswered"}
    assert all(v > 0 for v in got.values()), got


# A family whose promise is the exact top-k of the first half of the
# corpus: the whole corpus's top-k is wrong for it.
PLANTED = """
import numpy as np
from bench import reference


def expected(cfg, q_codes, corpus, *, round_bf16=False):
    half = cfg["n_docs"] // 2
    chunks = ((s, c) for s, c in corpus.chunks() if s < half)
    none = -np.ones((np.asarray(q_codes).shape[0], 1), np.int64)
    v, i, _ = reference.exact_search(
        q_codes, none, chunks, n_levels=cfg["binarizer"]["n_levels"],
        k=cfg["k"], n_docs=cfg["n_docs"], round_bf16=round_bf16)
    return v, i
"""


def _plant(monkeypatch, tmp_path, family, source):
    """A family reference under a temporary root, found by the registry."""
    path = tmp_path / "bench" / "refs" / (family + ".py")
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(source)
    find = registry.reference
    monkeypatch.setattr(registry, "reference",
                        lambda fam, root=str(tmp_path): find(fam, root=root))


def test_rank_gap_is_taken_against_a_family_reference(tmp_path, monkeypatch):
    assert registry.reference("planted", root=str(tmp_path)) is None
    for family in ("flat", "ivf", "engine"):
        assert registry.reference(family) is None
    _plant(monkeypatch, tmp_path, "planted", PLANTED)
    assert registry.reference("planted") is not None

    limits = {"answer_err": 1e-4, "rank_gap": 1e-4, "recall_miss": 5e-3,
              "unanswered": 0.0}
    cfg = _config("web-flat", limits=limits,
                  index={"family": "planted", "builder": "FlatBuilder",
                         "params": {}})
    weights, corpus, queries, codes = _inputs(cfg)
    half = [(s, c) for s, c in corpus.chunks() if s < cfg["n_docs"] // 2]
    for what, (scores, ids) in [
            ("whole corpus", _exact(cfg, corpus, codes, cfg["k"])),
            ("planted", _exact(cfg, corpus, codes, cfg["k"], iter(half)))]:
        got, recall, _ = harness.compare(_cell(cfg), weights, corpus,
                                         queries, codes, scores, ids)
        assert got["answer_err"] <= 1e-5, (what, got)
        if what == "whole corpus":
            # Right for the whole corpus, wrong for the family's promise;
            # the recall is the whole corpus's.
            assert got["rank_gap"] > 1e-2 and recall == 1.0, got
            assert got["recall_miss"] == 0.0
        else:
            assert got["rank_gap"] == 0.0 and recall < 1.0, got
            assert got["recall_miss"] == pytest.approx(1.0 - recall)

    # The control answers as the family promises, one precision lower.
    from bench import control

    out = control.control_numbers("web-flat.online", 5, dict(
        cfg, check_queries=256))
    assert out["numbers"]["recall_miss"] > 0.0
    assert out["fails"], out["numbers"]


BIGRANULAR = dict(
    SMALL, index={"family": "bigranular", "builder": "FlatBuilder",
                  "params": {"packed": True, "coarse_levels": 2,
                             "k_coarse": 32}},
    limits={"answer_err": 1e-4, "rank_gap": 1e-4, "unanswered": 0.0})


def _run(overrides, wrap=None, emit=None):
    return harness.run("web-flat.online", SEED, 0.5, False,
                       t_start=time.perf_counter(), backend="xla",
                       overrides=dict(overrides), wrap_search=wrap,
                       emit=emit or (lambda line: None))


@pytest.mark.parametrize("broken", [False, True])
def test_a_two_tier_run_is_checked_against_its_own_reference(broken):
    from bench.tests.test_bench_faults import altered_answer

    assert registry.reference("bigranular") is not None
    res = _run(BIGRANULAR, wrap=altered_answer if broken else None)
    assert res["correct"] is (not broken), res["checks"]
    if not broken:
        assert res["failed"] == 0
        assert res["checks"]["rank_gap"]["value"] == 0.0


class _Codes:
    """A corpus of given codes, yielded in chunks of rows as
    ``data.Corpus.chunks`` yields them."""

    def __init__(self, codes, rows):
        self.codes, self.rows = codes, rows

    def chunks(self):
        for start in range(0, len(self.codes), self.rows):
            yield start, self.codes[start:start + self.rows]


def _brute_two_tier(q_codes, docs, n_levels, coarse_levels, k_coarse, k):
    """Each query's coarse top-``k_coarse`` at ``coarse_levels`` levels,
    reranked at full levels, by sorting every score: highest score first,
    lowest id first among equal scores."""
    def scores(qc, dc, levels):
        a, beta = 2.0 ** (2 - levels), -(2.0 - 2.0 ** (1 - levels))
        qv, dv = qc * a + beta, dc * a + beta
        # Grid values are multiples of 1/8: the sums are exact, and the
        # float32 rounding is the reference's.
        norm = np.sqrt(np.sum(dv * dv, axis=1).astype(np.float32))
        return (qv @ dv.T).astype(np.float32) / norm

    q_codes, docs = q_codes.astype(np.int64), docs.astype(np.int64)
    shift = n_levels - coarse_levels
    coarse = scores(q_codes >> shift, docs >> shift, coarse_levels)
    out_v, out_i = [], []
    for q, row in zip(q_codes, coarse):
        survivors = sorted(range(len(docs)), key=lambda d: (-row[d], d))
        survivors = survivors[:k_coarse]
        fine = scores(q[None], docs[survivors], n_levels)[0]
        best = sorted(zip(survivors, fine), key=lambda t: (-t[1], t[0]))[:k]
        out_i.append([d for d, _ in best])
        out_v.append([v for _, v in best])
    return np.array(out_v, np.float32), np.array(out_i)


def test_the_bigranular_reference_is_a_brute_force_two_tier_ranking():
    rng = np.random.default_rng(SEED)
    docs = rng.integers(0, 16, (96, 8)).astype(np.int8)
    # Every document twice, the copy at a higher id: ties at every rank.
    docs[48:] = docs[:48]
    q_codes = rng.integers(0, 16, (6, 8)).astype(np.int8)
    cfg = {"binarizer": {"n_levels": 4}, "k": 7, "n_docs": 96,
           "index": {"params": {"coarse_levels": 2, "k_coarse": 20}}}
    ref = registry.reference("bigranular")
    v, i = ref.expected(cfg, q_codes, _Codes(docs, 32))
    want_v, want_i = _brute_two_tier(q_codes, docs, 4, 2, 20, 7)
    np.testing.assert_array_equal(i, want_i)
    np.testing.assert_array_equal(v, want_v)
    # The ties reached the answers.
    assert any(len(set(row)) < len(row) for row in v.tolist())


def _record_snapshots(monkeypatch):
    seen = []
    build = harness.make_search

    def record(cfg, snapshot, backend):
        seen.append(snapshot)
        return build(cfg, snapshot, backend)

    monkeypatch.setattr(harness, "make_search", record)
    return seen


def test_a_host_snapshot_is_a_memmap_of_the_same_corpus(monkeypatch):
    from repro.launch.lifecycle import CorpusSnapshot

    seen = _record_snapshots(monkeypatch)
    res = _run(dict(SMALL, snapshot="host"))
    assert res["correct"], res["checks"]
    (snap,) = seen
    assert isinstance(snap.codes, np.memmap)
    assert not snap.codes.flags.writeable
    cfg = _config("web-flat")
    _, _, corpus = harness.deployment(cfg)
    on_device = CorpusSnapshot(codes=corpus.all_codes(),
                               n_levels=snap.n_levels)
    assert snap.digest == on_device.digest
    assert not os.path.exists(os.path.dirname(snap.codes.filename))

    seen.clear()
    assert _run(SMALL)["correct"]
    assert not isinstance(seen[0].codes, np.ndarray)


def test_an_untraceable_search_fails_the_run_with_no_result(monkeypatch):
    import jax.numpy as jnp

    seen = _record_snapshots(monkeypatch)
    build = harness.make_search

    def untraceable(cfg, snapshot, backend):
        # The configuration's own search, with its ids read back to the
        # host between two device programs.
        search = build(cfg, snapshot, backend)

        def fn(q):
            scores, ids = search(q)
            return scores, jnp.asarray(np.asarray(ids))
        return fn

    monkeypatch.setattr(harness, "make_search", untraceable)
    printed = []
    with pytest.raises(harness.UntraceableSearch,
                       match="web-flat.online.*one traceable program"):
        _run(dict(SMALL, snapshot="host"), emit=printed.append)
    assert printed == []
    assert not os.path.exists(os.path.dirname(seen[0].codes.filename))


def test_a_snapshot_is_on_the_device_or_the_host():
    with pytest.raises(ValueError, match="snapshot 'disk'"):
        _run(dict(SMALL, snapshot="disk"))


def test_a_spilled_corpus_equals_its_chunks(tmp_path):
    cfg = _config("web-flat")
    _, _, corpus = harness.deployment(cfg)
    path = str(tmp_path / "codes.int8")
    codes = harness.spill_codes(corpus, cfg["binarizer"]["code_dim"], path)
    np.testing.assert_array_equal(codes, np.asarray(corpus.all_codes()))
