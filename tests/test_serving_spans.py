"""Profiler spans of the served path (``launch/spans.py``): every boundary
of a request writes one span into the profiler's own trace, read back
here with ``jax.profiler.ProfileData``; every span that crosses threads
is closed once on every path (shed, expired, failed, closed); the stage
totals of ``ServingPipeline.stats()`` count the same boundaries; and
nothing changes with no profiler session."""

import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import proxy, serving, spans
from repro.launch.proxy import QueryRouter, ReplicaSet, serve_replicated
from repro.launch.serving import (
    PipelineClosed,
    RequestShed,
    ServingConfig,
    serve_sequential,
)

# One request's spans, in the order its stages run.
STAGE_ORDER = ("proxy.submit", "serving.admit", "serving.queued",
               "serving.encode", "serving.handoff", "serving.dispatch",
               "serving.await", "serving.resolve")
W = jnp.asarray(np.random.default_rng(0).standard_normal((16, 8)),
                jnp.float32)
DOCS = jnp.asarray(np.random.default_rng(1).standard_normal((64, 8)),
                   jnp.float32)


def _encode(x):
    return jnp.sign(jnp.asarray(x) @ W)


def _search(codes, sleep_s=0.0):
    if sleep_s:
        time.sleep(sleep_s)
    return jax.lax.top_k(codes @ DOCS.T, 5)


def _batches(n, seed=2):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((4, 16)).astype(np.float32) for _ in range(n)]


def _read(log_dir):
    """{span name: [(req or None, start s, end s)]} of the host spans."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)
    assert len(path) == 1
    out = {}
    for plane in ProfileData.from_file(path[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("proxy.", "serving.")):
                    req = dict(ev.stats).get("req")
                    out.setdefault(ev.name, []).append(
                        (None if req is None else int(req),
                         ev.start_ns * 1e-9, ev.end_ns * 1e-9))
    return out


class _Traced:
    """A profiler session over the block; ``spans`` holds what it wrote.

    Also counts, in the program's own ``Span`` objects, how often each
    was closed: a span left open, or closed twice, fails the test."""

    def __init__(self, tmp_path, monkeypatch):
        self.dir = str(tmp_path)
        self.opened = []

        class Counted(spans.Span):
            __slots__ = ("name", "closes")

            def __init__(s, name, req=None):
                super().__init__(name, req)
                s.name, s.closes = name, 0
                self.opened.append(s)

            def close(s):
                s.closes += 1
                return super().close()

        for mod in (serving, proxy):
            monkeypatch.setattr(mod, "Span", Counted)

    def __enter__(self):
        jax.profiler.start_trace(self.dir)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        self.spans = _read(self.dir)

    def unclosed(self):
        return [(s.name, s.closes) for s in self.opened if s.closes != 1]


def _by_req(events):
    out = {}
    for req, s, e in events:
        out.setdefault(req, []).append((s, e))
    return out


def _covered(root, children):
    """Share of ``root`` = (start, end) that the children's union covers."""
    lo, hi = root
    cur, got = lo, 0.0
    for s, e in sorted(children):
        s, e = max(s, cur), min(e, hi)
        if e > s:
            got += e - s
            cur = e
    return got / (hi - lo)


def test_each_request_has_one_span_of_each_stage_in_order(tmp_path,
                                                           monkeypatch):
    batches = _batches(8)
    router = QueryRouter(ReplicaSet(
        [(_encode, lambda c: _search(c, 0.02))]))
    serving.warmup(_encode, _search, batches[:1])
    with _Traced(tmp_path, monkeypatch) as tr:
        tickets = [router.submit(b) for b in batches]
        for t in tickets:
            t.result(timeout=30)
        router.close()
    assert tr.unclosed() == []
    reqs = sorted(t.seq for t in tickets)
    per = {name: _by_req(tr.spans.get(name, []))
           for name in STAGE_ORDER + ("proxy.request",)}
    covered = root_s = 0.0
    for r in reqs:
        for name, got in per.items():
            assert len(got.get(r, [])) == 1, (name, r, got.get(r))
        (root,) = per["proxy.request"][r]
        stages = [per[name][r][0] for name in STAGE_ORDER]
        # Stages start in order; each hand-over starts after the last
        # stage of the request ends (the caller's submit overlaps the
        # admission queue).
        assert all(a[0] <= b[0] for a, b in zip(stages, stages[1:]))
        for a, b in zip(stages[2:], stages[3:]):
            assert a[1] <= b[0]
        assert root[0] <= stages[0][0]
        assert stages[-1][0] <= root[1] <= stages[-1][1]
        covered += _covered(root, stages) * (root[1] - root[0])
        root_s += root[1] - root[0]
    assert covered / root_s >= 0.95
    # Idle spans belong to no request.
    assert tr.spans["serving.scan_idle"]
    assert all(r is None for r, _, _ in tr.spans["serving.encode_idle"])


def _blocking_tier(gate, *, policy="block", depth=8, fail_on=None):
    """A one-replica tier whose search waits for ``gate``; the search of
    the batch whose first value is ``fail_on`` raises."""

    def search(codes):
        gate.wait(timeout=30)
        if fail_on is not None and float(np.asarray(codes)[0, 0]) == fail_on:
            raise RuntimeError("planted search failure")
        return codes * 2, codes + 1

    return QueryRouter(ReplicaSet(
        [(lambda x: x, search)],
        config=ServingConfig(queue_depth=depth, policy=policy)))


def _batch(v):
    return np.full((2, 4), float(v), np.float32)


def _wait_for(cond, timeout=10.0):
    t = time.perf_counter() + timeout
    while not cond():
        assert time.perf_counter() < t, "condition not reached"
        time.sleep(0.005)


def test_shed_requests_close_their_spans(tmp_path, monkeypatch):
    gate = threading.Event()
    router = _blocking_tier(gate, policy="shed", depth=1)
    pipe = router.replicas.pipelines[0]
    admitted, shed = [], 0
    with _Traced(tmp_path, monkeypatch) as tr:
        # Request 0 is dispatched and blocks the scan, 1 waits in the
        # hand-off, 2 holds the encode stage, 3 fills the queue.
        for v in range(4):
            admitted.append(router.submit(_batch(v)))
            _wait_for(lambda: pipe._admission._q.qsize() == 0 or v == 3)
        for v in range(4, 7):
            with pytest.raises(RequestShed):
                router.submit(_batch(v))
            shed += 1
        gate.set()
        for t in admitted:
            t.result(timeout=30)
        router.close()
    assert tr.unclosed() == []
    assert len(tr.spans["serving.queued"]) == len(admitted)
    assert len(tr.spans["proxy.request"]) == len(admitted) + shed
    assert len(tr.spans["serving.admit"]) == len(admitted) + shed


def test_expired_and_failed_requests_close_their_spans(tmp_path,
                                                       monkeypatch):
    gate = threading.Event()
    router = _blocking_tier(gate, fail_on=1.0)
    pipe = router.replicas.pipelines[0]
    with _Traced(tmp_path, monkeypatch) as tr:
        first = router.submit(_batch(0))
        _wait_for(lambda: pipe.scan_oldest_age() is not None)
        failing = router.submit(_batch(1))
        # Expires in the hand-off (encoded while the scan is blocked)...
        soon = time.perf_counter() + 0.1
        late = router.submit(_batch(2), deadline=soon)
        # ...and this one at the encode stage's dequeue.
        queued = router.submit(_batch(3), deadline=soon)
        time.sleep(0.15)
        gate.set()
        first.result(timeout=30)
        with pytest.raises(RuntimeError, match="planted"):
            failing.result(timeout=30)
        # The failure takes the only replica out, so the router fails
        # what it still held; the replica's stages expire them anyway.
        for t in (late, queued):
            with pytest.raises(RuntimeError):
                t.result(timeout=30)
        router.close()
    assert tr.unclosed() == []
    assert len(tr.spans["proxy.request"]) == 4
    assert len(tr.spans["serving.queued"]) == 4
    stats = pipe.stats()
    assert stats["deadline_expired"] == 2
    assert stats["stages"]["admission_wait"]["count"] == 4
    # The failed search was dispatched and resolved, never awaited.
    assert stats["stages"]["dispatch"]["count"] == \
        stats["stages"]["await"]["count"] + 1


def test_close_without_drain_closes_queued_spans(tmp_path, monkeypatch):
    gate = threading.Event()
    router = _blocking_tier(gate)
    pipe = router.replicas.pipelines[0]
    with _Traced(tmp_path, monkeypatch) as tr:
        tickets = [router.submit(_batch(0))]
        _wait_for(lambda: pipe.scan_oldest_age() is not None)
        tickets += [router.submit(_batch(v)) for v in range(1, 6)]
        threading.Timer(0.2, gate.set).start()
        router.close(drain=False)
    assert tr.unclosed() == []
    errors = [t.error() for t in tickets]
    assert errors[0] is None
    assert any(isinstance(e, PipelineClosed) for e in errors)
    assert all(t.done() for t in tickets)
    assert len(tr.spans["serving.queued"]) == len(tickets)
    assert len(tr.spans["proxy.request"]) == len(tickets)
    assert len(tr.spans["serving.handoff"]) == \
        len(tr.spans["serving.encode"])


def test_stage_totals_count_the_span_boundaries():
    batches = _batches(6)
    pipe = serving.ServingPipeline(_encode, _search)
    try:
        for t in [pipe.submit(b) for b in batches]:
            t.result(timeout=30)
        stages = pipe.stats()["stages"]
        assert set(stages) == set(spans.STAGES)
        for k in ("admission_wait", "encode", "handoff", "dispatch",
                  "await", "resolve"):
            assert stages[k]["count"] == len(batches), k
            assert stages[k]["seconds"] >= 0.0
        assert 0.0 <= pipe.stats()["scan_input_wait_frac"] <= 1.0
        assert pipe.quiesce(timeout=10)
        pipe.new_generation()
        stages = pipe.stats()["stages"]
        assert all(v["count"] == 0 for k, v in stages.items()
                   if k != "scan_input_wait")
    finally:
        pipe.close()


def test_results_are_bit_identical_with_and_without_a_session(tmp_path):
    batches = _batches(6, seed=3)
    replicas = [(_encode, _search)]
    seq = serve_sequential(_encode, _search, batches)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    plain, _ = serve_replicated(replicas, batches)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced, _ = serve_replicated(replicas, batches)
    finally:
        jax.profiler.stop_trace()
    for (sv, si), (pv, pi), (tv, ti) in zip(seq, plain, traced):
        np.testing.assert_array_equal(np.asarray(si), np.asarray(pi))
        np.testing.assert_array_equal(np.asarray(sv), np.asarray(pv))
        np.testing.assert_array_equal(np.asarray(pi), np.asarray(ti))
        np.testing.assert_array_equal(np.asarray(pv), np.asarray(tv))
