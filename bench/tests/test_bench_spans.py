"""The readers of the program's own spans (``bench/spans.py`` and the
``metrics/`` files that use it), on traces written by hand and on one
recorded on the CPU from the served path."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import registry, spans, trace  # noqa: E402
from bench.harness import LayerContext  # noqa: E402

Op = trace.Op

# metric -> the span whose mean it reads (router_ms reads a self time)
MEANS = {
    "admission_wait_ms.online": "serving.queued",
    "encode_host_ms.online": "serving.encode",
    "handoff_ms.online": "serving.handoff",
    "dispatch_ms.online": "serving.dispatch",
    "dispatch_ms.bulk": "serving.dispatch",
    "resolve_ms.online": "serving.resolve",
    "resolve_ms.bulk": "serving.resolve",
}
READERS = sorted(MEANS) + ["router_ms.online"]


def _ctx(host, window=(0.0, 10.0), ops=(), inflight=()):
    tr = trace.Trace(ops=list(ops), modules=[], host=host, offset=0.0)
    busy = trace.busy_by_device(tr.ops, *window)
    return LayerContext(trace=tr, window=window, inflight=list(inflight),
                        busy=busy, busy_s=0.0, n_requests=1, least_s=0.0,
                        chips=max(1, len(busy)))


def _span(name, s, e):
    return Op(-1, s, e, name)


@pytest.mark.parametrize("metric", sorted(MEANS))
def test_mean_of_the_spans_that_end_in_the_window(metric):
    name = MEANS[metric]
    host = [_span(name, 1.0, 1.002), _span(name, 2.0, 2.004),
            # Ends before the window opens, and after it closes: left out.
            _span(name, -1.0, -0.5), _span(name, 9.99, 10.5),
            # Starts before the window, ends inside: counted whole.
            _span(name, -0.004, 0.002),
            _span("serving.other", 3.0, 4.0), _span("bench.await", 1.0, 2.0)]
    got = registry.metric_reader(metric)(_ctx(host))
    assert got == pytest.approx(1e3 * (0.002 + 0.004 + 0.006) / 3)


def test_router_time_is_submit_less_the_admission_put_inside_it():
    host = [_span("proxy.submit", 1.0, 1.010), _span("serving.admit",
                                                     1.002, 1.007),
            _span("proxy.submit", 2.0, 2.004),  # admitted at once
            _span("serving.admit", 2.001, 2.001),
            _span("proxy.submit", 11.0, 11.5)]  # after the window
    got = registry.metric_reader("router_ms.online")(_ctx(host))
    assert got == pytest.approx(1e3 * (0.005 + 0.004) / 2)


@pytest.mark.parametrize("metric", READERS)
def test_readers_give_nothing_without_program_spans(metric):
    # The parent program writes only the client's spans.
    host = [_span("bench.submit", 1.0, 1.1), _span("bench.await", 1.1, 2.0)]
    assert registry.metric_reader(metric)(_ctx(host)) is None
    assert registry.metric_reader(metric)(_ctx([])) is None


def test_idle_split_names_the_scan_threads_span_over_each_gap():
    # The device runs [0, 1] and [3, 4]; one request in flight over
    # [0, 4]: idle 1..3, while the scan thread resolved (1..1.5), waited
    # for input (1.5..2.5: encoding 1.5..2) and dispatched (2.5..3).
    ops = [Op(0, 0.0, 1.0, "scan"), Op(0, 3.0, 4.0, "scan")]
    host = [_span("serving.await", 0.0, 1.0),
            _span("serving.resolve", 1.0, 1.5),
            _span("serving.scan_idle", 1.5, 2.5),
            _span("serving.encode", 1.5, 2.0),
            _span("serving.dispatch", 2.5, 3.0),
            _span("bench.await", 0.0, 4.0)]
    out = spans.idle_split(_ctx(host, (0.0, 5.0), ops, [(0.0, 4.0)]))
    assert out["idle_in_flight_s"] == pytest.approx(2.0)
    assert out["scan_thread"] == pytest.approx({
        "serving.resolve": 0.5, "serving.scan_idle": 1.0,
        "serving.dispatch": 0.5, "serving.await": 0.0, "-": 0.0})
    assert out["encode_thread_while_scan_waits"] == pytest.approx({
        "serving.encode": 0.5, "serving.encode_idle": 0.0, "-": 0.5})
    assert out["await"] == pytest.approx({
        "before_first_op": 0.0, "between_ops": 0.0, "after_last_op": 0.0,
        "no_op": 0.0})
    (gap,) = out["longest"]
    assert gap["s"] == pytest.approx(2.0) and gap["at"] == pytest.approx(1.0)
    assert set(gap["spans"]) == {"serving.resolve", "serving.scan_idle",
                                 "serving.encode", "serving.dispatch"}
    assert spans.idle_split(_ctx([_span("bench.await", 0.0, 4.0)],
                                 (0.0, 5.0), ops, [(0.0, 4.0)])) is None


def test_a_recorded_trace_of_the_served_path_feeds_every_reader(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.proxy import QueryRouter, ReplicaSet

    docs = jnp.asarray(np.random.default_rng(0).standard_normal((256, 16)),
                       jnp.float32)
    encode = jax.jit(lambda x: jnp.sign(x))
    search = jax.jit(lambda c: jax.lax.top_k(c @ docs.T, 10))
    batches = [np.random.default_rng(i).standard_normal((8, 16)).astype(
        np.float32) for i in range(6)]
    search(encode(batches[0]))
    router = QueryRouter(ReplicaSet([(encode, search)]))
    with trace.capture(str(tmp_path)):
        for b in batches:
            router.submit(b).result(timeout=30)
    router.close()
    tr = trace.load(str(tmp_path))
    ctx = _ctx(tr.host, window=(min(h.start for h in tr.host),
                                max(h.end for h in tr.host)))
    for metric in READERS:
        got = registry.metric_reader(metric)(ctx)
        assert got is not None and got >= 0.0, metric
    assert len(spans.ended_in_window(ctx, "proxy.request")) == len(batches)


def test_idle_inside_an_await_is_split_around_the_device_ops():
    # One await over [0, 10]: the search starts at 2 (a late launch; the
    # next request's encoder ran 0.5..1), pauses 4..5 and ends at 7; the
    # host is back at 10. A second await over [11, 12] sees no op at all.
    ops = [Op(0, 0.5, 1.0, "dot"), Op(0, 2.0, 4.0, "scan"),
           Op(0, 5.0, 7.0, "scan")]
    ops[0].module = "jit__encode"
    host = [_span("serving.await", 0.0, 10.0),
            _span("serving.await", 11.0, 12.0)]
    out = spans.idle_split(_ctx(host, (0.0, 20.0), ops, [(0.0, 12.0)]))
    assert out["await"] == pytest.approx({
        "before_first_op": 1.5, "between_ops": 1.0, "after_last_op": 3.0,
        "no_op": 1.0})
    assert out["scan_thread"]["serving.await"] == pytest.approx(6.5)
    assert out["scan_thread"]["-"] == pytest.approx(1.0)
