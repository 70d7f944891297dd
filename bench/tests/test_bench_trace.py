"""The reduction from a profiler trace to per-layer metrics, on traces
written by hand and on one recorded on the CPU."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import registry, trace  # noqa: E402
from bench.harness import LayerContext, breakdown  # noqa: E402

Op = trace.Op


def test_union_merges_overlaps_and_drops_empty():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (5, 5)]) == [(0, 2), (3, 4)]
    assert trace.length([(0, 1), (0.5, 2), (3, 4)]) == 3


def test_subtract_and_gaps():
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert trace.gaps([(1, 2), (1.5, 3)], 0, 4) == [(0, 1), (3, 4)]
    assert trace.subtract([(0, 1)], []) == [(0, 1)]


def test_modules_are_assigned_by_execution():
    mods = [Op(0, 0.0, 1.0, "jit__encode"), Op(0, 2.0, 5.0, "jit_search"),
            Op(1, 2.0, 5.0, "jit_search")]
    ops = [Op(0, 0.1, 0.5, "dot"), Op(0, 2.5, 3.0, "custom-call"),
           Op(0, 6.0, 6.5, "stray"), Op(1, 2.1, 2.2, "all-gather")]
    trace.assign_modules(ops, mods)
    assert [o.module for o in ops] == ["jit__encode", "jit_search", "",
                                       "jit_search"]
    assert ops[1].run == 2.0 and ops[3].run == 2.0


def _ctx(ops, modules, inflight, window, n_requests, least_s=0.0, chips=1,
         host=()):
    tr = trace.Trace(ops=ops, modules=modules, host=list(host), offset=0.0)
    trace.assign_modules(tr.ops, tr.modules)
    busy = trace.busy_by_device(tr.ops, *window)
    busy_s = sum(trace.length(b) for b in busy.values()) / max(1, len(busy))
    return LayerContext(trace=tr, window=window, inflight=inflight,
                        busy=busy, busy_s=busy_s, n_requests=n_requests,
                        least_s=least_s, chips=chips)


def test_busy_is_the_union_of_ops_and_idle_counts_only_in_flight_time():
    # Two overlapping ops (a kernel and a copy) and one after a gap.
    ops = [Op(0, 1.0, 3.0, "custom-call"), Op(0, 2.0, 4.0, "copy"),
           Op(0, 6.0, 7.0, "custom-call")]
    # Requests in flight over [0.5, 4.5] and [5.5, 7]; nothing 7..10.
    ctx = _ctx(ops, [], [(0.5, 4.5), (5.5, 7.0)], (0.0, 10.0), n_requests=2,
               least_s=0.4)
    assert ctx.busy_s == pytest.approx(4.0)
    idle = registry.metric_reader("device_idle.online")(ctx)
    # Idle with work in flight: 0.5-1, 4-4.5 and 5.5-6 = 1.5 s of 10.
    assert idle == pytest.approx(15.0)
    roof = registry.metric_reader("search_roofline.bulk")(ctx)
    assert roof == pytest.approx(100.0 * 0.4 / 4.0)


def test_idle_is_averaged_over_chips():
    ops = [Op(0, 0.0, 10.0, "scan"), Op(1, 0.0, 5.0, "scan")]
    ctx = _ctx(ops, [], [(0.0, 10.0)], (0.0, 10.0), n_requests=1, chips=2)
    assert registry.metric_reader("device_idle.online")(ctx) == \
        pytest.approx(25.0)


def test_readers_return_nothing_without_work():
    ctx = _ctx([], [], [], (0.0, 1.0), n_requests=0)
    for name in ("search_roofline.online", "device_idle.bulk",
                 "encode_ms.online", "engine_merge_ms"):
        assert registry.metric_reader(name)(ctx) is None


def test_per_module_time_for_encode_and_merge():
    mods = [Op(0, 0.0, 1.0, "jit__encode(12)"), Op(0, 1.0, 4.0, "jit_fn"),
            Op(1, 1.0, 4.0, "jit_fn"), Op(0, 5.0, 6.0, "jit__encode(12)"),
            Op(0, 6.0, 9.0, "jit_fn"), Op(1, 6.0, 9.0, "jit_fn")]
    ops = [Op(0, 0.2, 0.4, "fusion"), Op(0, 5.2, 5.6, "fusion")]
    for d in (0, 1):
        for base in (1.0, 6.0):
            ops += [Op(d, base + 0.1, base + 2.0, "custom-call"),
                    Op(d, base + 2.0, base + 2.25, "all-gather.1"),
                    Op(d, base + 2.25, base + 2.5, "sort")]
    ctx = _ctx(ops, mods, [(0.0, 9.0)], (0.0, 10.0), n_requests=2)
    assert registry.metric_reader("encode_ms.online")(ctx) == \
        pytest.approx(1e3 * 0.6 / 2)
    # Per chip, per request: the all-gather and what follows it.
    assert registry.metric_reader("engine_merge_ms")(ctx) == \
        pytest.approx(1e3 * 0.5 / 1)


def test_idle_gaps_are_named_by_the_host_span_over_them():
    host = [Op(-1, 0.0, 2.0, "bench.sleep"), Op(-1, 2.0, 2.2, "bench.submit")]
    gaps = trace.label_gaps([(0.5, 1.5), (2.05, 2.1), (5.0, 5.01)], host)
    assert gaps[0] == ("bench.sleep", 1.0)
    assert gaps[1][0] == "bench.submit"
    assert gaps[2][0] == "-"


def test_a_recorded_trace_gives_the_host_clock_offset(tmp_path):
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x.T)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with trace.capture(str(tmp_path)):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.submit"):
            f(x).block_until_ready()
        t1 = time.perf_counter()
    tr = trace.load(str(tmp_path))
    assert tr.offset is not None
    spans = [h for h in tr.host if h.name == "bench.submit"]
    assert len(spans) == 1
    # The span sits where the host clock put it, to within a millisecond.
    assert spans[0].start == pytest.approx(t0 + tr.offset, abs=1e-3)
    assert spans[0].end == pytest.approx(t1 + tr.offset, abs=1e-3)


def test_op_names_lose_their_hlo_text():
    ops = [Op(0, 0.0, 1.0, "%copy.4 = u8[8,64]{1,0} copy(u8[8,64] %d)"),
           Op(1, 0.0, 0.5, "%copy.4 = u8[8,64]{1,0} copy(u8[8,64] %d)"),
           Op(0, 1.0, 1.25, "fusion.2")]
    assert trace.time_by_name(ops, 0.0, 2.0) == {"copy.4": 1.5,
                                                 "fusion.2": 0.25}


def test_the_breakdown_sums_the_ops_of_one_name_whatever_their_number():
    ops = [Op(0, 0.0, 1.0, "%copy.4 = u8[8,64]{1,0} copy(u8[8,64] %d)"),
           Op(0, 1.0, 1.5, "copy.27"),
           Op(0, 2.0, 4.0, "sdc_topk_q128.1"),
           Op(0, 4.0, 4.25, "fusion"), Op(0, 4.25, 4.375, "fusion.38")]
    ctx = _ctx(ops, [], [(0.0, 5.0)], (0.0, 5.0), n_requests=1)
    assert breakdown(ctx)["device_ops"] == [
        ["sdc_topk_q128", 2.0], ["copy", 1.5], ["fusion", 0.375]]


def test_idle_gaps_are_named_by_the_program_span_over_them():
    # The device runs [0, 2], [3, 5], [6, 7] and [9, 10]: idle 2..3, 5..6
    # and 7..9.
    ops = [Op(0, 0.0, 2.0, "scan"), Op(0, 3.0, 5.0, "scan"),
           Op(0, 6.0, 7.0, "scan"), Op(0, 9.0, 10.0, "scan")]
    span = Op
    host = [
        # One request over [0, 6], queued until 2 and dispatched at 2.
        span(-1, 0.0, 6.0, "proxy.request"),
        span(-1, 0.0, 6.0, "bench.await"),
        span(-1, 1.0, 2.0, "serving.queued"),
        span(-1, 2.0, 3.0, "serving.dispatch"),
        # Runtime events are not the program's spans.
        span(-1, 2.0, 3.0, "PjitFunction(search)"),
        span(-1, 5.0, 6.0, "dot_general.1"),
        span(-1, 6.0, 10.0, "bench.sleep"),
        span(-1, 0.0, 10.0, "bench.sync"),
    ]
    ctx = _ctx(ops, [], [(0.0, 6.0)], (0.0, 10.0), n_requests=1, host=host)
    assert breakdown(ctx)["idle_gaps"] == [
        # Under no program span: the client's.
        ["bench.sleep", 2.0],
        # Under the dispatch, inside the whole request: the dispatch.
        ["serving.dispatch", 1.0],
        # Under the whole request alone: the client's.
        ["bench.await", 1.0]]
    assert trace.label_gaps([(20.0, 21.0)], host) == [("-", 1.0)]


def test_a_gap_tie_goes_to_the_shorter_program_span():
    host = [Op(-1, 0.0, 4.0, "serving.encode_idle"),
            Op(-1, 1.0, 3.0, "serving.scan_idle"),
            Op(-1, 0.0, 4.0, "bench.sleep")]
    assert trace.label_gaps([(1.5, 2.5)], host) == [("serving.scan_idle",
                                                      1.0)]
    # Overlap, not length, comes first.
    assert trace.label_gaps([(0.5, 2.5)], host) == [("serving.encode_idle",
                                                      2.0)]
