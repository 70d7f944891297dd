"""Replicated serving tier (launch/proxy.py): routing policies, cross-
replica shedding (proxy sheds only when every replica is saturated),
failover (replica death mid-stream re-dispatches in-flight tickets with
no drops and no client-visible reordering), and router bit-identity vs
serve_sequential for all three index families."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.index import ivf as ivf_lib
from repro.index.flat import FlatSDC
from repro.index.hnsw_lite import build_hnsw, prepare_batched, search_hnsw_batched
from repro.kernels.sdc import ref as R
from repro.launch.clock import FakeClock
from repro.launch.faults import FaultInjector, FaultPlan
from repro.launch.mesh import make_replica_meshes
from repro.launch.proxy import (
    AllReplicasDown,
    EffortKnob,
    QueryRouter,
    ReplicaSet,
    serve_replicated,
)
from repro.launch.serving import (
    DeadlineExpired,
    RequestShed,
    ScanStalled,
    ServingConfig,
    serve_sequential,
)

LEVELS = 4


def _identity_replica(tag, calls=None, fail_after=None, scan_sleep=0.0):
    """(encode, search) whose output encodes the input batch; optionally
    records which replica served each batch. Fault schedules come from
    the shared chaos vocabulary: ``fail_after=N`` wraps the pair in a
    ``FaultInjector`` whose scans raise from scan call N on."""

    def encode(x):
        return x

    def search(c):
        if scan_sleep:
            time.sleep(scan_sleep)
        if calls is not None:
            calls.append((tag, int(np.asarray(c).ravel()[0])))
        return c * 2, c + 1

    if fail_after is None:
        return encode, search
    return FaultInjector(
        encode, search, FaultPlan.fail_after(fail_after), name=f"r{tag}"
    ).pair


def _batches(n=6, width=4):
    return [np.full((width,), i, dtype=np.int64) for i in range(n)]


def _check_identity(results, n):
    assert len(results) == n
    for i, (vals, ids) in enumerate(results):
        np.testing.assert_array_equal(np.asarray(vals), np.full((4,), 2 * i))
        np.testing.assert_array_equal(np.asarray(ids), np.full((4,), i + 1))


# ---------------------------------------------------------------------------
# routing policies
# ---------------------------------------------------------------------------


def test_round_robin_spreads_batches_evenly():
    calls = []
    replicas = [_identity_replica(t, calls) for t in range(3)]
    results, stats = serve_replicated(replicas, _batches(9),
                                      policy="round-robin")
    _check_identity(results, 9)
    served = {t: [b for (r, b) in calls if r == t] for t in range(3)}
    assert all(len(v) == 3 for v in served.values()), served
    assert stats["requests"] == 9 and stats["queries"] == 36
    assert stats["router"] == "round-robin"


def test_least_outstanding_avoids_the_busy_replica():
    gate = threading.Event()
    started = threading.Event()
    calls = []

    def slow_encode(x):
        started.set()
        gate.wait(timeout=10)
        return x

    _, slow_search = _identity_replica(0, calls)
    fast = _identity_replica(1, calls)
    router = QueryRouter(
        ReplicaSet([(slow_encode, slow_search), fast],
                   config=ServingConfig(queue_depth=8)),
        policy="least-outstanding",
    )
    try:
        t0 = router.submit(_batches()[0])  # ties break to replica 0
        assert started.wait(timeout=5)
        # replica 0 is stuck in encode with 1 outstanding: every new
        # batch (awaited before the next, so replica 1 is drained and
        # its count is back to 0) must route to replica 1.
        for b in _batches(5)[1:]:
            router.submit(b).result(timeout=10)
        assert all(r == 1 for (r, _) in calls)
        gate.set()
        t0.result(timeout=10)
        assert t0.replica == 0
    finally:
        gate.set()
        router.close()


def test_unknown_policy_rejected():
    with pytest.raises(ValueError, match="unknown routing policy"):
        QueryRouter(ReplicaSet([_identity_replica(0)]), policy="random")


# ---------------------------------------------------------------------------
# cross-replica shedding
# ---------------------------------------------------------------------------


def test_proxy_sheds_only_when_every_replica_is_saturated():
    gates = [threading.Event(), threading.Event()]
    started = [threading.Event(), threading.Event()]

    def gated_replica(i):
        def encode(x):
            started[i].set()
            gates[i].wait(timeout=10)
            return x

        def search(c):
            return c * 2, c + 1

        return encode, search

    router = QueryRouter(
        ReplicaSet([gated_replica(0), gated_replica(1)],
                   config=ServingConfig(queue_depth=1, policy="shed")),
        policy="round-robin",
    )
    try:
        tickets = [router.submit(b) for b in _batches(2)]  # one per encode
        assert started[0].wait(timeout=5) and started[1].wait(timeout=5)
        # Both encodes gated; each replica has one free queue slot. The
        # next two submits bounce off one replica but land on the other:
        # NOT proxy sheds.
        tickets += [router.submit(b) for b in _batches(4)[2:]]
        assert router.shed_count == 0
        # Every replica's queue is now full: the proxy finally sheds.
        with pytest.raises(RequestShed, match="healthy replicas saturated"):
            router.submit(_batches(5)[4])
        assert router.shed_count == 1
        stats = router.stats()
        assert stats["shed"] == 1
        assert stats["replica_shed"] >= 2  # the absorbed bounces
        for g in gates:
            g.set()
        for t in tickets:
            t.result(timeout=10)
    finally:
        for g in gates:
            g.set()
        router.close()


# ---------------------------------------------------------------------------
# failover
# ---------------------------------------------------------------------------


def test_replica_death_mid_stream_redispatches_without_loss_or_reorder():
    calls = []
    healthy = _identity_replica(0, calls)
    # replica 1 serves one scan, then dies with tickets still queued on
    # it (slow scan so the stream piles up behind the failure).
    dying = _identity_replica(1, calls, fail_after=1, scan_sleep=0.02)
    router = QueryRouter(
        ReplicaSet([healthy, dying], config=ServingConfig(queue_depth=16)),
        policy="round-robin",
    )
    try:
        tickets = [router.submit(b) for b in _batches(12)]
        results = [t.result(timeout=30) for t in tickets]
        _check_identity(results, 12)  # nothing dropped, nothing reordered
        stats = router.stats()
        assert stats["healthy"] == [0]
        assert stats["failovers"] >= 1
        assert stats["requests"] == 12  # failed-over requests count once
        # the survivor picked up every re-dispatched batch
        assert sum(1 for (r, _) in calls if r == 1) == 1
    finally:
        router.close()


def test_eager_failover_redispatches_before_client_awaits():
    """The router's done-callback re-dispatches the moment a scan fails —
    tickets recover even if the client never touched result() yet."""
    calls = []
    healthy = _identity_replica(0, calls)
    dying = _identity_replica(1, calls, fail_after=0)  # dies on first scan
    router = QueryRouter(
        ReplicaSet([healthy, dying], config=ServingConfig(queue_depth=8)),
        policy="round-robin",
    )
    try:
        tickets = [router.submit(b) for b in _batches(6)]
        deadline = time.time() + 15
        while time.time() < deadline and not all(t.done() for t in tickets):
            time.sleep(0.01)
        assert all(t.done() for t in tickets)  # resolved with no client pull
        _check_identity([t.result() for t in tickets], 6)
        assert router.healthy() == [0]
    finally:
        router.close()


def test_all_replicas_down_surfaces_error_and_rejects_submits():
    replicas = [_identity_replica(i, fail_after=0) for i in range(2)]
    router = QueryRouter(
        ReplicaSet(replicas, config=ServingConfig(queue_depth=8))
    )
    try:
        t = router.submit(_batches(1)[0])
        with pytest.raises(RuntimeError, match="injected fail"):
            t.result(timeout=15)
        assert router.healthy() == []
        with pytest.raises(AllReplicasDown):
            router.submit(_batches(2)[1])
    finally:
        router.close()


# ---------------------------------------------------------------------------
# bit-identity vs the sequential loop, all three index families
# ---------------------------------------------------------------------------


def _code_corpus(n=600, q=24, dim=32, seed=0):
    key = jax.random.PRNGKey(seed)
    cd = jax.random.randint(key, (n, dim), 0, 2**LEVELS).astype(jnp.int8)
    cq = jax.random.randint(
        jax.random.fold_in(key, 1), (q, dim), 0, 2**LEVELS
    ).astype(jnp.int8)
    return cd, cq


@pytest.mark.parametrize("kind", ["flat", "ivf", "hnsw"])
def test_router_bit_identical_to_sequential(kind):
    cd, cq = _code_corpus()
    if kind == "flat":
        index = FlatSDC.build(cd, LEVELS, backend="xla")
        search = lambda q: index.search(q, 10)
    elif kind == "ivf":
        index = ivf_lib.build_ivf(
            jax.random.PRNGKey(1), cd, n_levels=LEVELS, nlist=8,
            kmeans_iters=3,
        )
        search = lambda q: ivf_lib.search(index, q, nprobe=4, k=10,
                                          backend="xla")
    else:
        inv = np.asarray(R.doc_inv_norms(cd, LEVELS))
        graph = build_hnsw(np.asarray(cd), inv, n_levels=LEVELS, M=8,
                           ef_construction=24, seed=0)
        tables = prepare_batched(graph)
        search = lambda q: search_hnsw_batched(
            tables, q, k=10, ef=24, beam=8, backend="xla"
        )

    encode = lambda q: q  # codes in, codes out: isolates routing
    batches = [cq[i : i + 8] for i in range(0, cq.shape[0], 8)]
    seq = serve_sequential(encode, search, batches)
    # Two replicas over the same index closure: every replica must be
    # bit-identical, so routing is invisible to correctness.
    routed, stats = serve_replicated(
        [(encode, search)] * 2, batches, policy="round-robin"
    )
    assert stats["requests"] == len(batches)
    assert stats["replicas"] == 2
    for (sv, si), (rv, ri) in zip(seq, routed):
        np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))
        np.testing.assert_array_equal(np.asarray(sv), np.asarray(rv))


def test_stats_aggregate_per_replica_rows():
    replicas = [_identity_replica(i) for i in range(2)]
    results, stats = serve_replicated(replicas, _batches(8))
    _check_identity(results, 8)
    assert len(stats["per_replica"]) == 2
    assert sum(s["requests"] for s in stats["per_replica"]) == 8
    for s in stats["per_replica"]:
        for key in ("replica", "healthy", "requests", "queries", "shed",
                    "scan_input_wait_frac", "stages"):
            assert key in s
    assert stats["latency_p99_ms"] >= stats["latency_p50_ms"]


# ---------------------------------------------------------------------------
# robustness: deadlines, stuck-scan watchdog, retry, degradation
# ---------------------------------------------------------------------------


def test_ticket_result_timeout_then_late_resolution_no_leaks():
    """result(timeout=) raising TimeoutError must not consume the ticket:
    a later resolution still lands, exactly once, and runs each done
    callback exactly once (no leaked callback registrations)."""
    from repro.launch.serving import Ticket

    t = Ticket(0, 4)
    with pytest.raises(TimeoutError, match="not ready"):
        t.result(timeout=0.05)
    assert not t.done()
    fired = []
    t.add_done_callback(lambda tk: fired.append("a"))
    t.add_done_callback(lambda tk: fired.append("b"))
    assert t._resolve(value=("v", "i")) is True
    assert t.result(timeout=1) == ("v", "i")
    # second resolution loses: value not clobbered, callbacks not re-run
    assert t._resolve(error=RuntimeError("late loser")) is False
    assert t.result() == ("v", "i") and t.error() is None
    assert fired == ["a", "b"] and t._callbacks == []
    # post-resolution registration fires immediately, exactly once
    t.add_done_callback(lambda tk: fired.append("c"))
    assert fired == ["a", "b", "c"] and t._callbacks == []


def test_watchdog_fails_over_stuck_scan_without_loss_or_reorder():
    """A scan that HANGS (never raises) must not deadlock the tier: the
    watchdog marks the replica unhealthy (ScanStalled) and failover
    re-dispatches its in-flight tickets to the survivor — every ticket
    resolves, in order, bit-identical."""
    calls = []
    stuck = FaultInjector(*_identity_replica(0, calls),
                          plan=FaultPlan.stick_at(0), name="r0")
    router = QueryRouter(
        ReplicaSet([stuck.pair, _identity_replica(1, calls)],
                   config=ServingConfig(queue_depth=16)),
        policy="round-robin",
    )
    try:
        router.start_watchdogs(0.1)
        tickets = [router.submit(b) for b in _batches(8)]
        results = [t.result(timeout=30) for t in tickets]
        _check_identity(results, 8)  # nothing lost, FIFO per client
        assert router.wait_state(0, ("unhealthy",), timeout=10)
        stats = router.stats()
        assert stats["watchdog_stalls"] >= 1
        assert stats["failovers"] >= 1
        assert isinstance(router._errors[0], ScanStalled)
        # the survivor answered everything; the stuck scan answered none
        assert all(r == 1 for (r, _) in calls)
    finally:
        stuck.release()  # un-wedge the scan thread before close() joins
        router.close()


def test_deadline_expired_sheds_at_dequeue_replica_stays_healthy():
    """Tickets whose deadline passes while queued are shed at dequeue —
    counted as deadline_expired (not queue sheds, not failures), never
    scanned, and the replica stays healthy."""
    calls = []
    router = QueryRouter(
        ReplicaSet([_identity_replica(0, calls, scan_sleep=0.2)],
                   config=ServingConfig(queue_depth=8)),
    )
    try:
        deadline = time.perf_counter() + 0.05
        tickets = [router.submit(b, deadline=deadline) for b in _batches(4)]
        outcomes = []
        for t in tickets:
            try:
                t.result(timeout=30)
                outcomes.append("ok")
            except DeadlineExpired:
                outcomes.append("expired")
        # the first batch was dequeued before the deadline; the ones
        # stuck behind its slow scan expired un-scanned
        assert outcomes[0] == "ok" and outcomes.count("expired") == 3
        assert len(calls) == 1  # expired work never reached the scan
        stats = router.stats()
        assert stats["deadline_expired"] == 3
        assert stats["shed"] == 0 and stats["failovers"] == 0
        assert router.healthy() == [0]  # a missed budget is not a fault
    finally:
        router.close()


def test_submit_rejects_already_expired_deadline():
    router = QueryRouter(ReplicaSet([_identity_replica(0)]))
    try:
        with pytest.raises(DeadlineExpired, match="already expired"):
            router.submit(_batches(1)[0],
                          deadline=time.perf_counter() - 1.0)
        stats = router.stats()
        assert stats["deadline_expired"] == 1
        assert stats["requests"] == 0  # never reached a replica
    finally:
        router.close()


def _gated_tier(n_extra_queued=1, clock=None):
    """One replica whose encode blocks on a gate, with its admission
    queue then filled: the next submit must shed tier-wide."""
    gate = threading.Event()
    started = threading.Event()

    def encode(x):
        started.set()
        gate.wait(timeout=30)
        return x

    def search(c):
        return c * 2, c + 1

    kw = {} if clock is None else {"clock": clock}
    router = QueryRouter(
        ReplicaSet([(encode, search)],
                   config=ServingConfig(queue_depth=n_extra_queued,
                                        policy="shed")),
        **kw,
    )
    head, *rest = _batches(1 + n_extra_queued)
    tickets = [router.submit(head)]
    # only fill the queue once the encode thread holds the head batch,
    # or the filler itself would race the dequeue and shed
    assert started.wait(timeout=5)
    tickets += [router.submit(b) for b in rest]
    return router, gate, tickets


def test_submit_with_retry_succeeds_once_pressure_clears():
    """Runs on FakeClock: the retry parks on the simulated clock, the
    gate opens mid-backoff, and the test hands it time to retry."""
    clk = FakeClock()
    router, gate, tickets = _gated_tier(clock=clk)
    try:
        result = {}

        def work():
            t = router.submit_with_retry(
                _batches(3)[2], attempts=20, base_delay_s=0.5,
                max_delay_s=2.0,
            )
            result["vals"] = t.result(timeout=10)[0]

        w = threading.Thread(target=work)
        w.start()
        # saturated right now -> the first attempt sheds and the retry
        # parks on the clock for its backoff
        clk.wait_for_sleepers(1)
        assert router.shed_count >= 1  # it genuinely shed before landing
        gate.set()  # pressure clears while the retry is backing off
        deadline = time.time() + 10
        while w.is_alive() and time.time() < deadline:
            clk.advance(2.0)  # serve out the current backoff (jitter incl.)
            time.sleep(0.005)
        w.join(timeout=10)
        assert not w.is_alive()
        np.testing.assert_array_equal(np.asarray(result["vals"]),
                                      np.full((4,), 4))
        for tk in tickets:
            tk.result(timeout=10)
    finally:
        gate.set()
        router.close()


def test_submit_with_retry_deadline_cuts_backoff_short():
    clk = FakeClock()
    router, gate, tickets = _gated_tier(clock=clk)
    try:
        t0 = clk.now()
        with pytest.raises(DeadlineExpired, match="retry backoff"):
            router.submit_with_retry(
                _batches(3)[2], deadline=clk.now() + 0.05,
                attempts=50, base_delay_s=0.2, jitter=0.0,
            )
        # failed by deadline MATH: simulated time never moved, so not a
        # single second of the 50 x 0.2s backoff schedule was served
        assert clk.now() == t0
        assert router.stats()["deadline_expired"] >= 1
    finally:
        gate.set()
        router.close()


def test_submit_with_retry_terminal_errors_propagate_immediately():
    router = QueryRouter(
        ReplicaSet([_identity_replica(i, fail_after=0) for i in range(2)],
                   config=ServingConfig(queue_depth=4)),
    )
    try:
        t = router.submit(_batches(1)[0])
        with pytest.raises(RuntimeError, match="injected fail"):
            t.result(timeout=15)
        assert router.healthy() == []
        t0 = time.perf_counter()
        with pytest.raises(AllReplicasDown):
            router.submit_with_retry(_batches(2)[1], attempts=8,
                                     base_delay_s=0.2)
        assert time.perf_counter() - t0 < 1.0  # no backoff on terminal
    finally:
        router.close()


def test_transiently_empty_tier_sheds_retryable_under_deadline_path():
    """RequestShed (retryable) vs AllReplicasDown (terminal) must stay
    distinguishable when submits carry deadlines: a tier that is merely
    draining sheds; a tier that is dead raises AllReplicasDown."""
    router = QueryRouter(ReplicaSet([_identity_replica(0)],
                                    config=ServingConfig(queue_depth=4)))
    try:
        deadline = time.perf_counter() + 30.0
        router.drain(0, timeout=5)  # healthy -> draining: tier empty
        with pytest.raises(RequestShed, match="no routable replica"):
            router.submit(_batches(1)[0], deadline=deadline)
        router.mark_unhealthy(0, RuntimeError("boom"))
        with pytest.raises(AllReplicasDown):
            router.submit(_batches(1)[0], deadline=deadline)
    finally:
        router.close()


def test_stop_health_probe_raises_when_probe_thread_is_wedged():
    """A probe wedged on a stuck canary must make stop_health_probe fail
    LOUDLY (the old silent join timeout leaked a daemon thread that kept
    reviving replicas behind the caller's back)."""
    clk = FakeClock()
    stuck = FaultInjector(*_identity_replica(0),
                          plan=FaultPlan.stick_at(0), name="r0")
    router = QueryRouter(
        ReplicaSet([stuck.pair], config=ServingConfig(queue_depth=4)),
        clock=clk,
    )
    try:
        router.mark_unhealthy(0, RuntimeError("down"))
        router.start_health_probe(_batches(1)[0], interval=1.0,
                                  timeout=30.0)
        clk.wait_for_sleepers(1)
        clk.advance(1.0)  # first tick: the probe dives into the canary
        deadline = time.time() + 10
        while time.time() < deadline and stuck.stuck_count == 0:
            time.sleep(0.005)
        assert stuck.stuck_count == 1  # the probe is wedged in the canary
        with pytest.raises(RuntimeError, match="did not exit"):
            router.stop_health_probe(timeout=0.05)
        # the hang clears: the wedged probe completes, revives the
        # replica, sees the stop flag, and the thread exits for real
        stuck.release()
        assert router.wait_state(0, ("healthy",), timeout=10)
    finally:
        stuck.release()
        router.close()


def test_flap_suppression_backs_off_a_permanently_failing_replica():
    """Runs on FakeClock: the probe loop is handed exactly one simulated
    second per tick, so the backoff schedule is counted, not raced."""
    clk = FakeClock()
    flaky = FaultInjector(*_identity_replica(1),
                          plan=FaultPlan.fail_after(0), name="r1")
    router = QueryRouter(
        ReplicaSet([_identity_replica(0), flaky.pair],
                   config=ServingConfig(queue_depth=8)),
        clock=clk,
    )
    try:
        tickets = [router.submit(b) for b in _batches(4)]
        for t in tickets:
            t.result(timeout=15)  # failover absorbs replica 1's faults
        assert router.wait_state(1, ("unhealthy",), timeout=10)
        router.start_health_probe(_batches(1)[0], interval=1.0,
                                  timeout=2.0)
        for _ in range(16):  # 16 simulated seconds, lockstep with the loop
            clk.tick(1.0)
        fails = router.probe_failures().get(1, 0)
        # without backoff 16 ticks = 16 probes; with 1x,2x,4x... spacing
        # the probe lands at t=1,2,4,8,16 — and it must have actually
        # retried, not given up after the first failure
        assert 2 <= fails <= 6, fails
        assert router.states()[1] == "unhealthy"
    finally:
        router.close()


def test_degradation_steps_down_before_shedding_and_back_up():
    gate = threading.Event()
    started = threading.Event()

    def encode(x):
        started.set()
        gate.wait(timeout=30)
        return x

    def search(c):
        return c * 2, c + 1

    knob = EffortKnob(2)
    router = QueryRouter(
        ReplicaSet([(encode, search)],
                   config=ServingConfig(queue_depth=1, policy="shed")),
    )
    router.enable_degradation(knob, high_water=0.5, low_water=0.0)
    try:
        b = _batches(4)
        t0 = router.submit(b[0])  # encode gated: 1 outstanding
        assert started.wait(timeout=5)
        t1 = router.submit(b[1])  # pressure 1.0 >= 0.5: degrades first
        assert knob.level == 1 and knob.degrade_count == 1
        # queue now full and the knob is at its floor: the shed is real
        with pytest.raises(RequestShed):
            router.submit(b[2])
        assert router.stats()["effort_level"] == 1
        gate.set()
        _check_identity([t0.result(timeout=10), t1.result(timeout=10)], 2)
        # dispatches served while degraded were counted
        assert router.stats()["degraded"] >= 1
        # pressure cleared: the next submit restores full effort
        t3 = router.submit(b[0])
        assert knob.level == 0 and knob.restore_count == 1
        t3.result(timeout=10)
        assert router.stats()["effort_level"] == 0
    finally:
        gate.set()
        router.close()


# ---------------------------------------------------------------------------
# replica submeshes
# ---------------------------------------------------------------------------


def test_make_replica_meshes_partitions_disjoint_devices():
    meshes = make_replica_meshes(1, shape=(1, 1))
    assert len(meshes) == 1 and meshes[0].devices.size == 1
    n = len(jax.devices())
    with pytest.raises(RuntimeError, match="need"):
        make_replica_meshes(n + 1, shape=(1, 1))


def test_engine_replicas_on_submeshes_route_and_fail_over():
    """End-to-end tier over the distributed engine: 2 replicas on
    disjoint (2,1) submeshes of 4 forced host devices, each sharding the
    whole corpus over its own leaves. Routed results must equal the
    exact top-k, and killing one replica mid-stream must lose nothing
    (a replica holds the whole corpus: failover costs a retry, not
    recall)."""
    import os
    import subprocess
    import sys
    import textwrap

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = src
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.index.engine import (
                engine_input_shardings, make_distributed_search)
            from repro.kernels.sdc import ref as R
            from repro.launch import proxy, serving
            from repro.launch.mesh import make_replica_meshes

            key = jax.random.PRNGKey(0)
            codes = jax.random.randint(key, (2048, 64), 0, 16).astype(jnp.int8)
            q = jax.random.randint(jax.random.fold_in(key, 1), (32, 64), 0,
                                   16).astype(jnp.int8)
            inv = R.doc_inv_norms(codes, 4)

            fail_at = [None]  # scan-call countdown for the dying replica

            def make_replica(mesh, dies=False):
                search = make_distributed_search(mesh, n_levels=4, k=10)
                qspec, *in_specs = engine_input_shardings(mesh)
                ins = [jax.device_put(a, s)
                       for a, s in zip((codes, inv), in_specs)]
                count = [0]
                def search_one(qc):
                    if dies:
                        count[0] += 1
                        if fail_at[0] is not None and count[0] > fail_at[0]:
                            raise RuntimeError("replica leaf crashed")
                    return search(qc, *ins)
                encode = lambda e: jax.device_put(jnp.asarray(e), qspec)
                return encode, search_one

            meshes = make_replica_meshes(2, shape=(2, 1))
            assert not (set(meshes[0].devices.flat)
                        & set(meshes[1].devices.flat))
            replicas = [make_replica(meshes[0]),
                        make_replica(meshes[1], dies=True)]
            batches = [q[i:i+8] for i in range(0, 32, 8)]
            serving.warmup_replicas(replicas, batches)

            ev, ei = jax.lax.top_k(R.sdc_ref(q, codes, 4), 10)

            # healthy tier: routed == exact
            results, stats = proxy.serve_replicated(replicas, batches * 2)
            ids = np.concatenate(
                [np.asarray(i) for _, i in results[:len(batches)]], 0)
            np.testing.assert_array_equal(ids, np.asarray(ei))
            assert stats["healthy"] == [0, 1]

            # replica 1 dies after its first scan of the next stream
            fail_at[0] = 0
            results, stats = proxy.serve_replicated(replicas, batches * 2)
            assert stats["healthy"] == [0], stats["healthy"]
            assert stats["requests"] == 2 * len(batches)
            for r, (bv, bi) in enumerate(results):
                exp = np.asarray(ei)[(r % len(batches)) * 8:
                                     (r % len(batches)) * 8 + 8]
                np.testing.assert_array_equal(np.asarray(bi), exp)
            print("ENGINE-REPLICA-OK")
        """)],
        capture_output=True, text=True, env=env, timeout=500,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ENGINE-REPLICA-OK" in out.stdout
