"""Shed-pressure autoscaler (launch/autoscale.py) + the injectable
clock it runs on (launch/clock.py).

Every timing property here — hysteresis, cooldown spacing, backoff
interruption — is proven on a ``FakeClock`` by advancing simulated
time, never by sleeping real time: the only real waits are the fake
clock's millisecond poll quantum and thread joins on work that has
already been released.
"""

import threading
import time
import random

import numpy as np
import pytest

from repro.launch.autoscale import (
    ADMISSION_POLICIES,
    Autoscaler,
    InvalidTierSpec,
    TierSpec,
)
from repro.launch.clock import SYSTEM_CLOCK, Clock, FakeClock, SystemClock
from repro.launch.proxy import QueryRouter, ReplicaSet
from repro.launch.serving import PipelineClosed, RequestShed, ServingConfig

LEVELS = 4


def _identity_pair(calls=None, tag="r"):
    def encode(x):
        return x

    def search(c):
        if calls is not None:
            calls.append((tag, int(np.asarray(c).ravel()[0])))
        return c * 2, c + 1

    return encode, search


def _batches(n=8, width=4):
    return [np.full((width,), i, dtype=np.int64) for i in range(n)]


def _tier(clk, n=1, queue_depth=4, policy="shed"):
    return QueryRouter(
        ReplicaSet([_identity_pair() for _ in range(n)],
                   config=ServingConfig(queue_depth=queue_depth,
                                        policy=policy)),
        clock=clk,
    )


def _scaler(router, spec, clk, pressure, **kw):
    """Autoscaler over identity replicas with a synthetic pressure
    signal; ``pressure`` is a mutable one-element list the test sets."""
    kw.setdefault("replica_factory", lambda slot: _identity_pair())
    kw.setdefault("warm_batches", _batches(1))
    return Autoscaler(router, spec, clock=clk,
                      pressure_fn=lambda: pressure[0], **kw)


# ---------------------------------------------------------------------------
# FakeClock semantics
# ---------------------------------------------------------------------------


def test_clock_protocol_is_satisfied_by_both_implementations():
    assert isinstance(SYSTEM_CLOCK, Clock)
    assert isinstance(SystemClock(), Clock)
    assert isinstance(FakeClock(), Clock)


def test_fake_clock_now_moves_only_on_advance():
    clk = FakeClock(start=100.0)
    assert clk.now() == 100.0
    clk.advance(2.5)
    assert clk.now() == 102.5
    with pytest.raises(ValueError, match="backwards"):
        clk.advance(-0.1)


def test_fake_clock_sleep_parks_until_advance():
    clk = FakeClock()
    woke = []
    th = threading.Thread(target=lambda: (clk.sleep(5.0), woke.append(1)))
    th.start()
    assert clk.wait_for_sleepers(1)
    assert not woke  # simulated time has not moved: still parked
    clk.advance(4.9)
    assert th.is_alive()
    clk.advance(0.1)  # deadline reached exactly
    th.join(timeout=5)
    assert woke == [1]
    assert clk.sleepers == 0


def test_fake_clock_wait_is_level_triggered_on_the_event():
    clk = FakeClock()
    ev = threading.Event()
    ev.set()
    t0 = clk.now()
    assert clk.wait(ev, 60.0) is True  # no advance needed
    assert clk.now() == t0


def test_fake_clock_wait_times_out_on_simulated_time():
    clk = FakeClock()
    ev = threading.Event()
    out = []
    th = threading.Thread(target=lambda: out.append(clk.wait(ev, 3.0)))
    th.start()
    assert clk.wait_for_sleepers(1)
    clk.advance(3.0)
    th.join(timeout=5)
    assert out == [False]  # timed out; the event never fired


def test_fake_clock_wait_wakes_on_event_set_without_advance():
    clk = FakeClock()
    ev = threading.Event()
    out = []
    th = threading.Thread(target=lambda: out.append(clk.wait(ev, 1e9)))
    th.start()
    assert clk.wait_for_sleepers(1)
    ev.set()  # production interrupt path: no clock advance at all
    th.join(timeout=5)
    assert out == [True]


def test_fake_clock_tick_hands_a_loop_exactly_one_interval():
    clk = FakeClock()
    stop = threading.Event()
    iters = []
    th = threading.Thread(
        target=lambda: [iters.append(1)
                        for _ in iter(lambda: clk.wait(stop, 1.0), True)])
    th.start()
    for _ in range(3):
        clk.tick(1.0)
    assert len(iters) == 3  # lockstep: one wake per tick, no more
    stop.set()
    th.join(timeout=5)


# ---------------------------------------------------------------------------
# TierSpec validation
# ---------------------------------------------------------------------------


def test_tier_spec_defaults_validate_and_round_trip():
    spec = TierSpec(min_replicas=1, max_replicas=3,
                    build_params={"k": 5})
    again = TierSpec.from_json(__import__("json").dumps(spec.to_dict()))
    assert again == spec
    assert spec.window_ticks == 3  # 3.0s window / 1.0s tick


def test_tier_spec_backend_reaches_every_builder():
    # "auto" resolves to the Pallas kernel on a TPU; a spec never falls
    # back to a builder default of its own.
    assert TierSpec().make_index_builder().params["backend"] == "auto"
    spec = TierSpec(index="ivf", backend="xla", build_params={"nlist": 8})
    assert spec.make_index_builder().params["backend"] == "xla"


def test_tier_spec_window_ticks_rounds_and_floors_at_one():
    assert TierSpec(window_s=0.1, tick_s=0.05).window_ticks == 2
    assert TierSpec(window_s=1.0, tick_s=1.0).window_ticks == 1


@pytest.mark.parametrize("bad", [
    dict(min_replicas=0),
    dict(min_replicas=True),                 # bool is not an int here
    dict(min_replicas=2, max_replicas=1),
    dict(max_replicas=2.0),                  # float replica count
    dict(queue_depth=0),
    dict(policy="drop"),
    dict(router="hash-ring"),
    dict(high_water=0.3, low_water=0.3),     # need low < high
    dict(high_water=1.5),
    dict(low_water=-0.1),
    dict(tick_s=0.0),
    dict(window_s=0.5, tick_s=1.0),          # window shorter than a tick
    dict(cooldown_s=-1.0),
    dict(swap_every_s=-5.0),
    dict(build_params=[("k", 5)]),           # not a dict
    dict(index="pq"),                        # unknown index kind
    dict(index="flat", build_params={"nlist": 8}),  # flat has no nlist
    dict(backend="tpu"),                     # not an SDC backend
    dict(build_params={"backend": "xla"}),   # backend is the spec's own key
])
def test_tier_spec_rejects_malformed_fields_with_typed_error(bad):
    with pytest.raises(InvalidTierSpec):
        TierSpec(**bad)
    # the typed error still reads as a ValueError for generic handlers
    assert issubclass(InvalidTierSpec, ValueError)


def test_tier_spec_error_names_the_field():
    with pytest.raises(InvalidTierSpec, match="queue_depth"):
        TierSpec(queue_depth=-1)
    with pytest.raises(InvalidTierSpec, match="low_water"):
        TierSpec(high_water=0.2, low_water=0.4)
    with pytest.raises(InvalidTierSpec, match=str(ADMISSION_POLICIES)[1:-1]):
        TierSpec(policy="bogus")


def test_tier_spec_from_dict_rejects_unknown_keys_and_non_objects():
    with pytest.raises(InvalidTierSpec, match="unknown tier spec keys"):
        TierSpec.from_dict({"min_replicas": 1, "replicas": 3})
    with pytest.raises(InvalidTierSpec, match="JSON object"):
        TierSpec.from_dict([1, 2, 3])


def test_tier_spec_from_json_rejects_malformed_json():
    with pytest.raises(InvalidTierSpec, match="not valid JSON"):
        TierSpec.from_json("{min_replicas: 1")


def test_tier_spec_from_file_round_trips(tmp_path):
    spec = TierSpec(min_replicas=1, max_replicas=2, index="flat",
                    build_params={"k": 7}, high_water=0.6, low_water=0.2)
    p = tmp_path / "spec.json"
    p.write_text(__import__("json").dumps(spec.to_dict()))
    assert TierSpec.from_file(str(p)) == spec


# ---------------------------------------------------------------------------
# hysteresis: a noisy trace must not flap the tier
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("low,high", [(0.1, 0.5), (0.2, 0.6), (0.3, 0.7)])
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_hysteresis_never_flaps_under_an_oscillating_noise_trace(
        low, high, seed):
    """Samples alternate ABOVE high water and BELOW low water — the
    worst case for a per-sample controller, which would scale on every
    tick. The window mean stays inside the deadband, so the windowed
    controller must take zero scaling actions over the whole trace."""
    rng = random.Random(seed)
    mid = (low + high) / 2
    amp = 1.2 * (high - low)
    clk = FakeClock()
    router = _tier(clk, n=2)
    spec = TierSpec(min_replicas=1, max_replicas=3, low_water=low,
                    high_water=high, cooldown_s=0.0, window_s=4.0,
                    tick_s=1.0)
    pressure = [mid]
    scaler = _scaler(router, spec, clk, pressure)
    try:
        sign = 1
        crossings = 0
        for _ in range(60):
            # jittered amplitude, strictly alternating sign: every
            # sample individually crosses a threshold...
            a = amp * (0.8 + 0.4 * rng.random())
            pressure[0] = min(1.0, max(0.0, mid + sign * a))
            crossings += (pressure[0] >= high or pressure[0] <= low)
            sign = -sign
            scaler.tick()
            clk.advance(spec.tick_s)
        assert crossings == 60  # the trace really was threshold-crossing
        # ...yet the windowed mean never left the deadband: no actions
        assert scaler.scale_up_count == 0
        assert scaler.scale_down_count == 0
        assert len(router.active_replicas()) == 2
        decisions = {e["decision"] for e in scaler.events}
        assert decisions <= {"warming", "hold"}
    finally:
        router.close()


def test_sustained_pressure_does_scale_up_with_the_same_thresholds():
    """Companion to the no-flap property: the deadband must not be so
    wide that a REAL sustained burst is ignored."""
    clk = FakeClock()
    router = _tier(clk, n=1)
    spec = TierSpec(min_replicas=1, max_replicas=2, low_water=0.1,
                    high_water=0.5, cooldown_s=0.0, window_s=4.0,
                    tick_s=1.0)
    pressure = [0.9]
    scaler = _scaler(router, spec, clk, pressure)
    try:
        outcomes = []
        for _ in range(4):
            outcomes.append(scaler.tick())
            clk.advance(1.0)
        assert outcomes == ["warming", "warming", "warming", "scale-up"]
        assert len(router.active_replicas()) == 2
    finally:
        router.close()


# ---------------------------------------------------------------------------
# cooldown
# ---------------------------------------------------------------------------


def test_cooldown_spaces_consecutive_scale_ups():
    clk = FakeClock()
    router = _tier(clk, n=1)
    spec = TierSpec(min_replicas=1, max_replicas=3, low_water=0.1,
                    high_water=0.5, cooldown_s=10.0, window_s=1.0,
                    tick_s=1.0)
    pressure = [0.9]
    scaler = _scaler(router, spec, clk, pressure)
    try:
        decisions = []
        for _ in range(12):
            decisions.append(scaler.tick())
            clk.advance(1.0)
        # t=0 scale-up; t=1..9 inside the 10s cooldown; t=10 scale-up
        assert decisions[0] == "scale-up"
        assert decisions[1:10] == ["cooldown"] * 9
        assert decisions[10] == "scale-up"
        assert scaler.scale_up_count == 2
        assert len(router.active_replicas()) == 3
    finally:
        router.close()


def test_window_resets_after_an_action():
    """Post-action decisions must not re-consume the pre-action burst:
    after a scale-up the window refills from scratch (decision goes
    back to 'warming'), even with cooldown disabled."""
    clk = FakeClock()
    router = _tier(clk, n=1)
    spec = TierSpec(min_replicas=1, max_replicas=3, low_water=0.1,
                    high_water=0.5, cooldown_s=0.0, window_s=2.0,
                    tick_s=1.0)
    pressure = [0.9]
    scaler = _scaler(router, spec, clk, pressure)
    try:
        assert scaler.tick() == "warming"
        clk.advance(1.0)
        assert scaler.tick() == "scale-up"
        clk.advance(1.0)
        pressure[0] = 0.3  # burst settles to mid-band right after
        assert scaler.tick() == "warming"  # old samples were discarded
        clk.advance(1.0)
        assert scaler.tick() == "hold"  # full window again, all mid-band
    finally:
        router.close()


# ---------------------------------------------------------------------------
# min/max bounds
# ---------------------------------------------------------------------------


def test_scaling_respects_min_and_max_bounds():
    clk = FakeClock()
    router = _tier(clk, n=1)
    spec = TierSpec(min_replicas=1, max_replicas=2, low_water=0.1,
                    high_water=0.5, cooldown_s=0.0, window_s=1.0,
                    tick_s=1.0)
    pressure = [1.0]
    scaler = _scaler(router, spec, clk, pressure)
    try:
        for _ in range(6):
            scaler.tick()
            clk.advance(1.0)
        # pegged pressure: one scale-up to max, then hold — never above
        assert scaler.scale_up_count == 1
        assert len(router.active_replicas()) == 2
        pressure[0] = 0.0
        for _ in range(6):
            scaler.tick()
            clk.advance(1.0)
        # dead quiet: one scale-down to min, then hold — never below
        assert scaler.scale_down_count == 1
        assert len(router.active_replicas()) == 1
        assert scaler.max_replicas_seen <= spec.max_replicas
        assert scaler.min_replicas_seen >= spec.min_replicas
    finally:
        router.close()


def test_bounds_enforcement_outruns_cooldown():
    """A tier outside its spec bounds is wrong, not noisy: enforcement
    acts immediately even while a cooldown is pending."""
    clk = FakeClock()
    router = _tier(clk, n=3)  # three replicas, spec allows two
    spec = TierSpec(min_replicas=1, max_replicas=2, low_water=0.1,
                    high_water=0.9, cooldown_s=1000.0, window_s=1.0,
                    tick_s=1.0)
    pressure = [0.5]
    scaler = _scaler(router, spec, clk, pressure)
    try:
        assert scaler.tick() == "above-max"
        assert len(router.active_replicas()) == 2
        # in bounds again: ordinary hysteresis (and its cooldown) resume
        clk.advance(1.0)
        assert scaler.tick() == "cooldown"
    finally:
        router.close()


def test_below_min_scales_up_immediately():
    clk = FakeClock()
    router = _tier(clk, n=1)
    spec = TierSpec(min_replicas=2, max_replicas=3, cooldown_s=1000.0,
                    window_s=1.0, tick_s=1.0)
    pressure = [0.0]
    scaler = _scaler(router, spec, clk, pressure)
    try:
        assert scaler.tick() == "below-min"
        assert len(router.active_replicas()) == 2
        assert sorted(router.healthy()) == [0, 1]
    finally:
        router.close()


# ---------------------------------------------------------------------------
# scale-down drains losslessly
# ---------------------------------------------------------------------------


def test_scale_down_drains_in_flight_work_losslessly():
    """Tickets queued on the victim replica when the scale-down lands
    must all resolve with correct answers — drained or re-dispatched,
    never dropped, never reordered."""
    clk = FakeClock()
    gate = threading.Event()
    first_in = threading.Event()

    def slow_pair(tag):
        def encode(x):
            return x

        def search(c):
            first_in.set()
            gate.wait(timeout=30)  # hold scans so work is truly in flight
            return c * 2, c + 1

        return encode, search

    router = QueryRouter(
        ReplicaSet([slow_pair(0), slow_pair(1)],
                   config=ServingConfig(queue_depth=8, policy="block")),
        clock=clk,
    )
    spec = TierSpec(min_replicas=1, max_replicas=2, low_water=0.1,
                    high_water=0.9, cooldown_s=0.0, window_s=1.0,
                    tick_s=1.0)
    pressure = [0.0]
    scaler = _scaler(router, spec, clk, pressure)
    try:
        batches = _batches(8)
        tickets = [router.submit(b) for b in batches]  # spread over both
        assert first_in.wait(timeout=10)
        # scale-down decides while replica 1 still holds queued work;
        # retire_replica drains, so the tick blocks until it is empty
        done = []
        th = threading.Thread(
            target=lambda: done.append(scaler.tick()))
        th.start()
        time.sleep(0.01)  # let the drain begin before releasing scans
        gate.set()
        th.join(timeout=30)
        assert done == ["scale-down"]
        assert router.states()[1] == "retired"
        results = [t.result(timeout=30) for t in tickets]
        for b, (vals, ids) in zip(batches, results):  # zero lost/reordered
            np.testing.assert_array_equal(np.asarray(vals), b * 2)
            np.testing.assert_array_equal(np.asarray(ids), b + 1)
        # the tier keeps serving on the survivor
        vals, ids = router.submit(batches[0]).result(timeout=10)
        np.testing.assert_array_equal(np.asarray(ids), batches[0] + 1)
    finally:
        gate.set()
        router.close()


def test_scale_down_never_retires_the_last_routable_replica():
    clk = FakeClock()
    router = _tier(clk, n=2)
    spec = TierSpec(min_replicas=1, max_replicas=2, low_water=0.1,
                    high_water=0.9, cooldown_s=0.0, window_s=1.0,
                    tick_s=1.0)
    pressure = [0.0]
    scaler = _scaler(router, spec, clk, pressure)
    try:
        assert scaler.tick() == "scale-down"  # 2 -> 1: fine
        clk.advance(1.0)
        # n == min_replicas now: the decision path refuses to go lower
        for _ in range(3):
            assert scaler.tick() == "hold"
            clk.advance(1.0)
        assert len(router.healthy()) == 1
    finally:
        router.close()


# ---------------------------------------------------------------------------
# scale-up admission discipline: warmed + canary-probed before traffic
# ---------------------------------------------------------------------------


def test_scale_up_replica_is_warmed_and_probed_before_traffic():
    clk = FakeClock()
    calls = []  # every batch the NEW replica's stages ever see, in order
    router = _tier(clk, n=1)
    spec = TierSpec(min_replicas=1, max_replicas=2, low_water=0.1,
                    high_water=0.5, cooldown_s=0.0, window_s=1.0,
                    tick_s=1.0)
    warm = [np.full((4,), 100, dtype=np.int64)]
    canary = np.full((4,), 200, dtype=np.int64)

    def factory(slot):
        def encode(x):
            return x

        def search(c):
            calls.append(int(np.asarray(c).ravel()[0]))
            return c * 2, c + 1

        return encode, search

    pressure = [0.9]
    scaler = Autoscaler(router, spec, clock=clk,
                        replica_factory=factory, warm_batches=warm,
                        canary=canary, pressure_fn=lambda: pressure[0])
    try:
        assert scaler.tick() == "scale-up"
        # admission order: warm batches (tag 100) ran on the throwaway
        # pair, then the canary probe (tag 200) went through the
        # pipeline — and NO traffic batch precedes either of them
        assert 200 in calls
        first_canary = calls.index(200)
        assert first_canary >= 1  # warmed at least once before the probe
        assert set(calls[:first_canary]) == {100}
        n_admission = len(calls)
        # now route real traffic until the new replica serves some
        deadline = time.time() + 10
        while time.time() < deadline and len(calls) == n_admission:
            router.submit(_batches(1)[0]).result(timeout=10)
        assert len(calls) > n_admission  # takes traffic — but only after
        assert router.states()[1] == "healthy"
    finally:
        router.close()


def test_failed_canary_retires_the_slot_before_it_ever_serves():
    clk = FakeClock()
    served = []
    router = _tier(clk, n=1)
    spec = TierSpec(min_replicas=1, max_replicas=2, low_water=0.1,
                    high_water=0.5, cooldown_s=0.0, window_s=1.0,
                    tick_s=1.0)

    def broken_factory(slot):
        def encode(x):
            return x

        def search(c):
            served.append(int(np.asarray(c).ravel()[0]))
            raise RuntimeError("bad build")

        return encode, search

    pressure = [0.9]
    scaler = Autoscaler(router, spec, clock=clk,
                        replica_factory=broken_factory,
                        warm_batches=None, canary=_batches(1)[0],
                        pressure_fn=lambda: pressure[0])
    try:
        assert scaler.tick() == "scale-up-failed"
        assert scaler.probe_failures == 1
        assert router.states()[1] == "retired"  # tombstoned, not counted
        assert len(router.active_replicas()) == 1
        n_probe = len(served)  # only the canary ever reached it
        # traffic continues on the original replica; the dead slot is
        # never routed to again
        for b in _batches(4):
            router.submit(b).result(timeout=10)
        assert len(served) == n_probe
    finally:
        router.close()


# ---------------------------------------------------------------------------
# background loop + clock integration
# ---------------------------------------------------------------------------


def test_background_loop_ticks_on_the_clock_and_stops_cleanly():
    clk = FakeClock()
    router = _tier(clk, n=1)
    spec = TierSpec(min_replicas=1, max_replicas=2, low_water=0.1,
                    high_water=0.5, cooldown_s=0.0, window_s=2.0,
                    tick_s=0.5)
    pressure = [0.9]
    scaler = _scaler(router, spec, clk, pressure)
    try:
        scaler.start()
        scaler.start()  # idempotent while alive
        for _ in range(4):
            clk.tick(0.5)
        scaler.stop()
        assert len(scaler.events) == 4  # exactly one decision per tick
        assert scaler.scale_up_count == 1
        assert len(router.active_replicas()) == 2
    finally:
        router.close()


def test_router_close_interrupts_a_parked_retry_backoff():
    """The satellite fix: close() during a retry backoff must wake the
    waiter immediately (PipelineClosed), not wait out the delay — on
    the fake clock, 'immediately' means with NO time advance at all."""
    clk = FakeClock()
    gate = threading.Event()
    started = threading.Event()

    def encode(x):
        started.set()
        gate.wait(timeout=30)
        return x

    router = QueryRouter(
        ReplicaSet([(encode, lambda c: (c * 2, c + 1))],
                   config=ServingConfig(queue_depth=1, policy="shed")),
        clock=clk,
    )
    try:
        b = _batches(3)
        t0 = router.submit(b[0])
        assert started.wait(timeout=5)
        t1 = router.submit(b[1])  # fills the queue
        errs = []

        def work():
            try:
                router.submit_with_retry(b[2], attempts=10,
                                         base_delay_s=3600.0)
            except PipelineClosed as e:
                errs.append(e)
            except RequestShed as e:  # pragma: no cover - wrong path
                errs.append(e)

        th = threading.Thread(target=work)
        th.start()
        assert clk.wait_for_sleepers(1)  # parked on a one-HOUR backoff
        gate.set()
        before = clk.now()
        router.close()
        th.join(timeout=10)
        assert not th.is_alive()
        assert clk.now() == before  # zero simulated seconds were served
        assert len(errs) == 1 and isinstance(errs[0], PipelineClosed)
    finally:
        gate.set()
        router.close()


def test_autoscaler_requires_a_canary_and_a_replica_source():
    clk = FakeClock()
    router = _tier(clk, n=1)
    spec = TierSpec(min_replicas=1, max_replicas=2)
    try:
        with pytest.raises(ValueError, match="canary"):
            Autoscaler(router, spec, clock=clk,
                       replica_factory=lambda s: _identity_pair())
        with pytest.raises(ValueError, match="replica_factory"):
            Autoscaler(router, spec, clock=clk, canary=_batches(1)[0])
    finally:
        router.close()
