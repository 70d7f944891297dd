"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run of a cell on the CPU at a small size (the
jnp twin of the kernels in the program's place), skipping only the
look for a chip, with one fault planted in the served search, and sees
``correct`` come out false; the same run unbroken comes out true. The
lower-precision control (``control.py``) fails the limits too.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

SMALL = dict(n_docs=4096, corpus_chunk=1024, check_queries=64)


def _ivf(nprobe):
    return dict(SMALL, index={"family": "ivf", "builder": "IVFBuilder",
                              "params": {"nlist": 8, "nprobe": nprobe,
                                         "seed": 1, "packed": False}})


# At this size only probing every list finds the whole exact top-10.
IVF_SMALL = _ivf(8)


def _run(cell, wrap=None, overrides=SMALL, seed=2**31 + 3):
    return harness.run(cell, seed, 0.5, False, t_start=time.perf_counter(),
                       backend="xla", overrides=dict(overrides),
                       wrap_search=wrap, emit=lambda line: None)


def altered_answer(search):
    """One answer altered where it is produced: a neighbour's id."""
    def fn(q):
        s, i = search(q)
        return s, i.at[0, 0].set((i[0, 0] + 1) % SMALL["n_docs"])
    return fn


def half_batch(search):
    """Half of the batch left out: its rows get the other half's answers."""
    def fn(q):
        s, i = search(q)
        h = q.shape[0] // 2
        return (s.at[h:2 * h].set(s[:h]), i.at[h:2 * h].set(i[:h]))
    return fn


def stale_state(search):
    """A step that returns its state unchanged: every call after the
    first answers with the first call's result."""
    first = []

    def fn(q):
        if not first:
            first.append(search(q))
        return first[0]
    return fn


def fails_a_request(search):
    """A request that fails where it is served, as a shed, a timeout or a
    lost scan would: the fourth call raises (the warm-up makes two, one
    through each serving driver)."""
    calls = []

    def fn(q):
        calls.append(q.shape)
        if len(calls) == 4:
            raise RuntimeError("planted failure of one request")
        return search(q)
    return fn


@pytest.mark.parametrize("cell", ["web-flat.online", "web-flat.bulk"])
def test_a_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("fault", [altered_answer, half_batch, stale_state,
                                   fails_a_request])
def test_a_broken_search_is_not_correct(fault):
    res = _run("web-flat.online", wrap=fault)
    assert not res["correct"], res["checks"]
    if fault is fails_a_request:
        assert res["failed"] > 0
        assert res["checks"]["unanswered"]["value"] >= res["failed"]


@pytest.mark.parametrize("fault", ["altered_answer", "fewer_probes"])
def test_a_broken_ivf_search_is_not_correct(fault):
    assert _run("web-ivf.bulk", overrides=IVF_SMALL)["correct"]
    if fault == "altered_answer":
        res = _run("web-ivf.bulk", wrap=altered_answer, overrides=IVF_SMALL)
    else:
        # Probe selection that keeps one list of the eight it should:
        # every answer is an exact score in order, and the misses show.
        res = _run("web-ivf.bulk", overrides=_ivf(1))
        assert res["checks"]["answer_err"]["value"] <= 1e-5
    assert not res["correct"], res["checks"]


def test_a_sound_ivf_online_run_is_correct():
    res = _run("web-ivf.online", overrides=IVF_SMALL)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "recall_miss" in res["checks"]


@pytest.mark.parametrize("fault", [altered_answer, half_batch, stale_state,
                                   fails_a_request])
def test_a_broken_ivf_online_search_is_not_correct(fault):
    # The gather kernel's 8-query requests, the faults of the flat cell.
    res = _run("web-ivf.online", wrap=fault, overrides=IVF_SMALL)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell,overrides", [("web-flat.online", SMALL),
                                            ("web-ivf.bulk", IVF_SMALL)])
def test_the_lower_precision_control_is_not_correct(cell, overrides):
    from bench import control

    out = control.control_numbers(cell, 5, dict(overrides, check_queries=256))
    assert out["fails"], out["numbers"]


ENGINE_RUN = """
import sys, time, json
sys.path[:0] = [{root!r}, {src!r}]
import jax
if {broken}:
    jax.lax.all_gather = lambda x, *a, **k: x  # the exchange left out
from bench import harness
res = harness.run("web-flat.online", 2**31 + 9, 0.5, False,
                  t_start=time.perf_counter(), backend="xla",
                  overrides={small!r}, emit=lambda line: None)
print(json.dumps(res["correct"]))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_the_engine_without_its_exchange_is_not_correct(broken):
    # The sharded engine of bench/configs/web-flat-x4.json at a small size,
    # on four virtual CPU devices, which need a process of their own.
    with open(os.path.join(ROOT, "bench", "configs", "web-flat-x4.json")) as f:
        engine = dict(SMALL, index=json.load(f)["index"])
    code = ENGINE_RUN.format(root=ROOT, src=os.path.join(ROOT, "src"),
                             broken=broken, small=engine)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) is (not broken)


def test_the_percentile_and_rate_are_taken_over_every_request():
    from bench.traffic import Request

    assert harness.percentile(list(range(1, 101)), 95) == 95
    assert harness.percentile([5.0], 95) == 5.0
    reqs = [Request(queries=np.zeros((4, 1)), done=t)
            for t in (0.5, 1.0, 2.0, 3.0, 9.0)]
    # Answers at 1, 2 and 3 s inside a window of [0.8, 3.5]: every query
    # answered inside it, over the time from its opening to the last.
    assert harness.completed_rate(reqs, 0.8, 2.7) == pytest.approx(12 / 2.2)
    assert harness.completed_rate(reqs, 3.5, 1.0) == 0.0
