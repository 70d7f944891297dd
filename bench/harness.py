"""One run of one cell: set-up, the measured window, the check, the result.

Set-up draws the binarizer weights and the corpus from the seed on the
device, hands the corpus snapshot to the configuration's ``lifecycle``
builder on the device or, with ``"snapshot": "host"``, as a read-only
memmap in a directory that lives as long as the run, builds the index,
places the served encoder and search behind a one-replica
``proxy.QueryRouter`` at the program's default ``ServingConfig``, and
warms the cell's own batch shape. The window offers the cell's traffic
for ``--seconds``. Then the answers are checked against the plain
reference (``reference.py``) and the result line is printed.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import registry

GRACE_S = 60.0  # how long past the window an answer is awaited


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent cache where the program keeps it (the fixed
    ``<checkout>/.jax-comp-cache``, or ``JAX_COMPILATION_CACHE_DIR``), with
    every program kept, however fast it compiled."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable

    path, _ = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts the programs JAX traces and compiles, and the seconds it
    spends compiling, from JAX's monitoring events."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.traced = self.compiled = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event == self.TRACE:
            self.traced += 1
        elif event == self.COMPILE:
            self.compiled += 1
            self.compile_s += duration

    def snapshot(self):
        return self.traced, self.compiled, self.compile_s


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Served:
    encode: Callable  # the served encoder, recording what it produced
    search: Callable
    codes_of: Dict[int, object]  # id(query array) -> served codes
    weights: tuple
    corpus: object
    pool: np.ndarray


def _binarizer_config(cfg):
    from repro.core import BinarizerConfig

    b = cfg["binarizer"]
    return BinarizerConfig(input_dim=b["input_dim"], code_dim=b["code_dim"],
                           n_levels=b["n_levels"], hidden_dim=b["hidden_dim"])


def make_search(cfg: dict, snapshot, backend: str):
    """The served search of the configuration's index, from its builder."""
    from repro.launch import lifecycle

    idx = cfg["index"]
    params = dict(idx["params"])
    if idx["builder"] == "EngineBuilder":
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(tuple(idx["mesh"]))
        builder = lifecycle.EngineBuilder(
            [mesh], n_levels=cfg["binarizer"]["n_levels"], k=cfg["k"],
            backend=backend, **params)
    else:
        builder = getattr(lifecycle, idx["builder"])(
            k=cfg["k"], backend=backend, **params)
    return builder.build(snapshot)


def deployment(cfg: dict):
    """(params, state, corpus) of a configuration: every run serves the
    one corpus and binarizer of its ``corpus_seed``; the run's own seed
    draws only the queries and their order."""
    from bench import data

    seed = cfg["corpus_seed"]
    params, state = data.binarizer_weights(seed, cfg["binarizer"])
    return params, state, data.Corpus(seed, cfg, params, state)


def spill_codes(corpus, code_dim: int, path: str) -> np.ndarray:
    """The corpus codes in a read-only memmap at ``path``, written chunk
    by chunk: no copy of the whole corpus is ever on the device."""
    shape = (corpus.n_docs, code_dim)
    out = np.memmap(path, dtype=np.int8, mode="w+", shape=shape)
    for start, codes in corpus.chunks():
        out[start:start + codes.shape[0]] = np.asarray(codes)
    out.flush()
    del out
    return np.memmap(path, dtype=np.int8, mode="r", shape=shape)


def setup(cell, seed: int, backend: str, spill_dir: str) -> Served:
    """Draw and build the cell's deployment; a host snapshot is written
    under ``spill_dir``, which the caller removes when the run ends."""
    import jax

    from repro.core import binarize_lib
    from repro.launch.lifecycle import CorpusSnapshot

    cfg = cell.config
    where = cfg.get("snapshot", "device")
    if where not in ("device", "host"):
        raise ValueError(f"{cell.name}: snapshot {where!r} is neither "
                         f"'device' nor 'host'")
    t = time.perf_counter()
    params, state, corpus = deployment(cfg)
    if where == "device":
        codes = jax.block_until_ready(corpus.all_codes())
    else:
        # A deployment writes its cold tier at every restart, so the
        # spill is set-up.
        codes = spill_codes(corpus, cfg["binarizer"]["code_dim"],
                            os.path.join(spill_dir, "codes.int8"))
    log(f"[setup] weights and {corpus.n_docs} corpus codes drawn on the "
        f"device, snapshot on the {where}, in "
        f"{time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    snapshot = CorpusSnapshot(codes=codes, n_levels=cfg["binarizer"]["n_levels"])
    search = make_search(cfg, snapshot, backend)
    del snapshot, codes
    gc.collect()
    log(f"[setup] {cfg['index']['builder']} built in "
        f"{time.perf_counter() - t:.2f} s")

    enc = binarize_lib.make_encode_fn(params, state, _binarizer_config(cfg))
    precision = cfg["binarizer"]["matmul_precision"]
    codes_of: Dict[int, object] = {}

    def encode(x):
        # The configuration runs the encoder's float32 matmuls at this
        # precision; the served codes are kept to be checked.
        with jax.default_matmul_precision(precision):
            out = enc(x)
        codes_of[id(x)] = out
        return out

    pool = corpus.queries(cell.mix["pool"], seed)
    return Served(encode=encode, search=search, codes_of=codes_of,
                  weights=(params, state), corpus=corpus, pool=pool)


class UntraceableSearch(RuntimeError):
    """The served search is not one program that JAX can trace."""


def device_bytes(search, q_codes, cell_name: str) -> float:
    """Bytes on the chips of the served search at this batch shape: the
    device arrays it closes over plus the temporaries of its compiled
    program on every chip."""
    import jax
    from jax.extend import core as jex_core

    try:
        closed = jax.make_jaxpr(search)(q_codes)
    except (jax.errors.JAXTypeError, jax.errors.JAXIndexError) as e:
        raise UntraceableSearch(
            f"{cell_name}: the served search cannot be traced "
            f"({type(e).__name__}), so its device bytes cannot be counted. "
            f"The served search must be one traceable program, with its "
            f"host reads inside it (for example a host callback, "
            f"jax.pure_callback).") from e
    arrays = [c for c in closed.consts if isinstance(c, jax.Array)]
    resident = sum(s.data.nbytes for a in arrays for s in a.addressable_shards)

    def program(consts, q):
        return jex_core.jaxpr_as_fun(
            jex_core.ClosedJaxpr(closed.jaxpr, consts))(q)

    compiled = jax.jit(program).lower(closed.consts, q_codes).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    chips = len({d for a in arrays for d in a.devices()}) or 1
    return float(resident + temp * chips)


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------


def percentile(values: List[float], p: float) -> float:
    """Nearest-rank percentile."""
    v = sorted(values)
    return v[max(0, int(np.ceil(p / 100.0 * len(v))) - 1)]


def completed_rate(reqs, t0: float, seconds: float) -> float:
    """Queries answered inside the window, over the time from its opening
    to the last of those answers. A closed loop answers in whole requests,
    so a rate over the whole window would move in steps of one request
    (2.7% of web-flat.bulk's); this one moves with the time of each."""
    inside = [r for r in reqs if r.error is None and r.done is not None
              and t0 <= r.done <= t0 + seconds]
    if not inside:
        return 0.0
    return (sum(r.queries.shape[0] for r in inside)
            / (max(r.done for r in inside) - t0))


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def sample(cell, reqs, seed: int):
    """The answered requests the check compares, drawn from the seed:
    whole requests, ``check_queries`` queries in all at most."""
    done = [r for r in reqs if r.error is None and r.done is not None]
    n = min(len(done), max(1, cell.config["check_queries"]
                           // max(1, cell.mix["batch"])))
    pick = np.random.default_rng([seed, 13]).choice(len(done), size=n,
                                                   replace=False)
    return [done[i] for i in sorted(pick)]


def compare(cell, weights, corpus, queries, codes, scores, ids):
    """The numbers compared with their limits for one set of answers, the
    recall of the exact top-k, and the parts of ``answer_err``."""
    from bench import reference

    cfg = cell.config
    limits = cfg["limits"]
    n_levels = cfg["binarizer"]["n_levels"]
    margin = reference.code_margin(*weights, queries, codes, n_levels)
    top_v, top_i, ref_served = reference.exact_search(
        codes, ids, corpus.chunks(), n_levels=n_levels, k=cfg["k"],
        n_docs=cfg["n_docs"])
    # The limits name what the configuration promises. A family with a
    # reference of its own promises its top-k; every served score, the
    # order and the recall stay against the whole corpus.
    family = registry.reference(cell.family)
    promised = None
    if family is not None and "rank_gap" in limits:
        promised, _ = family.expected(cfg, codes, corpus)
    found = reference.compare(scores, ids, top_v, ref_served,
                              rank="rank_gap" in limits,
                              order="order_gap" in limits, promised=promised)
    parts = {"code_margin": margin, "score_err": found.pop("score_err")}
    # One number for the precision of an answer: a code bit on the wrong
    # side of zero and a score off the reference's are both relative
    # errors; either alone can read 0 under a lower precision.
    numbers = {"answer_err": max(parts.values()), **found}
    recall = float(np.mean([len(set(a) & set(b)) / len(a)
                            for a, b in zip(top_i.tolist(), ids.tolist())]))
    if "recall_miss" in limits:
        # Which documents were searched shows only in what the answers
        # miss.
        numbers["recall_miss"] = 1.0 - recall
    return numbers, recall, parts


def check(cell, served: Served, reqs, seed: int):
    """Compare a seeded sample of the served answers with the reference.
    Returns (numbers {name: value}, recall, queries compared)."""
    picked = sample(cell, reqs, seed)
    m = cell.config["binarizer"]["code_dim"]
    got = [served.codes_of.get(id(r.queries)) for r in picked]
    lost = sum(1 for c in got if c is None)
    codes = np.concatenate([
        np.zeros((r.queries.shape[0], m), np.int8) if c is None
        else np.asarray(c) for r, c in zip(picked, got)])
    queries = np.concatenate([r.queries for r in picked])
    numbers, recall, parts = compare(
        cell, served.weights, served.corpus, queries, codes,
        np.concatenate([np.asarray(r.scores) for r in picked]),
        np.concatenate([np.asarray(r.ids) for r in picked]))
    log("[check] parts of answer_err: " + json.dumps(parts))
    # A request that failed, was shed, timed out or was never answered, and
    # a sampled answer whose codes were never encoded, fail the check: a
    # dropped request must not pass as a fast one.
    missing = sum(1 for r in reqs if r.error is not None or r.done is None)
    numbers["unanswered"] = float(missing + lost)
    return numbers, recall, queries.shape[0]


# ---------------------------------------------------------------------------
# the traced run's per-layer metrics
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LayerContext:
    trace: object
    window: tuple  # (lo, hi) on the trace clock
    inflight: list  # requests' [sent, done] on the trace clock
    busy: dict  # {device: busy intervals in the window}
    busy_s: float  # busy seconds, averaged over chips
    n_requests: int  # requests answered inside the window
    least_s: float  # least time of those requests on these chips
    chips: int


def layer_context(cell, served, tr, reqs, t0, seconds, peaks):
    from bench import trace

    if tr.offset is None:
        raise RuntimeError("the trace holds no bench.sync span")
    lo, hi = t0 + tr.offset, t0 + seconds + tr.offset
    busy = trace.busy_by_device(tr.ops, lo, hi)
    inside = [r for r in reqs if r.error is None and r.done is not None
              and t0 <= r.done <= t0 + seconds]
    work = registry.work(cell.config["index"]["family"])
    probe_codes = served.codes_of.get(id(inside[0].queries)) if inside else None
    obs = (work.observe(served.search, probe_codes, cell.config)
           if probe_codes is not None else None)
    chips = cell.chips
    least = 0.0
    for r in inside:
        w = work.least(cell.config, np.asarray(served.codes_of[id(r.queries)]),
                       obs)
        compute = (w["int8_ops"] / peaks["int8_ops_per_s"]
                   + w["flops"] / peaks["bf16_flops_per_s"])
        least += max(compute, w["bytes"] / peaks["hbm_bytes_per_s"]) / chips
    busy_s = (sum(trace.length(b) for b in busy.values()) / len(busy)
              if busy else 0.0)
    return LayerContext(
        trace=tr, window=(lo, hi),
        inflight=[(r.sent + tr.offset, r.done + tr.offset) for r in reqs
                  if r.done is not None],
        busy=busy, busy_s=busy_s, n_requests=len(inside), least_s=least,
        chips=chips)


def breakdown(ctx) -> dict:
    """The ops that took most device time, under names that hold from
    compile to compile, and the longest idle gaps by what the host did."""
    from bench import trace

    lo, hi = ctx.window
    by_name: Dict[str, float] = {}
    for name, s in trace.time_by_name(ctx.trace.ops, lo, hi).items():
        k = trace.base(name)
        by_name[k] = by_name.get(k, 0.0) + s
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    idle = trace.gaps([iv for b in ctx.busy.values() for iv in b], lo, hi)
    gaps = trace.label_gaps(idle, ctx.trace.host)
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run(name: str, seed: int, seconds: float, traced: bool, *,
        t_start: float, backend: str = "pallas",
        overrides: Optional[dict] = None,
        wrap_search: Optional[Callable] = None,
        emit: Callable[[str], None] = print) -> dict:
    """Run cell ``name`` once; prints and returns the result object.

    ``overrides`` replaces keys of the configuration (tests run a cell at
    a small size on the CPU); ``wrap_search`` wraps the served search
    (tests plant faults under the timed path).
    """
    import jax

    cell = registry.cell(name)
    cell.config.update(overrides or {})
    compiles = CompileCounter()
    devices = jax.devices()
    kind = devices[0].device_kind
    peaks = registry.peaks(kind) if traced else None

    with tempfile.TemporaryDirectory(prefix="bench-snapshot-") as spill_dir:
        served = setup(cell, seed, backend, spill_dir)
        return _serve(cell, served, seed, seconds, traced, t_start=t_start,
                      compiles=compiles, devices=devices, peaks=peaks,
                      wrap_search=wrap_search, emit=emit)


def _serve(cell, served: Served, seed: int, seconds: float, traced: bool, *,
           t_start: float, compiles: CompileCounter, devices, peaks,
           wrap_search: Optional[Callable], emit: Callable[[str], None]):
    """The rest of a run after set-up has drawn and built ``served``."""
    from bench import traffic
    from repro.launch import proxy, serving

    cfg = cell.config
    kind = devices[0].device_kind
    search = wrap_search(served.search) if wrap_search else served.search
    batch = cell.mix["batch"]
    warm = [served.pool[:batch]]
    serving.warmup_replicas([(served.encode, search)], warm)
    q_codes = served.encode(warm[0])
    bytes_per_doc = (device_bytes(served.search, q_codes, cell.name)
                     / cfg["n_docs"])
    served.codes_of.clear()
    router = proxy.QueryRouter(proxy.ReplicaSet([(served.encode, search)]))
    # What set-up left live is never garbage again: a full collection over
    # it inside the window would hold every thread for about 0.1 s.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    traced0, compiled0, compile_s = compiles.snapshot()
    log(f"[setup] done in {setup_s:.2f} s ({compiled0} programs compiled in "
        f"{compile_s:.2f} s); window of {seconds} s opens")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        if traced:
            from bench import trace

            ctx_mgr = trace.capture(trace_dir)
        else:
            import contextlib

            ctx_mgr = contextlib.nullcontext()
        with ctx_mgr:
            if cell.mix["loop"] == "open":
                reqs, late, t0 = traffic.run_open(
                    router, served.pool, cell.mix, cell.mix["rate_per_s"],
                    seconds, seed, grace=GRACE_S)
            else:
                reqs, late, t0 = traffic.run_closed(
                    router, served.pool, cell.mix, seconds, seed,
                    grace=GRACE_S)
        router.close()
        traced1, compiled1, _ = compiles.snapshot()
        log(f"[window] programs traced inside the window: "
            f"{traced1 - traced0}, compiled: {compiled1 - compiled0}")
        mem = [d.memory_stats() or {} for d in devices[:cell.chips]]
        peak_bytes = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

        layer = {}
        dev_extra = {}
        brk = None
        if traced:
            from bench import trace

            tr = trace.load(trace_dir)
            log("[trace] " + trace.describe(tr).replace("\n", "\n[trace] "))
            ctx = layer_context(cell, served, tr, reqs, t0, seconds, peaks)
            for m in cell.per_layer:
                v = registry.metric_reader(m["name"])(ctx)
                if v is not None:
                    layer[m["name"]] = {"value": v, "unit": m["unit"]}
            dev_extra = {"busy_s": ctx.busy_s, "window_s": seconds}
            brk = breakdown(ctx)
    finally:
        gc.unfreeze()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # The program's state goes before the reference runs on the chip.
    del router, search
    served.search = None
    gc.collect()

    attempted = len(reqs)
    failed = sum(1 for r in reqs if r.error is not None)
    for r in reqs:
        if r.error is not None:
            log(f"[window] failed request: {r.error!r}")
    done = [r for r in reqs if r.error is None and r.done is not None]
    log(f"[window] sent {attempted}, completed {len(done)}, failed {failed}; "
        f"generator at worst {late * 1e3:.3f} ms late")

    t = time.perf_counter()
    numbers, recall, n_checked = check(cell, served, reqs, seed)
    log(f"[check] {n_checked} queries compared with the reference in "
        f"{time.perf_counter() - t:.2f} s")
    limits = cfg["limits"]
    correct = all(numbers[k] <= limits[k] for k in numbers)

    e2e = {}
    for m in cell.end_to_end:
        n = m["name"]
        if n == "setup_s":
            v = setup_s
        elif n == "device_bytes_per_doc":
            v = bytes_per_doc
        elif n == "latency_p95_ms":
            lat = [r.latency if r.error is None and r.done is not None
                   else seconds + GRACE_S for r in reqs]
            v = 1e3 * percentile(lat, 95)
            log(f"[window] latency p50 {1e3 * percentile(lat, 50):.3f} ms, "
                f"p95 {v:.3f} ms over {len(lat)} requests "
                f"({len(lat) - int(np.ceil(0.95 * len(lat)))} beyond the p95)")
        elif n == "qps":
            v = completed_rate(reqs, t0, seconds)
            inside = [r.done for r in done if r.done <= t0 + seconds]
            log(f"[window] {v} queries/s over {len(inside)} answers, the "
                f"last {max(inside, default=t0) - t0:.4f} s into the window")
        elif n == "recall_at_10":
            v = recall
        else:
            raise KeyError(f"no measure for end-to-end metric {n!r}")
        e2e[n] = {"value": v, "unit": m["unit"]}

    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "metrics": layer if traced else e2e,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices), "memory_peak_bytes": peak_bytes,
                   **dev_extra},
    }
    if brk is not None:
        result["breakdown"] = brk
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in numbers}
    if not traced:
        log("[metrics] " + json.dumps(e2e))
    for k in numbers:
        log(f"[check] {k} {numbers[k]!r} limit {limits[k]!r}")
    emit(json.dumps(result))
    return result
