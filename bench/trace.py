"""The profiler trace of a ``--trace 1`` run, reduced to intervals.

``capture`` wraps the measured window in ``jax.profiler`` tracing;
``load`` reads the ``.xplane.pb`` it wrote with ``jax.profiler
.ProfileData`` and returns a ``Trace``: the device operations of each
chip, the programs (XLA modules) they ran in, and the host's spans, all
on the profiler's clock in seconds. ``sync`` spans, which carry the host
``perf_counter`` at which they were opened, give the offset between that
clock and the host clock the client loops record requests on.

The reductions below are plain functions of interval lists, checked on
written traces by ``tests/test_bench_trace.py``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import time
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC = "bench.sync"
CLIENT = "bench."  # the client's own spans (``traffic.py``)
# The program's spans (``src/repro/launch/spans.py``) are named
# ``<layer>.<stage>`` in lower case: ``serving.dispatch``, ``proxy.submit``.
# The runtime's host events are not (``PjitFunction(f)``, ``$file:line f``).
PROGRAM_SPAN = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
WHOLE_REQUEST = "proxy.request"


@dataclasses.dataclass
class Op:
    device: int
    start: float
    end: float
    name: str
    module: str = ""
    run: float = -1.0  # start of the program execution that holds it


@dataclasses.dataclass
class Trace:
    ops: List[Op]  # device operations, every chip
    modules: List[Op]  # device program executions, every chip
    host: List[Op]  # host spans (device = -1)
    offset: Optional[float]  # trace clock minus host perf_counter, s
    lines: List[str] = dataclasses.field(default_factory=list)  # plane/line

    @property
    def devices(self) -> List[int]:
        return sorted({o.device for o in self.ops})


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the given intervals."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The parts of ``a`` that no interval of ``b`` covers."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi] between busy intervals."""
    return subtract([(lo, hi)], busy)


def busy_by_device(ops: Iterable[Op], lo: float, hi: float):
    """{device: disjoint busy intervals inside [lo, hi]}."""
    per: Dict[int, List[Interval]] = {}
    for o in ops:
        per.setdefault(o.device, []).append((o.start, o.end))
    return {d: union(clip(iv, lo, hi)) for d, iv in per.items()}


def short(name: str) -> str:
    """An op's instruction name without its HLO text: ``%copy.4 = u8[..]
    copy(..)`` -> ``copy.4``."""
    return name.split(" = ", 1)[0].lstrip("%")


def base(name: str) -> str:
    """An instruction name without the ``.N`` that XLA numbers it with,
    which changes from compile to compile: ``copy.4`` -> ``copy``,
    ``sdc_topk_q128.1`` -> ``sdc_topk_q128``."""
    return re.sub(r"\.\d+$", "", name)


def time_by_name(ops: Iterable[Op], lo: float, hi: float):
    """{op instruction name: seconds of device time inside [lo, hi]},
    summed over chips and calls."""
    out: Dict[str, float] = {}
    for o in ops:
        s, e = max(o.start, lo), min(o.end, hi)
        if e > s:
            k = short(o.name)
            out[k] = out.get(k, 0.0) + (e - s)
    return out


def _most_over(s: float, e: float, spans: List[Op]) -> Optional[str]:
    """The span that overlaps [s, e] most, the shorter on a tie."""
    best = max(((min(e, h.end) - max(s, h.start), h.start - h.end, h.name)
                for h in spans), default=None)
    return best[2] if best is not None and best[0] > 0 else None


def label_gaps(idle: List[Interval], host: List[Op], top: int = 10):
    """The ``top`` longest idle stretches, each named after the program
    span that overlaps it most, the shorter on a tie (``proxy.request``,
    which holds a whole request, names none); where no program span
    overlaps it, after the benchmark client's span that does; else
    ``-``."""
    program, client = [], []
    for h in host:
        if h.name.startswith(CLIENT):
            if h.name != SYNC:
                client.append(h)
        elif h.name != WHOLE_REQUEST and PROGRAM_SPAN.match(h.name):
            program.append(h)
    return [(_most_over(s, e, program) or _most_over(s, e, client) or "-",
             e - s)
            for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]]


# ---------------------------------------------------------------------------
# capture and load
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace the enclosed block into ``log_dir``, with sync spans at both
    ends."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        sync_span()
        yield
        sync_span()
    finally:
        jax.profiler.stop_trace()


def sync_span():
    """A host span that carries the perf_counter at which it opened."""
    import jax

    t = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(SYNC, host_ns=t):
        pass


def load(log_dir: str) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    ops, modules, host, offsets, seen = [], [], [], [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            seen.append(f"{plane.name}/{line.name}")
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dest = ops if line.name == OPS_LINE else modules
                for ev in line.events:
                    dest.append(Op(int(m.group(1)), ev.start_ns * 1e-9,
                                   ev.end_ns * 1e-9, ev.name))
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    host.append(Op(-1, ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                   ev.name))
                    if ev.name == SYNC:
                        stats = dict(ev.stats)
                        if "host_ns" in stats:
                            offsets.append(ev.start_ns * 1e-9
                                           - float(stats["host_ns"]) * 1e-9)
    assign_modules(ops, modules)
    offset = sorted(offsets)[len(offsets) // 2] if offsets else None
    return Trace(ops=ops, modules=modules, host=host, offset=offset,
                 lines=seen)


def assign_modules(ops: List[Op], modules: List[Op]) -> None:
    """Name each op's program: the module execution on its chip that
    holds its start."""
    by_dev: Dict[int, List[Op]] = {}
    for m in modules:
        by_dev.setdefault(m.device, []).append(m)
    for lst in by_dev.values():
        lst.sort(key=lambda m: m.start)
    for o in ops:
        lst = by_dev.get(o.device, [])
        lo, hi = 0, len(lst)
        while lo < hi:  # last module starting at or before the op
            mid = (lo + hi) // 2
            if lst[mid].start <= o.start:
                lo = mid + 1
            else:
                hi = mid
        if lo and lst[lo - 1].end >= o.start:
            o.module, o.run = lst[lo - 1].name, lst[lo - 1].start


def describe(tr: Trace) -> str:
    """One line per device: ops, distinct names, the busiest programs."""
    out = ["lines: " + ", ".join(sorted(set(tr.lines))[:40])]
    for d in tr.devices:
        mine = [o for o in tr.ops if o.device == d]
        mods: Dict[str, float] = {}
        for o in mine:
            mods[o.module] = mods.get(o.module, 0.0) + o.end - o.start
        top = sorted(mods.items(), key=lambda kv: -kv[1])[:4]
        out.append(f"device {d}: {len(mine)} ops, "
                   f"{len({o.name for o in mine})} names; programs "
                   + ", ".join(f"{k or '-'} {v:.4f}s" for k, v in top))
    return "\n".join(out)
