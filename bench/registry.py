"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

Adding a configuration, a traffic mix, a cell or a per-layer metric means
adding files, never editing one:

* ``configs/<config>.json``: one deployment (``BENCHMARK.json`` names the
  file of each configuration). Its ``limits`` name the numbers the check
  compares, and so what the deployment promises: ``rank_gap`` the exact
  top-k, ``order_gap`` exact scores in descending order, ``recall_miss``
  the share of the whole corpus's top-k that the answers miss. Its
  ``snapshot`` says where the corpus snapshot handed to the builder
  lives: ``"device"`` (the default) or ``"host"``, a read-only memmap;
* ``traffic/<mix>.json``: one traffic mix, read by ``traffic.py``, with
  an open loop's fixed rate;
* ``metrics/<metric>.py``: the reader of one per-layer metric, with a
  ``read(ctx)`` function; a metric named ``<base>.<suffix>`` uses
  ``metrics/<base>.py`` when it has no file of its own;
* ``work/<family>.py``: the least work of one request of an index family;
* ``refs/<family>.py``: where an index family promises another top-k
  than the whole corpus's, that promise: ``expected(cfg, q_codes, corpus,
  *, round_bf16=False) -> (scores [Q, k], ids [Q, k])`` in plain numpy
  and jax.numpy, importing nothing of the program. ``rank_gap`` and the
  lower-precision control take it; without the file the family promises
  the whole corpus's exact top-k;
* ``peaks.json``: the peak rates of each device kind.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class UnknownDevice(KeyError):
    """The device kind has no entry in ``peaks.json``."""


def _json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]  # the metrics this cell reports with --trace 0
    per_layer: List[dict]  # ... and with --trace 1

    @property
    def family(self) -> str:
        return self.config["index"]["family"]


def _reports(metric: dict, cell: str, wanted: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in wanted if "moves" in metric else True


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its files read."""
    bench = benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _json(os.path.join(root, cfg_entry["file"]))
    mix = _json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                end_to_end=e2e, per_layer=layer)


def _load(path: str, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of per-layer metric ``name``."""
    base = os.path.join(root, "bench", "metrics")
    for stem in (name, name.split(".")[0]):
        path = os.path.join(base, stem + ".py")
        if os.path.exists(path):
            return _load(path, "bench_metric_" + stem.replace(".", "_")).read
    raise KeyError(f"no reader for per-layer metric {name!r} under {base}")


def work(family: str, root: str = ROOT):
    """The work module (``observe``, ``least``) of an index family."""
    path = os.path.join(root, "bench", "work", family + ".py")
    if not os.path.exists(path):
        raise KeyError(f"no work module for index family {family!r}")
    return _load(path, "bench_work_" + family)


def reference(family: str, root: str = ROOT):
    """The module of ``refs/<family>.py`` (``expected``), or None where the
    family has no reference of its own."""
    path = os.path.join(root, "bench", "refs", family + ".py")
    if not os.path.exists(path):
        return None
    return _load(path, "bench_ref_" + family)


def peaks(device_kind: str, root: str = ROOT) -> Dict[str, float]:
    table = _json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"device kind {device_kind!r} is not in "
                            f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]
