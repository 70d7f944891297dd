"""Everything a cell needs is found by name: a new configuration, traffic
mix or per-layer metric is a new file, and a new cell an entry of
``BENCHMARK.json``; none needs a code edit."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import registry  # noqa: E402


def test_every_cell_of_the_benchmark_resolves():
    bench = registry.benchmark()
    for w in bench["workloads"]:
        cell = registry.cell(w["name"])
        assert cell.chips == w["chips"]
        registry.work(cell.family)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            registry.metric_reader(m["name"])
        if cell.mix["loop"] == "open":
            assert cell.mix["rate_per_s"] > 0
    # The online IVF cell reports the online latency and every per-layer
    # metric of an online cell, and not the recall of the bulk IVF cell.
    ivf = registry.cell("web-ivf.online")
    assert (ivf.family, ivf.chips, ivf.mix["batch"]) == ("ivf", 1, 8)
    assert {m["name"] for m in ivf.end_to_end} == {
        "latency_p95_ms", "device_bytes_per_doc", "setup_s"}
    assert {m["name"] for m in ivf.per_layer} == {
        n + ".online" for n in (
            "search_roofline", "device_idle", "encode_ms",
            "admission_wait_ms", "router_ms", "encode_host_ms", "handoff_ms",
            "dispatch_ms", "resolve_ms")}


def test_peaks_know_the_v5e_and_refuse_other_devices():
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(registry.UnknownDevice):
        registry.peaks("TPU v9 imaginary")
    with pytest.raises(registry.UnknownDevice):
        registry.peaks("cpu")


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def test_new_config_mix_cell_and_metric_are_found_by_name(tmp_path):
    root = str(tmp_path)
    bench = registry.benchmark()
    bench["configs"].append({"name": "video-flat", "source": "x",
                             "file": "bench/configs/video-flat.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "video-flat.burst",
                               "config": "video-flat", "traffic": "burst",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "admission_wait_ms", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "router", "moves": "latency_p95_ms",
                               "workloads": ["video-flat.burst"]})
    bench["end_to_end"][0]["workloads"].append("video-flat.burst")
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    _write(os.path.join(root, "bench/configs/video-flat.json"),
           {"binarizer": {"code_dim": 64}, "index": {"family": "flat"}})
    _write(os.path.join(root, "bench/traffic/burst.json"),
           {"loop": "open", "arrival": "poisson", "batch": 8,
            "rate_per_s": 3.0})
    _write(os.path.join(root, "bench/metrics/admission_wait_ms.py"),
           "def read(ctx):\n    return 42.0\n")
    cell = registry.cell("video-flat.burst", root=root)
    assert cell.config["binarizer"]["code_dim"] == 64
    assert cell.mix["batch"] == 8 and cell.mix["rate_per_s"] == 3.0
    assert "latency_p95_ms" in {m["name"] for m in cell.end_to_end}
    assert [m["name"] for m in cell.per_layer] == ["admission_wait_ms"]
    assert registry.metric_reader("admission_wait_ms", root=root)(None) == 42
    # A suffixed name falls back to its base reader.
    assert registry.metric_reader("admission_wait_ms.bulk",
                                  root=root)(None) == 42
    with pytest.raises(KeyError):
        registry.metric_reader("no_such_metric", root=root)


def _run(args, cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu"}, **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    p = _run(["--workload", "web-flat.online", "--seed", "3",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_a_run_with_only_the_benchmark_files_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "web-flat.online", "--seed", "3",
              "--seconds", "1", "--trace", "0"], str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
