"""Shared set-up of the benchmark's own tests: a copy of the benchmark
at a size the CPU runs in seconds, and a way to drive one run of it
without the chip check."""

from __future__ import annotations

import importlib.util
import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: CPU-sized stand-ins: the shapes of each configuration at a smaller
#: scale (1,024 rows so that the planner still picks the host driver)
TINY_CONFIGS = {
    "randwalk-n1000": {
        "rows": 1024,
        "length": 32,
        "w": 3,
        "engine": {"max_batch": 8, "max_wait_ms": 20, "max_queue": 64,
                   "cache_capacity": 64},
        "check": {"answers": 16, "pairs_per_call": 4096,
                  "limits": {"dist_gap": 1e-4, "index_gap": 1e-4,
                             "unanswered": 0}},
    },
    "ucr-rw-stream": {
        "templates": 4,
        "length": 32,
        "w": 3,
        "exclusion": 32,
        "check": {"windows_per_call": 2048,
                  "limits": {"dist_gap": 1e-4, "unexplained": 0,
                             "unfed": 0}},
    },
}
TINY_TRAFFIC = {
    "closed32-cold": {"clients": 8, "max_qps": 20000},
    "closed32-mixed": {"clients": 8, "max_qps": 20000},
    "stream-chunk1024": {"chunk": 256, "plant_every": 400,
                         "calibration_windows": 16, "calibration_stride": 16,
                         "warmup_chunks": 2, "max_samples_per_s": 400000},
}


def load_run_module():
    spec = importlib.util.spec_from_file_location(
        "_bench_run_under_test", ROOT / "bench" / "run.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout-like directory holding ``BENCHMARK.json`` and a copy of
    ``bench/`` whose configurations and mixes are the tiny ones."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for name, over in TINY_CONFIGS.items():
        path = tmp / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(over)
        path.write_text(json.dumps(cfg))
    for name, over in TINY_TRAFFIC.items():
        path = tmp / "bench" / "traffic" / f"{name}.json"
        mix = json.loads(path.read_text())
        mix.update(over)
        path.write_text(json.dumps(mix))
    return tmp


def run_cell(root: pathlib.Path, cell: str, seed: int, seconds: float = 2.0) -> dict:
    """One run of ``cell`` on the CPU, chip check skipped, the persistent
    compile cache left off."""
    import jax

    from bench import harness

    run = load_run_module()
    run.enable_cache = lambda: None
    spec = harness.load_spec(root)
    args = run.parse_args(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", "0"])
    return run.run(args, spec, harness.workload(spec, cell), jax.devices()[:1],
                   {"platform": "cpu", "kind": "cpu", "count": 1}, root=root)


def cell_object(root: pathlib.Path, cell: str, seed: int, seconds: float = 2.0):
    """The service's cell for ``cell``, set up, warmed up and run through
    its window, with the program's state freed: ready for ``check`` or
    ``control``."""
    from bench import harness

    spec = harness.load_spec(root)
    wl = harness.workload(spec, cell)
    config = harness.load_config(spec, wl["config"], root)
    traffic = harness.load_traffic(wl["traffic"], root)
    service = harness.service_module(config["service"], root)
    sess = service.Cell(config, traffic, seed, seconds, harness.Spans())
    sess.warmup()
    sess.run_window(seconds)
    sess.free()
    return sess
