"""Shared pieces of the chip benchmark.

The harness is driven by data.  ``BENCHMARK.json`` names each cell's
configuration and traffic mix, and every per-layer metric; each of
those lives in a file of its own that is found here by that name:

* ``bench/configs/<config>.json``  - one deployment: sizes, the search
  settings, the service that runs it, and the limits of its comparison
* ``bench/traffic/<mix>.json``     - one traffic mix, read by
  ``bench/generate.py``
* ``bench/services/<service>.py``  - set-up, window and comparison of
  one kind of service (k-NN search, stream matching)
* ``bench/metrics/<metric>.py``    - one reader per per-layer metric

A later cell or metric is a new file and a new entry in
``BENCHMARK.json``; no file that exists has to change.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class SpecError(ValueError):
    """BENCHMARK.json names something that has no file, or is malformed."""


# ---------------------------------------------------------------- the spec


def load_spec(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"{path} not found")
    return json.loads(path.read_text())


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(spec: dict, name: str) -> dict:
    return _by_name(spec["workloads"], name, "workload")


def load_config(spec: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    entry = _by_name(spec["configs"], name, "config")
    path = root / entry["file"]
    if not path.is_file():
        raise SpecError(f"config {name!r}: {path} not found")
    cfg = json.loads(path.read_text())
    cfg.setdefault("name", name)
    return cfg


def load_traffic(name: str, root: pathlib.Path = ROOT) -> dict:
    path = root / "bench" / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SpecError(f"traffic {name!r}: {path} not found")
    mix = json.loads(path.read_text())
    mix.setdefault("name", name)
    return mix


def load_module(path: pathlib.Path, prefix: str):
    """Import a file by path (metric names hold dots, so they are not
    importable module names)."""
    if not path.is_file():
        raise SpecError(f"{path} not found")
    modname = f"_bench_{prefix}_" + "".join(
        c if c.isalnum() else "_" for c in path.stem
    )
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def service_module(name: str, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "services" / f"{name}.py", "service")


def metric_module(name: str, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py", "metric")


def cell_metrics(spec: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
    that list it under ``workloads``, or that list no cells at all."""
    return [
        m
        for m in spec[kind]
        if "workloads" not in m or cell in m["workloads"]
    ]


# ------------------------------------------------------------------ device


def check_device(chips: int) -> tuple[list, dict]:
    """The chips this run may use and how the result names them.  There
    is no fallback: without a TPU, or with too few, the run ends."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise NoAccelerator(
            f"JAX found {len(devs)} {platform} device(s) and no TPU; the "
            f"benchmark measures the chip only"
        )
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devs)}")
    used = devs[:chips]
    return used, {
        "platform": platform,
        "kind": used[0].device_kind,
        "count": len(used),
    }


def memory_peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest chip, where the backend
    reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def load_peaks(kind: str, root: pathlib.Path = ROOT) -> dict:
    """Published peaks of one chip, keyed by ``device_kind``.  A device
    missing from the table is an error, never a default."""
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(
            f"device kind {kind!r} is not in bench/peaks.json "
            f"(known: {sorted(table['devices'])})"
        )
    return table["devices"][kind]


# ------------------------------------------------------------------- spans


class Spans:
    """Spans the harness records around its own calls into the program.

    While a profiler trace is running each span is also written into the
    trace as a ``TraceAnnotation``, so that device idle gaps can be named
    by what the host was doing.  A span keeps its start ``t0`` on the
    host's monotonic clock.
    """

    def __init__(self, traced: bool = False):
        self.traced = traced

    def span(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, owner: Spans, name: str):
        self.owner, self.name = owner, name
        self._ann = None

    def __enter__(self):
        if self.owner.traced:
            from jax.profiler import TraceAnnotation

            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


# ------------------------------------------------------------------ result


def metric_value(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def result_line(
    *,
    correct: bool,
    attempted: int,
    failed: int,
    metrics: dict,
    device: dict,
    checks: dict,
    breakdown: dict | None = None,
) -> str:
    """The last line of standard output.  ``checks`` (each number
    compared beside its limit) comes last."""
    out = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def checks_passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def format_checks(checks: dict) -> list[str]:
    return [
        f"check {name}: {c['value']!r} limit {c['limit']!r} "
        f"({'ok' if c['value'] <= c['limit'] else 'FAILED'})"
        for name, c in checks.items()
    ]
