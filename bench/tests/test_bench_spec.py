"""BENCHMARK.json against the benchmark's contract, and the harness as
data: every configuration, traffic mix and per-layer metric it names
resolves to a file of its own."""

from __future__ import annotations

import json
import re

import pytest

from bench_testing import ROOT

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")

SPEC = harness.load_spec()

CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


def _one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(_one_line(w) for w in SPEC["command"])
    for path in SPEC["paths"]:
        assert PATH.match(path) and not path.startswith("/") and ".." not in path
        assert (ROOT / path).is_dir()
    named = [w for w in SPEC["command"] if "/" in w]
    assert all(any(w.startswith(p + "/") for p in SPEC["paths"]) for w in named)
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_are_unique_and_allowed(kind):
    names = [e["name"] for e in SPEC[kind]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_units_and_directions(kind):
    for m in SPEC[kind]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m


def test_configs_resolve_to_their_own_files():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == CONFIG_KEYS
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        cfg = harness.load_config(SPEC, c["name"])
        assert _one_line(c["source"]) and _one_line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        # every key changed from the source is listed, and says how
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert c["source"] == cfg["source"]
        assert (ROOT / "bench" / "services" / f"{cfg['service']}.py").is_file()


def test_workloads_resolve_and_fit_the_chip_rule():
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == WORKLOAD_KEYS
        assert w["chips"] in (1, 4)
        assert _one_line(w["why"])
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        pairs.add((w["config"], w["traffic"]))
        harness.load_traffic(w["traffic"])
        harness.load_config(SPEC, w["config"])
    assert len(pairs) == len(SPEC["workloads"])
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_end_to_end_metrics_and_bounds():
    names = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in names
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == E2E_KEYS
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in SPEC["workloads"]}
    for cell in cells:
        reported = {m["name"] for m in harness.cell_metrics(SPEC, cell, "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2, cell
        assert harness.cell_metrics(SPEC, cell, "per_layer"), cell


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_has_its_own_reader(metric):
    entry = next(m for m in SPEC["per_layer"] if m["name"] == metric)
    assert set(entry) - {"workloads"} == LAYER_KEYS
    assert entry["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
    assert _one_line(entry["layer"])
    mod = harness.metric_module(metric)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        entry["name"], entry["unit"], entry["layer"], entry["moves"], entry["source"])
    assert callable(mod.read)
    # every cell the metric lists reports the end-to-end metric it moves
    assert entry["workloads"]
    for cell in entry["workloads"]:
        moved = {m["name"] for m in harness.cell_metrics(SPEC, cell, "end_to_end")}
        assert entry["moves"] in moved, (metric, cell)


def test_layers_are_named_alike():
    by_layer = {}
    for m in SPEC["per_layer"]:
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values()), by_layer


def test_peaks_table_and_unknown_device():
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    assert set(table["devices"]) == {"TPU v5 lite"}
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        harness.load_peaks("TPU v9 imaginary")


def test_full_check_fits_the_time_limit():
    runs = 2 + 14 * 24
    total = runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200
