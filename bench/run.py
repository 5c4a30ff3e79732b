#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics come from
``BENCHMARK.json`` and the files it names (see ``bench/harness.py``).
A run checks the chips first: without a TPU, or with fewer chips than
the cell asks for, it exits with code 3 and prints no result.  It then
makes every input from ``--seed``, builds the session and warms up every
program the window drives (set-up, reported as ``setup_s``), measures
for ``--seconds``, and compares what the window produced with the plain
reference under ``bench/reference``.  The last line of standard output
is one JSON object; the numbers compared, each with its limit, are also
the last lines of standard error.

``--trace 1`` runs the same window under the profiler and reports the
cell's per-layer metrics instead of its end-to-end ones.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: exit code of a run that found no chip, or too few
NO_CHIP = 3


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True, help="window length")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache() -> None:
    """The program's persistent compilation cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names another), holding every
    program however fast it compiled, so that only a cell's first run in
    a checkout compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts the backend compiles while it is on (there should be none
    inside the measured window)."""

    def __init__(self):
        import jax

        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        self.event, self.on, self.count = BACKEND_COMPILE_EVENT, False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **kwargs):
        if self.on and event == self.event:
            self.count += 1


class MetricContext:
    """What a per-layer metric reader may read."""

    def __init__(self, counters, trace, peaks, config):
        self.counters, self.trace, self.peaks, self.config = (
            counters,
            trace,
            peaks,
            config,
        )


def run(args, spec, cell, devices, device, *, root=ROOT, log=sys.stderr) -> dict:
    """Set up, measure and check one cell; returns the result object.
    The chip check has been made by the caller."""
    import gc

    from bench import harness
    from bench import trace as trace_mod

    enable_cache()
    config = harness.load_config(spec, cell["config"], root)
    traffic = harness.load_traffic(cell["traffic"], root)
    service = harness.service_module(config["service"], root)
    spans = harness.Spans(traced=bool(args.trace))
    compiles = CompileCounter()

    sess = service.Cell(config, traffic, args.seed, args.seconds, spans)
    sess.warmup()
    setup_s = time.perf_counter() - T_START
    print(f"setup: {setup_s:.3f} s", file=log, flush=True)

    trace_dir = root / ".bench_out" / "trace" / cell["name"]
    if args.trace:
        import jax

        shutil.rmtree(trace_dir, ignore_errors=True)
        # no Python function tracing: it would slow the host-bound paths
        # the trace is there to explain; the harness's spans stay
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    compiles.on = True
    try:
        window = sess.run_window(args.seconds)
    finally:
        compiles.on = False
        if args.trace:
            jax.profiler.stop_trace()
    counts = {
        k: v for k, v in sess.counters.items() if isinstance(v, (int, float))
    }
    print(
        f"window: {sess.counters['window_s']:.3f} s, compiles inside it: "
        f"{compiles.count}, counters: {counts}",
        file=log,
        flush=True,
    )
    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes(devices))

    summary = None
    if args.trace:
        summary = trace_mod.reduce_file(trace_mod.find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)

    sess.free()
    gc.collect()
    t_check = time.perf_counter()
    checks = sess.check()
    print(f"check: {time.perf_counter() - t_check:.3f} s", file=log, flush=True)

    if args.trace:
        peaks = harness.load_peaks(device["kind"], root)
        ctx = MetricContext(sess.counters, summary, peaks, config)
        metrics = {}
        for m in harness.cell_metrics(spec, cell["name"], "per_layer"):
            value = harness.metric_module(m["name"], root).read(ctx)
            if value is not None:
                metrics[m["name"]] = harness.metric_value(value, m["unit"])
    else:
        values = dict(window["values"], setup_s=setup_s)
        metrics = {
            m["name"]: harness.metric_value(values[m["name"]], m["unit"])
            for m in harness.cell_metrics(spec, cell["name"], "end_to_end")
        }
    failed = window["failed"]
    return {
        "correct": failed == 0 and harness.checks_passed(checks),
        "attempted": window["attempted"],
        "failed": failed,
        "metrics": metrics,
        "device": device,
        "checks": checks,
        "breakdown": summary.breakdown() if summary is not None else None,
    }


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench import harness

    spec = harness.load_spec()
    cell = harness.workload(spec, args.workload)
    try:
        devices, device = harness.check_device(cell["chips"])
    except harness.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return NO_CHIP
    result = run(args, spec, cell, devices, device)
    for line in harness.format_checks(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(harness.result_line(**result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
