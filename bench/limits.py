#!/usr/bin/env python3
"""The readings that a cell's comparison limits are set from.

    python3 bench/limits.py --workload <cell> --seeds 11,12,13 \\
        --control-seeds 3 --seconds 8 [--control-dtype bfloat16]

For each seed, in one process on the chip: the cell's set-up, a short
window at the cell's own load, and the comparison of what the window
produced with the plain reference (the program's readings, whose largest
is the lower end of each limit).  For the first ``--control-seeds``
seeds it then puts the reference, computed in ``--control-dtype``, in
the program's place and compares it the same way (the control's
readings, whose smallest is the upper end).  Each reading is one JSON
line on standard output.  The benchmark's own runs never run this.
"""

import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-dtype", default="bfloat16")
    args = ap.parse_args(argv)

    from bench import harness
    from bench.run import NO_CHIP, enable_cache

    spec = harness.load_spec()
    cell = harness.workload(spec, args.workload)
    try:
        harness.check_device(cell["chips"])
    except harness.NoAccelerator as e:
        print(f"no readings: {e}", file=sys.stderr)
        return NO_CHIP
    enable_cache()
    config = harness.load_config(spec, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    service = harness.service_module(config["service"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        sess = service.Cell(config, traffic, seed, args.seconds, harness.Spans())
        sess.warmup()
        window = sess.run_window(args.seconds)
        sess.free()
        gc.collect()
        checks = sess.check()
        line = {
            "workload": args.workload,
            "seed": seed,
            "attempted": window["attempted"],
            "program": {k: c["value"] for k, c in checks.items()},
            "limits": {k: c["limit"] for k, c in checks.items()},
        }
        if i < args.control_seeds:
            line["control"] = sess.control(args.control_dtype)
            line["control_dtype"] = args.control_dtype
        print(json.dumps(line), flush=True)
        del sess
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
