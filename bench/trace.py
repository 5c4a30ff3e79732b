"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The trace holds the device planes (``/device:TPU:<i>``), whose ``XLA
Ops`` line has one event per operation that ran on the chip and whose
``XLA Modules`` line has one event per program execution, and the host
plane, where the harness's spans (``TraceAnnotation``) sit on the
threads that opened them.  All events share one clock.

* busy time: the union of the operation intervals inside the traced
  window, per chip, averaged over the chips;
* device time per program (XLA module, the jitted function's name) and
  per operation;
* idle gaps: the stretches of the window in which no operation ran,
  each named by the innermost harness span that covers its middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
#: spans the harness opens; other host events are the runtime's own
SPAN_PREFIXES = ("bench.", "session.", "stream.")
OUTSIDE = "no harness span"
#: the span around the measured window; the trace is read inside it
WINDOW = "bench.window"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over chips
    chips: int
    module_s: dict[str, float]  # device seconds per program, all chips
    module_calls: dict[str, int]
    op_s: dict[str, float]  # device seconds per "program/op", all chips
    idle_by_span: dict[str, float]  # idle seconds per host span, chip mean

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0

    def module_seconds(self, needle: str) -> tuple[float, int]:
        """Device seconds and executions of the programs whose name
        holds ``needle``."""
        secs = sum(v for k, v in self.module_s.items() if needle in k)
        calls = sum(v for k, v in self.module_calls.items() if needle in k)
        return secs, calls

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle],
        }


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


_CALL_ID = re.compile(r"\(\d+\)$")


def _module_name(name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return _CALL_ID.sub("", name)


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _host_spans(planes):
    spans, window = [], None
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if not name.startswith(SPAN_PREFIXES):
                    continue
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                spans.append((name, s, e))
                if name == WINDOW and (window is None or e - s > window[1] - window[0]):
                    window = (s, e)
    return spans, window


def _innermost(spans, starts, t: int, look_back: int = 8) -> str:
    """The shortest span covering time ``t``.  The harness's spans nest
    and spans of one name do not overlap, so the candidates are the few
    that start last before ``t``."""
    i = bisect.bisect_right(starts, t)
    best = None
    for sp in spans[max(0, i - look_back) : i]:
        if sp[1] <= t < sp[2] and (best is None or sp[2] - sp[1] < best[2] - best[1]):
            best = sp
    return best[0] if best else OUTSIDE


def reduce_planes(planes) -> TraceSummary:
    """The summary of a trace given its planes (``ProfileData.planes``)."""
    planes = list(planes)
    spans, window = _host_spans(planes)
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    w0, w1 = window
    inner_spans = sorted((sp for sp in spans if sp[0] != WINDOW), key=lambda sp: sp[1])
    starts = [sp[1] for sp in inner_spans]
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    if not devices:
        raise ValueError(f"the trace has no {DEVICE_PREFIX}* plane")
    module_s: dict[str, float] = {}
    module_calls: dict[str, int] = {}
    op_s: dict[str, float] = {}
    busy_total = 0.0
    idle_by_span: dict[str, float] = {}
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{plane.name} has no {OPS_LINE!r} line")
        mod_intervals = []
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if e <= w0 or s >= w1:
                    continue
                name = _module_name(ev.name)
                d = (min(e, w1) - max(s, w0)) * 1e-9
                module_s[name] = module_s.get(name, 0.0) + d
                module_calls[name] = module_calls.get(name, 0) + 1
                mod_intervals.append((s, e, name))
        mod_intervals.sort()
        intervals = []
        mi = 0
        for ev in lines[OPS_LINE].events:
            s = int(ev.start_ns)
            e = s + int(ev.duration_ns)
            if e <= w0 or s >= w1:
                continue
            s, e = max(s, w0), min(e, w1)
            intervals.append((s, e))
            while mi < len(mod_intervals) and mod_intervals[mi][1] <= s:
                mi += 1
            owner = next(
                (m[2] for m in mod_intervals[mi : mi + 4] if m[0] <= s < m[1]),
                "?",
            )
            key = f"{owner}/{ev.name}"
            op_s[key] = op_s.get(key, 0.0) + (e - s) * 1e-9
        busy = _union(intervals)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        # idle gaps: the complement of the busy union inside the window
        cursor = w0
        gaps = []
        for s, e in busy + [(w1, w1)]:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        for gs, ge in gaps:
            name = _innermost(inner_spans, starts, (gs + ge) // 2)
            idle_by_span[name] = idle_by_span.get(name, 0.0) + (ge - gs) * 1e-9
    chips = len(devices)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / chips,
        chips=chips,
        module_s=module_s,
        module_calls=module_calls,
        op_s=op_s,
        idle_by_span={k: v / chips for k, v in idle_by_span.items()},
    )


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)
