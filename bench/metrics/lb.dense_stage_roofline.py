"""The host driver's dense LB stage programs (``_dense_stage_qblock``)
against the HBM roofline: the bytes each call must move, from its shapes
(``bench/roofline.py``), over the chip's peak HBM rate, divided by the
programs' device time in the trace.  No VPU peak is published, so the
compute side of the roofline is not counted."""

from bench.roofline import lb_stage_bytes

NAME = "lb.dense_stage_roofline"
UNIT = "%"
LAYER = "LB stage programs (core/cascade.py _dense_stage_qblock)"
MOVES = "qps"
SOURCE = "device_trace"
PROGRAM = "_dense_stage_qblock"


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace.module_seconds(PROGRAM)
    if calls == 0 or secs <= 0:
        return None
    cfg = ctx.config
    per_call = lb_stage_bytes(cfg["engine"]["max_batch"], cfg["block"], cfg["length"])
    return 100.0 * calls * per_call / ctx.peaks["hbm_bytes_per_s"] / secs
