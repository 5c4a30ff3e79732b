"""Device time of the banded DP programs (``_dtw_pairs_block``) in the
traced window, over the DP lanes the host driver dispatched in the
batches that ran in it (``SearchStats.dp_lane_work``)."""

NAME = "dp.us_per_lane"
UNIT = "us"
LAYER = "banded DP (core/dtw.py via core/cascade.py _dtw_pairs_block)"
MOVES = "qps"
SOURCE = "device_trace"
PROGRAM = "_dtw_pairs_block"


def read(ctx):
    if ctx.trace is None:
        return None
    secs, calls = ctx.trace.module_seconds(PROGRAM)
    lanes = sum(b[2] for b in ctx.counters.get("batches", ()))
    if calls == 0 or lanes == 0:
        return None
    return 1e6 * secs / lanes
