"""Share of the traced window in which no operation ran on the chip:
one minus the union of the device operation intervals over the window."""

NAME = "device.idle_share.knn"
UNIT = "%"
LAYER = "device (TPU v5e)"
MOVES = "qps"
SOURCE = "device_trace"


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share
