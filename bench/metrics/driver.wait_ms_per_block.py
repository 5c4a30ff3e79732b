"""Time per block of the window that ``nn_search_host`` spends blocked
on device-to-host reads: the summed ``session.host.wait`` spans under
the ``session.host.block`` spans, over the number of blocks."""

from bench.spans import per_block

NAME = "driver.wait_ms_per_block"
UNIT = "ms"
LAYER = "host driver (core/cascade.py nn_search_host)"
MOVES = "qps"
SOURCE = "program_span"


def read(ctx):
    got = per_block(ctx, "session.host.block", "session.host.wait")
    if got is None:
        return None
    blocks, block_s, wait_s = got
    return 1e3 * wait_s / blocks
