"""Host time per block of the window outside device reads: the summed
``session.host.block`` spans (one per block of ``nn_search_host``) less
the ``session.host.wait`` spans under them, over the number of blocks."""

from bench.spans import per_block

NAME = "driver.host_ms_per_block"
UNIT = "ms"
LAYER = "host driver (core/cascade.py nn_search_host)"
MOVES = "qps"
SOURCE = "program_span"


def read(ctx):
    got = per_block(ctx, "session.host.block", "session.host.wait")
    if got is None:
        return None
    blocks, block_s, wait_s = got
    return 1e3 * (block_s - wait_s) / blocks
