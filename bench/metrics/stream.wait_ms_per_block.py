"""Time per block of the window that the stream scanner spends blocked
on device-to-host reads: the summed ``stream.wait`` spans under the
``stream.block`` spans, over the number of blocks."""

from bench.spans import per_block

NAME = "stream.wait_ms_per_block"
UNIT = "ms"
LAYER = "stream scanner (stream/subsequence.py, stream/matcher.py)"
MOVES = "samples_per_s"
SOURCE = "program_span"


def read(ctx):
    got = per_block(ctx, "stream.block", "stream.wait")
    if got is None:
        return None
    blocks, block_s, wait_s = got
    return 1e3 * wait_s / blocks
