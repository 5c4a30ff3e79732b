"""Host time per block of the window outside device reads: the summed
``stream.block`` spans (one per block of the stream scanner) less the
``stream.wait`` spans under them, over the number of blocks."""

from bench.spans import per_block

NAME = "stream.host_ms_per_block"
UNIT = "ms"
LAYER = "stream scanner (stream/subsequence.py, stream/matcher.py)"
MOVES = "samples_per_s"
SOURCE = "program_span"


def read(ctx):
    got = per_block(ctx, "stream.block", "stream.wait")
    if got is None:
        return None
    blocks, block_s, wait_s = got
    return 1e3 * (block_s - wait_s) / blocks
