"""Share of (template, window) lanes of the window that the stream
scanner's bounds pruned before the banded DP (``StreamStats``: one minus
``full_dtw`` over ``n_windows``, summed over templates)."""

NAME = "stream.pruned_before_dtw"
UNIT = "%"
LAYER = "stream scanner (stream/subsequence.py, stream/matcher.py)"
MOVES = "samples_per_s"
SOURCE = "program_counter"


def read(ctx):
    windows = ctx.counters.get("windows")
    if not windows:
        return None
    return 100.0 * (1.0 - ctx.counters["full_dtw"] / windows)
