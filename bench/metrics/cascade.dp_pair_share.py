"""Share of (query, row) pairs that reached the banded DP, over the
answers the engine executed in the window (cache hits and coalesced
riders left out): sum of ``full_dtw`` over sum of ``n_candidates`` of
``Answer.stats``."""

NAME = "cascade.dp_pair_share"
UNIT = "%"
LAYER = "cascade stages (core/pipeline.py, core/lb.py)"
MOVES = "qps"
SOURCE = "program_counter"


def read(ctx):
    cand = ctx.counters.get("n_candidates")
    if not cand:
        return None
    return 100.0 * ctx.counters["full_dtw"] / cand
