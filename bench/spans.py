"""What the readers of the program's own spans share: the spans of the
measured window, taken from the program's ring (``repro.obs``) between
the window's ``t0`` and ``t_end`` counters, and sums of them per block.

Each returns None where there is nothing to read: a program without the
ring, a ring that overwrote spans of the window, or a window in which
the spans named did not occur.
"""

from __future__ import annotations


def window_spans(ctx):
    """The spans that opened and closed inside the window, or None."""
    try:
        from repro import obs
    except ImportError:  # a program that records no spans of its own
        return None
    try:
        return obs.spans_between(ctx.counters["t0"], ctx.counters["t_end"])
    except obs.RingWrapped:
        return None


def per_block(ctx, block: str, wait: str):
    """``(blocks, block_s, wait_s)`` over the window: the number of
    ``block`` spans, their summed seconds, and the summed seconds of the
    ``wait`` spans opened under one of them; or None."""
    spans = window_spans(ctx)
    if not spans:
        return None
    blocks = [s for s in spans if s.name == block]
    if not blocks:
        return None
    by_id = {s.id: s for s in spans}

    def under_block(s) -> bool:
        parent = by_id.get(s.parent)
        while parent is not None:
            if parent.name == block:
                return True
            parent = by_id.get(parent.parent)
        return False

    wait_s = sum(s.seconds for s in spans if s.name == wait and under_block(s))
    return len(blocks), sum(b.seconds for b in blocks), wait_s
