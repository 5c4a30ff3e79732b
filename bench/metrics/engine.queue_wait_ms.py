"""Mean admission-to-execution wait of the requests the engine served
from a batch in the window (``EngineStats.wait_ms_mean``, from snapshots
at the window's start and end)."""

NAME = "engine.queue_wait_ms"
UNIT = "ms"
LAYER = "engine (serve/engine.py)"
MOVES = "p95_ms"
SOURCE = "program_counter"


def read(ctx):
    return ctx.counters.get("queue_wait_ms")
