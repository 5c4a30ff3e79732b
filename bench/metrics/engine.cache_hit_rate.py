"""Share of the window's requests that the answer cache served
(``EngineStats.cache_hits`` over hits plus misses)."""

NAME = "engine.cache_hit_rate"
UNIT = "%"
LAYER = "engine (serve/engine.py)"
MOVES = "qps"
SOURCE = "program_counter"


def read(ctx):
    hits = ctx.counters.get("cache_hits")
    misses = ctx.counters.get("cache_misses")
    if hits is None or misses is None or hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)
