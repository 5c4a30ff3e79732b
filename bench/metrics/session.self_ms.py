"""Time the session spends on a search call outside the host driver:
the mean over the window's ``session.query`` spans (``Database.search``)
of their duration less the ``session.host`` span under each
(``nn_search_host``).  What is left is query preparation, planning and
result assembly."""

from bench.spans import window_spans

NAME = "session.self_ms"
UNIT = "ms"
LAYER = "session and planner (api/database.py, api/planner.py)"
MOVES = "p95_ms"
SOURCE = "program_span"


def read(ctx):
    spans = window_spans(ctx)
    if not spans:
        return None
    host_s: dict[int, float] = {}
    for s in spans:
        if s.name == "session.host" and s.parent is not None:
            host_s[s.parent] = host_s.get(s.parent, 0.0) + s.seconds
    calls = [
        s.seconds - host_s.get(s.id, 0.0) for s in spans if s.name == "session.query"
    ]
    if not calls:
        return None
    return 1e3 * sum(calls) / len(calls)
