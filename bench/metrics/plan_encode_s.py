"""Host seconds of the plan build's index sort, gather and tiled encoding
(span ``plan.encode``, one per projection)."""
from bench.spans import span_s


def read(ctx):
    return span_s("plan.encode")
