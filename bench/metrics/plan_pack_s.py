"""Host seconds of the plan build's column-combining packing choice
(span ``plan.pack``, one per projection)."""
from bench.spans import span_s


def read(ctx):
    return span_s("plan.pack")
