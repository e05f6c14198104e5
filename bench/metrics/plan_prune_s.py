"""Host seconds of the plan build's prune: transpose, magnitude argsorts
and mask transfer (span ``plan.prune``, one per projection)."""
from bench.spans import span_s


def read(ctx):
    return span_s("plan.prune")
