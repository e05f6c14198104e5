"""Host seconds of the plan build's block choices and shared KB
(span ``plan.blocks``, one per projection)."""
from bench.spans import span_s


def read(ctx):
    return span_s("plan.blocks")
