"""Device ms per decode step of attention over the cache (scope
``attention``)."""
from bench.hlo_scopes import step_ms


def read(ctx):
    return step_ms(ctx, "attention")
