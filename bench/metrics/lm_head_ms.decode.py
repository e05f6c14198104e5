"""Device ms per decode step of the final norm and logits (scope
``lm_head``)."""
from bench.hlo_scopes import step_ms


def read(ctx):
    return step_ms(ctx, "lm_head")
