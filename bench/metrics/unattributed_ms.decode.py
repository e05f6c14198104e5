"""Device ms per decode step of every op that no kernel name and no
single scope claims."""
from bench.hlo_scopes import step_ms


def read(ctx):
    return step_ms(ctx, "unattributed")
