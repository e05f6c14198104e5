"""Device ms per decode step of the KV-cache write (scope ``kv_update``)."""
from bench.hlo_scopes import step_ms


def read(ctx):
    return step_ms(ctx, "kv_update")
