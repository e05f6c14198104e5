"""Device ms per decode step of the balanced-spmm kernels, found by their
kernel name."""
from bench.hlo_scopes import step_ms


def read(ctx):
    return step_ms(ctx, "balanced_spmm")
