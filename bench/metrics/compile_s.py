"""Seconds of backend compilation during set-up (JAX's
``backend_compile_duration`` events; a persistent-cache hit adds none)."""


def read(ctx):
    return ctx.counters.get("compile_s")
