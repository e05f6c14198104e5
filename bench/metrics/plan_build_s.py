"""Host seconds of ``plan_model`` up to ``block_until_ready``."""


def read(ctx):
    return ctx.counters.get("plan_build_s")
