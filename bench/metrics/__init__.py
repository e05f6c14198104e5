"""Per-layer metric readers: ``bench/metrics/<metric>.py`` defines
``read(ctx) -> float | None``.  A reader that finds nothing to read
returns None and the metric is left out of the result line.  The helpers
here are shared by the readers of one quantity in different cells."""
from __future__ import annotations



def is_balanced_spmm(hlo: str) -> bool:
    """The balanced-spmm Pallas kernels (wide, skinny, batched-expert) are
    unnamed ``pallas_call``s: on a TPU each is a ``tpu_custom_call``
    (``%closed_call.N``) whose operands include the tiles' s32 indices.
    No other Mosaic kernel on the served paths takes an s32 tensor."""
    if 'custom_call_target="tpu_custom_call"' not in hlo:
        return False
    args = hlo.split("custom-call(", 1)[-1].split("custom_call_target", 1)[0]
    return " s32[" in " " + args or "(s32[" in args


def spmm_device_s(ctx) -> float:
    return ctx.summary.seconds_of(is_balanced_spmm)


def spmm_roofline(ctx) -> float | None:
    """Least time of the window's balanced-spmm work over the kernels'
    summed device time, in percent."""
    busy = spmm_device_s(ctx)
    if busy <= 0 or not ctx.counters.get("spmm_least_s"):
        return None
    return 100.0 * ctx.counters["spmm_least_s"] / busy


def mfu(ctx) -> float | None:
    """Required FLOPs of the window over the window times the chips'
    bf16 peak, in percent."""
    if not ctx.counters.get("flops"):
        return None
    chips = ctx.summary.n_devices if ctx.summary is not None else 1
    return 100.0 * ctx.counters["flops"] / (
        ctx.counters["window_s"] * chips * ctx.peak["bf16_flops_per_s"])


def idle_share(ctx) -> float | None:
    if ctx.summary is None:
        return None
    return 100.0 * ctx.summary.idle_share
