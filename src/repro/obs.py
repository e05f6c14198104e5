"""Named spans of the program's host work.

``span(name, **args)`` opens a `jax.profiler.TraceAnnotation`, so a running
profiler records it on the device trace's clock beside the device's ops,
and adds its host seconds and a count of one to an in-memory table keyed by
``name``.  Spans sit only at host boundaries that run a few dozen times per
process (the plan build's phases, one per projection), so the recorder is
always on.

    with obs.span("plan.prune", layer="wq"):
        ...
    obs.totals()   # {"plan.prune": (count, seconds), ...}
"""
from __future__ import annotations

import contextlib
import time

import jax

_TOTALS: dict[str, list] = {}


@contextlib.contextmanager
def span(name: str, **args):
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name, **args):
            yield
    finally:
        row = _TOTALS.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += time.perf_counter() - t0


def totals() -> dict[str, tuple[int, float]]:
    """``{name: (count, host seconds)}`` of every span closed since the
    last `reset`."""
    return {name: (n, s) for name, (n, s) in _TOTALS.items()}


def reset() -> None:
    _TOTALS.clear()
