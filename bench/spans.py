"""The program's own span table (`repro.obs`), for the metric readers."""
from __future__ import annotations


def span_s(name: str) -> float | None:
    """Host seconds of every ``name`` span the process closed, or None
    where the program records no such span."""
    try:
        from repro import obs
    except ImportError:             # a program without the recorder
        return None
    row = obs.totals().get(name)
    return None if row is None else row[1]
