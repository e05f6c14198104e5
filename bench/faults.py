"""Faults planted under the timed path, for checking that the comparison
which decides ``correct`` catches them: each wraps
``repro.models.build_model`` so that every decode step's output is
broken.  `bench/calibrate.py` runs them at a cell's own size on the chip;
`bench/tests/test_faults.py` runs them at smoke width on the CPU."""
from __future__ import annotations

import contextlib

import jax.numpy as jnp


def unchanged_state(logits, old, new):
    return logits, old                      # the cache is never written


def half_batch(logits, old, new):
    h = logits.shape[0] // 2                # rows past half copy the first
    return jnp.concatenate([logits[:h], logits[:h]]), new


def altered_token(logits, old, new):
    return logits.at[0].multiply(-1.0), new  # row 0 emits its worst token


FAULTS = {f.__name__: f for f in (unchanged_state, half_batch,
                                  altered_token)}


def broken(fault):
    """``repro.models.build_model`` with ``fault`` applied to every decode
    step's output."""
    from repro.models import build_model

    def build(cfg, mesh=None):
        b = build_model(cfg, mesh)

        def decode_step(params, batch, cache):
            logits, new = b.decode_step(params, batch, cache)
            return fault(logits, cache, new)
        return b.__class__(**{**b.__dict__, "decode_step": decode_step})
    return build


@contextlib.contextmanager
def planted(name: str):
    """Within the block, the program's models carry the fault ``name``."""
    import repro.models
    original = repro.models.build_model
    repro.models.build_model = broken(FAULTS[name])
    try:
        yield
    finally:
        repro.models.build_model = original
