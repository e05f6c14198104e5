"""Random weights for a benchmark configuration, drawn on the device from
the run's seed in one jitted call.

Every random weight is a fixed magnitude times a sign drawn from the seed:
the magnitudes come from one draw that is the same for every run, the
signs from the seed.  Each weight is still N(0, scale^2), and each seed
serves a different model, but the magnitudes alone decide which weights
the balanced pruning keeps.  So every seed gets the same sparsity
pattern, the same tile capacities and packing, and the same plan
footprint: the seed changes the values, not the work.

The tree has the layout the repo's models read (stacked ``[L, ...]``
blocks), as the family's ``draw`` (`bench/families/<family>.py`) lays it
out; the draw itself is the benchmark's, so the program under test and
the plain reference (`bench/reference`) both take their weights from here
and neither takes them from the other.  Leaves are made in the dtype the
configuration serves them in (``model.param_dtype``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import families


def seed_key(seed: int, stream: int = 0):
    """A PRNG key for any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


MAGNITUDE_SEED = 20220203       # the fixed draw of every weight's magnitude


def normal(keys, shape):
    """|N(0, 1)| from the fixed key times a random sign from the seed's."""
    fixed, signed = keys
    return jnp.abs(jax.random.normal(fixed, shape, jnp.float32)) \
        * jax.random.rademacher(signed, shape, jnp.float32)


def split(keys, n: int):
    """``n`` key pairs from a ``(fixed, signed)`` pair, for ``normal``."""
    return list(zip(*(jax.random.split(k, n) for k in keys)))


def make_params(model: dict, seed: int) -> dict:
    """The whole weight tree for ``model`` (a configuration file's
    ``model`` object), on the default device, from ``seed``."""
    draw = families.load(model["family"]).draw
    keys = (seed_key(MAGNITUDE_SEED, stream=1), seed_key(seed, stream=1))
    return jax.jit(lambda k: draw(model, k))(keys)
