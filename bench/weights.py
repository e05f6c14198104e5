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
blocks); the draw itself is the benchmark's, so the program under test and
the plain reference (`bench/reference`) both take their weights from here
and neither takes them from the other.  Leaves are made in the dtype the
configuration serves them in (``model.param_dtype``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


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


def _split(keys, n: int):
    return list(zip(*(jax.random.split(k, n) for k in keys)))


def _dense(m: dict, key) -> dict:
    d, dh, h, kh = m["d_model"], m["head_dim"], m["n_heads"], m["n_kv_heads"]
    f, l, v = m["d_ff"], m["n_layers"], m["vocab_size"]
    dt = jnp.dtype(m["param_dtype"])
    ks = _split(key, 8)

    def mat(k, n_in, n_out):
        return (normal(k, (l, n_in, n_out)) / math.sqrt(n_in)).astype(dt)

    blocks = {
        "wq": mat(ks[0], d, h * dh), "wk": mat(ks[1], d, kh * dh),
        "wv": mat(ks[2], d, kh * dh), "wo": mat(ks[3], h * dh, d),
        "attn_norm": jnp.ones((l, d), dt), "mlp_norm": jnp.ones((l, d), dt),
        "w_gate": mat(ks[4], d, f), "w_up": mat(ks[5], d, f),
        "w_down": mat(ks[6], f, d),
    }
    embed = (normal(ks[7], (v, d)) * 0.02).astype(dt)
    return {"embed": embed, "blocks": blocks,
            "final_norm": jnp.ones((d,), dt)}


_FAMILIES = {"dense": _dense}


def make_params(model: dict, seed: int) -> dict:
    """The whole weight tree for ``model`` (a configuration file's
    ``model`` object), on the default device, from ``seed``."""
    draw = _FAMILIES[model["family"]]
    keys = (seed_key(MAGNITUDE_SEED, stream=1), seed_key(seed, stream=1))
    return jax.jit(lambda k: draw(model, k))(keys)
