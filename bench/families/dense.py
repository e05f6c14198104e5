"""The dense decoder family (olmo-1b): every layer attends and runs a
gated MLP; the LM head is the tied embedding."""
from __future__ import annotations

import math

import jax.numpy as jnp

from bench.weights import normal, split

SCOPES = ("kv_update", "attention", "lm_head")


def draw(m: dict, key) -> dict:
    d, dh, h, kh = m["d_model"], m["head_dim"], m["n_heads"], m["n_kv_heads"]
    f, l, v = m["d_ff"], m["n_layers"], m["vocab_size"]
    dt = jnp.dtype(m["param_dtype"])
    ks = split(key, 8)

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


def projections(m: dict) -> dict:
    d, f = m["d_model"], m["d_ff"]
    qd, kd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    attn = {"wq": (d, qd), "wk": (d, kd), "wv": (d, kd), "wo": (qd, d)}
    mlp = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    return {k: (*v, m["n_layers"]) for k, v in {**attn, **mlp}.items()}


def attention_flops(m: dict) -> float:
    """Scores and the weighted sum, in every layer."""
    return 4.0 * m["n_heads"] * m["head_dim"] * m["n_layers"]


def head(params: dict):
    return params["embed"]
