"""Plain float32 reference of the dense decoder family (olmo-1b).

The repo's equations (``models/transformer.py``), written out once more:

    h_0 = embed[tokens]
    per layer:  x = LN(h)                  (non-parametric LayerNorm)
                q, k, v = x Wq, x Wk, x Wv (rotary on q and k)
                h = h + attn(q, k, v) Wo   (causal softmax)
                x = LN(h)
                h = h + (silu(x Wg) * (x Wu)) Wd
    logits = LN(h) embed^T                 (tied head)

Every planned projection is balanced-pruned here from the unpruned
weights (`common.prune_balanced`), so nothing the plan built is read.
A whole sequence runs at once; layers go through a ``lax.scan`` so that
only one layer's pruned f32 weights are alive at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import F32, causal_attention, mm, prune_balanced, rope


def _layer_norm(x, eps):
    x = x.astype(F32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _norm(m, x, gamma, eps):
    if m.get("norm", "rmsnorm") == "nonparam_ln":
        return _layer_norm(x, eps)
    x = x.astype(F32)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) \
        * gamma.astype(F32)


def hidden(spec: dict, params: dict, tokens, lowp: bool = False):
    """Final normalised hidden states ``[T, d]`` for one sequence."""
    m, eps = spec["model"], spec["norm_eps"]
    sparsity, planned = m["w_sparsity"], set(spec["planned"])
    h_, kh, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    theta = m.get("rope_theta", 10000.0)
    t = tokens.shape[0]
    pos = jnp.arange(t)

    def w(lp, name):
        x = lp[name]
        return prune_balanced(x, sparsity) if name in planned else x

    def layer(h, lp):
        x = _norm(m, h, lp["attn_norm"], eps)
        q = mm(x, w(lp, "wq"), lowp).reshape(t, h_, dh)
        k = mm(x, w(lp, "wk"), lowp).reshape(t, kh, dh)
        v = mm(x, w(lp, "wv"), lowp).reshape(t, kh, dh)
        q, k = rope(q, pos, theta), rope(k, pos, theta)
        rep = h_ // kh
        o = causal_attention(q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1))
        h = h + mm(o.reshape(t, h_ * dh), w(lp, "wo"), lowp)
        x = _norm(m, h, lp["mlp_norm"], eps)
        g = jax.nn.silu(mm(x, w(lp, "w_gate"), lowp)) * mm(x, w(lp, "w_up"),
                                                            lowp)
        return h + mm(g, w(lp, "w_down"), lowp), None

    h = params["embed"][tokens].astype(F32)
    h, _ = jax.lax.scan(layer, h, params["blocks"])
    return _norm(m, h, params["final_norm"], eps)
