"""Pieces shared by the plain float32 references.

Everything here is straightforward ``jax.numpy`` in float32, traced under
``jax.default_matmul_precision("highest")`` by the callers.  Nothing is
imported from the program under test.

``lowp=True`` selects the control: every matmul's operands are rounded to
float8 (e4m3, one scale per tensor, the usual fp8 recipe) before an f32
product.  It is the precision step below the configuration's bfloat16, and
the correctness limits must reject it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
_FP8_MAX = 448.0            # largest finite float8_e4m3fn


def keep_per_row(n_in: int, sparsity: float) -> int:
    """Weights each output channel keeps at a given zero fraction."""
    return max(1, min(n_in, int(round(n_in * (1.0 - sparsity)))))


def prune_balanced(w, sparsity: float):
    """``w [n_in, n_out]`` with every output channel cut to its K
    largest-magnitude weights (ties to the lower input index), as f32."""
    n_in = w.shape[0]
    k = keep_per_row(n_in, sparsity)
    wt = w.T.astype(F32)                                   # [n_out, n_in]
    _, idx = jax.lax.top_k(jnp.abs(wt), k)
    keep = jnp.zeros(wt.shape, bool).at[
        jnp.arange(wt.shape[0])[:, None], idx].set(True)
    return jnp.where(keep, wt, 0.0).T


def fp8(x):
    """Round to float8 e4m3 with one per-tensor scale, back in f32."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(x, w, lowp: bool = False):
    """``x @ w`` in f32 (operands rounded to fp8 first for the control)."""
    x, w = x.astype(F32), w.astype(F32)
    if lowp:
        x, w = fp8(x), fp8(w)
    return x @ w


def rms_norm(x, gamma, eps: float):
    x = x.astype(F32)
    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * gamma.astype(F32)


def rope(x, positions, theta: float):
    """Split-half rotary embedding.  x: [T, H, dh]; positions: [T]."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = positions[:, None].astype(F32) * freqs            # [T, dh/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(q, k, v, q_block: int = 256):
    """Softmax attention of every position over positions <= its own.
    q, k, v: [T, H, dh] f32; queries go in blocks of ``q_block`` rows so
    the score matrix never holds T x T at once."""
    t, h, dh = q.shape
    qb = min(q_block, t)
    assert t % qb == 0, (t, qb)
    scale = 1.0 / math.sqrt(dh)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)    # [qb, H, dh]
        s = jnp.einsum("qhd,khd->hqk", qi, k) * scale
        pos = i * qb + jnp.arange(qb)
        s = jnp.where(jnp.arange(t)[None, None, :] <= pos[None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(t // qb))          # [nb, qb, H, dh]
    return out.reshape(t, h, dh)


def gaps(h_ref, h_ctl, embed, served, lowp_head: bool = True):
    """Per checked position: how far the served token's reference logit
    lies below the reference's best, and the same for the token the
    control ranks first.

    h_ref / h_ctl: [P, d] final hidden states at the checked positions;
    served: [P] token ids.  Returns (served_gap [P], control_gap [P])."""
    e = embed.astype(F32)
    ref = h_ref.astype(F32) @ e.T                           # [P, V]
    best = ref.max(axis=-1)
    served_gap = best - jnp.take_along_axis(ref, served[:, None], 1)[:, 0]
    ctl = mm(h_ctl, e.T, lowp=lowp_head)
    ctl_top = jnp.argmax(ctl, axis=-1)
    control_gap = best - jnp.take_along_axis(ref, ctl_top[:, None], 1)[:, 0]
    return served_gap, control_gap
