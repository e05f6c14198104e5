"""Decoder-only transformer family: dense GQA, MoE, audio/VLM-frontend.

Covers olmo-1b, qwen3-8b, starcoder2-7b, command-r-plus-104b (dense),
deepseek-moe-16b, qwen3-moe-235b-a22b (MoE), musicgen-medium (audio stub
frontend) and internvl2-2b (vision stub frontend).

Layer parameters are stacked on a leading L axis and walked with
``lax.scan`` (+ remat) so compile cost is depth-independent; activations are
annotated with sequence-parallel sharding between layers (DESIGN.md §5).

Sense integration: when ``cfg.sparse_serving`` and the caller has attached
an offline-built projection plan (``params["sparse_plan"]``, an
`engine.plan.ModelPlan` from `engine.plan.plan_transformer`), the prefill
*and* decode paths run every planned projection through the balanced-sparse
kernel path (`engine.execute.apply_fc` — weights pre-encoded at plan time,
impl/blocks fixed per layer).  MoE expert tensors go through the per-expert
path (`engine.execute.apply_expert_fc`: the capacity-dispatch buffer
[E, C, d] hits one pre-encoded balanced-sparse matmul per expert).  The
plan's stacked [L, ...] leaves are scanned alongside ``params["blocks"]``,
so compile cost stays depth-independent.  Training stays dense (the paper
prunes *for inference*; the prune->retrain loop lives in core.pruning).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..distributed import sharding as shd
from ..kernels.kv_cache_update import kv_cache_write_chunk, to_planes
from .api import (ModelBundle, planned_proj as _proj, register_family,
                  serving_plan)
from .layers import (apply_rope, blocked_causal_attention, causal_lm_labels,
                     chunked_cross_entropy, decode_attention_planes,
                     layer_norm, rms_norm)

Array = jax.Array


def _norm(cfg: ModelConfig, x: Array, gamma: Array | None) -> Array:
    if cfg.norm == "nonparam_ln":
        return layer_norm(x, None, None)
    return rms_norm(x, gamma)


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_block(cfg: ModelConfig, rng: Array) -> Dict[str, Array]:
    d, dh = cfg.d_model, cfg.head_dim
    h, kh, f, l = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.n_layers
    ks = jax.random.split(rng, 16)
    dt = _pdtype(cfg)

    def mat(key, *shape):
        scale = 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(key, (l, *shape)) * scale).astype(dt)

    p: Dict[str, Array] = {
        "wq": mat(ks[0], d, h * dh),
        "wk": mat(ks[1], d, kh * dh),
        "wv": mat(ks[2], d, kh * dh),
        "wo": mat(ks[3], h * dh, d),
        "attn_norm": jnp.ones((l, d), dt),
        "mlp_norm": jnp.ones((l, d), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((l, dh), dt)
        p["k_norm"] = jnp.ones((l, dh), dt)
    if cfg.family == "moe":
        e, fs = cfg.n_experts, cfg.d_ff * max(cfg.n_shared_experts, 0)
        p["router"] = mat(ks[4], d, e)
        p["we_gate"] = mat(ks[5], e, d, f)
        p["we_up"] = mat(ks[6], e, d, f)
        p["we_down"] = (jax.random.normal(ks[7], (l, e, f, d))
                        / math.sqrt(f)).astype(dt)
        if fs:
            p["ws_gate"] = mat(ks[8], d, fs)
            p["ws_up"] = mat(ks[9], d, fs)
            p["ws_down"] = (jax.random.normal(ks[10], (l, fs, d))
                            / math.sqrt(fs)).astype(dt)
    else:
        if cfg.mlp == "swiglu":
            p["w_gate"] = mat(ks[4], d, f)
            p["w_up"] = mat(ks[5], d, f)
            p["w_down"] = (jax.random.normal(ks[6], (l, f, d))
                           / math.sqrt(f)).astype(dt)
        else:  # gelu
            p["w_in"] = mat(ks[4], d, f)
            p["w_out"] = (jax.random.normal(ks[5], (l, f, d))
                          / math.sqrt(f)).astype(dt)
    return p


def init_params(cfg: ModelConfig, rng: Array) -> Dict[str, Any]:
    k_emb, k_blk, k_fr = jax.random.split(rng, 3)
    params: Dict[str, Any] = {
        "embed": (jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model))
                  * 0.02).astype(_pdtype(cfg)),
        "blocks": _init_block(cfg, k_blk),
        "final_norm": jnp.ones((cfg.d_model,), _pdtype(cfg)),
    }
    if cfg.frontend:
        params["frontend_proj"] = (
            jax.random.normal(k_fr, (cfg.frontend_dim, cfg.d_model))
            / math.sqrt(cfg.frontend_dim)).astype(_pdtype(cfg))
    return params


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

def param_specs(cfg: ModelConfig, mesh) -> Dict[str, Any]:
    if mesh is None:
        return jax.tree.map(lambda _: P(), init_shapes(cfg),
                            is_leaf=lambda x: isinstance(x, tuple))
    d, dh = cfg.d_model, cfg.head_dim
    h, kh, f = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff

    def ls(shape, plan):  # layer-stacked: leading L replicated
        return shd.logical_spec(mesh, (0, *shape), [None, *plan])

    blocks: Dict[str, Any] = {
        "wq": ls((d, h * dh), [[("data", "pod")], ["model"]]),
        "wk": ls((d, kh * dh), [[("data", "pod")], ["model"]]),
        "wv": ls((d, kh * dh), [[("data", "pod")], ["model"]]),
        "wo": ls((h * dh, d), [["model"], [("data", "pod")]]),
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
    }
    if cfg.qk_norm:
        blocks["q_norm"] = P(None, None)
        blocks["k_norm"] = P(None, None)
    if cfg.family == "moe":
        e = cfg.n_experts
        fs = cfg.d_ff * max(cfg.n_shared_experts, 0)
        blocks["router"] = ls((d, e), [[("data", "pod")], None])
        blocks["we_gate"] = ls((e, d, f), [["model"], [("data", "pod")], None])
        blocks["we_up"] = ls((e, d, f), [["model"], [("data", "pod")], None])
        blocks["we_down"] = ls((e, f, d), [["model"], None, [("data", "pod")]])
        if fs:
            blocks["ws_gate"] = ls((d, fs), [[("data", "pod")], ["model"]])
            blocks["ws_up"] = ls((d, fs), [[("data", "pod")], ["model"]])
            blocks["ws_down"] = ls((fs, d), [["model"], [("data", "pod")]])
    else:
        if cfg.mlp == "swiglu":
            blocks["w_gate"] = ls((d, f), [[("data", "pod")], ["model"]])
            blocks["w_up"] = ls((d, f), [[("data", "pod")], ["model"]])
            blocks["w_down"] = ls((f, d), [["model"], [("data", "pod")]])
        else:
            blocks["w_in"] = ls((d, f), [[("data", "pod")], ["model"]])
            blocks["w_out"] = ls((f, d), [["model"], [("data", "pod")]])
    specs: Dict[str, Any] = {
        # vocab over model (sharded softmax/CE), d over data (FSDP)
        "embed": shd.logical_spec(mesh, (cfg.vocab_size, d),
                                  [["model"], [("data", "pod")]]),
        "blocks": blocks,
        "final_norm": P(None),
    }
    if cfg.frontend:
        specs["frontend_proj"] = shd.logical_spec(
            mesh, (cfg.frontend_dim, d), [[("data", "pod")], ["model"]])
    return specs


def init_shapes(cfg: ModelConfig):
    return jax.eval_shape(lambda r: init_params(cfg, r),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def _strip_fsdp(spec: P) -> P:
    """Use-time spec: drop the leading stacked-L dim and the data/pod (FSDP)
    dims, keep the model (TP) dims."""
    def clean(d):
        if d is None:
            return None
        names = (d,) if isinstance(d, str) else tuple(d)
        kept = tuple(n for n in names if n == "model")
        return kept[0] if len(kept) == 1 else (kept or None)
    return P(*[clean(d) for d in list(spec)[1:]])


def use_specs(cfg: ModelConfig, mesh) -> Dict[str, P]:
    return {k: _strip_fsdp(s)
            for k, s in param_specs(cfg, mesh)["blocks"].items()}


def gather_for_use(cfg: ModelConfig, mesh, lp: Dict[str, Array],
                   specs: Dict[str, P]) -> Dict[str, Array]:
    """ZeRO-3 style per-layer weight materialization, in compute dtype.

    Cast each layer parameter to bf16 *then* constrain its FSDP dims away:
    the all-gather moves half the bytes and is weight-sized.  Without this
    XLA resolves the sharded contraction with activation-sized all-reduces
    over ``data`` — measured 60x more collective traffic on the 104B train
    cell (EXPERIMENTS.md §Perf B).  Gradients flow back through the
    constraint as reduce-scatters onto the FSDP shards (ZeRO grad flow).
    """
    if mesh is None:
        return lp
    cd = _cdtype(cfg)
    out = {}
    for k, v in lp.items():
        sp = specs.get(k)
        w = v.astype(cd) if jnp.issubdtype(v.dtype, jnp.floating) else v
        if sp is not None and len(sp) == v.ndim:
            w = jax.lax.with_sharding_constraint(w, shd.named(mesh, sp))
        out[k] = w
    return out


# ---------------------------------------------------------------------------
# Block forward
# ---------------------------------------------------------------------------

def _attn(cfg: ModelConfig, lp, h: Array, positions: Array, mesh,
          kv_override=None, cache_len=None, plan_layers=None) -> tuple:
    """Attention sublayer.  Returns (out, (k, v)) — k/v for cache building.

    kv_override: (k_cache, v_cache, cache_len) for decode."""
    b, s, _ = h.shape
    dh, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    cd = _cdtype(cfg)
    x = _norm(cfg, h, lp["attn_norm"]).astype(cd)
    q = _proj(lp, plan_layers, "wq", x, cd).reshape(b, s, nh, dh)
    k = _proj(lp, plan_layers, "wk", x, cd).reshape(b, s, nkv, dh)
    v = _proj(lp, plan_layers, "wv", x, cd).reshape(b, s, nkv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"])
        k = rms_norm(k, lp["k_norm"])
    q = apply_rope(q, positions, theta=cfg.rope_theta)
    k = apply_rope(k, positions, theta=cfg.rope_theta)
    if kv_override is not None:
        # plane-layout cache [B*KH, Smax, dh]; s >= 1 new tokens land at
        # rows clen .. clen + s - 1 of each sequence's planes (s > 1 is a
        # prefill chunk attending to the cached prefix)
        k_cache, v_cache, clen = kv_override
        k_t = to_planes(k).astype(k_cache.dtype)            # [B*KH, s, dh]
        v_t = to_planes(v).astype(v_cache.dtype)
        pos_rep = jnp.repeat(clen, nkv)                     # [B*KH]
        with jax.named_scope("kv_update"):
            if cfg.cache_update == "scatter":
                # row-sized indexed write: O(B*KH*s*dh) traffic instead of
                # a full-cache rewrite (§Perf C) — the XLA twin of the
                # Pallas `kv_cache_update` kernel; plane layout keeps it
                # genuinely in place (no relayout around the write).
                k_cache = kv_cache_write_chunk(k_cache, k_t, pos_rep)
                v_cache = kv_cache_write_chunk(v_cache, v_t, pos_rep)
            else:
                # mask-select rewrite: elementwise over the cache,
                # trivially partition-safe for any cache sharding (the
                # baseline).  The one-hot einsum is exact (products with
                # 1.0/0.0), so this and the scatter path are
                # bitwise-identical.
                smax = k_cache.shape[1]
                rows = pos_rep[:, None] + jnp.arange(s)[None, :]
                oh = rows[:, :, None] == jnp.arange(smax)[None, None, :]
                written = oh.any(axis=1)[..., None]     # [B*KH, Smax, 1]
                ohf = oh.astype(k_cache.dtype)
                k_cache = jnp.where(written,
                                    jnp.einsum("pcs,pcd->psd", ohf, k_t),
                                    k_cache)
                v_cache = jnp.where(written,
                                    jnp.einsum("pcs,pcd->psd", ohf, v_t),
                                    v_cache)
        with jax.named_scope("attention"):
            o = decode_attention_planes(q, k_cache.astype(_cdtype(cfg)),
                                        v_cache.astype(_cdtype(cfg)), clen)
        kv_out = (k_cache, v_cache)
    else:
        q_chunk = min(cfg.q_chunk, s)
        kv_chunk = min(cfg.kv_chunk, s)
        while s % q_chunk:
            q_chunk //= 2
        while s % kv_chunk:
            kv_chunk //= 2
        with jax.named_scope("attention"):
            o = blocked_causal_attention(q, k, v, q_chunk=max(q_chunk, 1),
                                         kv_chunk=max(kv_chunk, 1),
                                         mesh=mesh)
        kv_out = (k, v)
    o = o.reshape(b, s, nh * dh)
    return _proj(lp, plan_layers, "wo", o, cd), kv_out


def _lm_head(cfg: ModelConfig, params, h: Array) -> Array:
    """Final norm and the tied-embedding logits of the last position."""
    with jax.named_scope("lm_head"):
        h = _norm(cfg, h, params["final_norm"])
        return (h[:, -1].astype(jnp.float32)
                @ params["embed"].astype(jnp.float32).T)


def _mlp(cfg: ModelConfig, lp, h: Array, plan_layers=None) -> Array:
    cd = _cdtype(cfg)
    x = _norm(cfg, h, lp["mlp_norm"]).astype(cd)
    if cfg.mlp == "swiglu":
        g = jax.nn.silu(_proj(lp, plan_layers, "w_gate", x, cd)) \
            * _proj(lp, plan_layers, "w_up", x, cd)
        return _proj(lp, plan_layers, "w_down", g, cd)
    g = jax.nn.gelu(_proj(lp, plan_layers, "w_in", x, cd), approximate=True)
    return _proj(lp, plan_layers, "w_out", g, cd)


def _expert_proj(lp, plan_layers, name: str, x: Array, cd) -> Array:
    """One per-expert projection on the dispatch buffer x: [E, C, n_in].

    Planned expert layers run the per-expert balanced-sparse kernels
    (`engine.execute.apply_expert_fc`, weights pre-encoded per expert at
    plan time); otherwise the dense batched einsum.  The contraction is
    the same for gate/up ([E, d, f]) and down ([E, f, d]) tensors."""
    if plan_layers is not None and name in plan_layers:
        from ..engine.execute import apply_expert_fc
        return apply_expert_fc(x, plan_layers[name]).astype(cd)
    return jnp.einsum("ecn,enf->ecf", x, lp[name].astype(cd))


def _moe(cfg: ModelConfig, lp, h: Array, mesh, plan_layers=None) -> tuple:
    """Capacity-dispatch MoE FFN (GShard-style, EP over ``model``).

    Returns (out, aux_loss).  Long sequences are processed in segments of
    <= ``_MOE_SEG`` tokens (scan): the dispatch scatter/gather buffers are
    O(tokens), so segmentation bounds them — without it the 1M-token
    qwen3-moe prefill cell overflows HBM (EXPERIMENTS.md §Dry-run).
    """
    cd = _cdtype(cfg)
    b, s, d = h.shape
    x = _norm(cfg, h, lp["mlp_norm"]).astype(cd)
    # segment along S (keeping the B-sharded layout intact — segmenting the
    # flattened B*S dim would split the batch sharding and force re-gathers)
    seg_s = max(1, _MOE_SEG // b)
    while s % seg_s:
        seg_s //= 2
    if s > seg_s:
        def one(_, xseg):                       # xseg: [b, seg_s, d]
            y, aux = _moe_tokens(cfg, lp, xseg.reshape(b * seg_s, d), mesh,
                                 plan_layers=plan_layers)
            return None, (y.reshape(b, seg_s, d), aux)
        xs = jnp.moveaxis(x.reshape(b, s // seg_s, seg_s, d), 1, 0)
        _, (y, auxes) = jax.lax.scan(one, None, xs)
        y = jnp.moveaxis(y, 0, 1).reshape(b, s, d)
        return y, jnp.mean(auxes)
    y, aux = _moe_tokens(cfg, lp, x.reshape(b * s, d), mesh,
                         plan_layers=plan_layers)
    return y.reshape(b, s, d), aux


_MOE_SEG = 65536


def _moe_tokens(cfg: ModelConfig, lp, xf: Array, mesh,
                plan_layers=None) -> tuple:
    cd = _cdtype(cfg)
    t, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = (xf @ lp["router"].astype(cd)).astype(jnp.float32)   # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                          # [T, K]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # load-balancing auxiliary (Switch): E * sum_e f_e * p_e
    assign = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], eidx].set(1.0)
    aux = e * jnp.mean(assign.mean(0) * probs.mean(0))
    # capacity + position within expert
    cap = max(8, int(math.ceil(t * k / e * cfg.capacity_factor)))
    oh = jax.nn.one_hot(eidx.reshape(-1), e, dtype=jnp.int32)     # [T*K, E]
    pos = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1               # [T*K]
    pos = pos.reshape(t, k)
    valid = (pos < cap).astype(cd)
    slot = (eidx * cap + jnp.clip(pos, 0, cap - 1)).reshape(-1)   # [T*K]
    # dispatch: scatter tokens into [E*C, D]
    xin = jnp.broadcast_to(xf[:, None, :], (t, k, d)).reshape(t * k, d)
    xin = xin * valid.reshape(-1, 1)
    buf = jnp.zeros((e * cap, d), cd).at[slot].add(xin)
    buf = buf.reshape(e, cap, d)
    if mesh is not None:
        buf = jax.lax.with_sharding_constraint(
            buf, shd.named(mesh, shd.logical_spec(
                mesh, (e, cap, d), [["model"], [("data", "pod")], None])))
    hidden = jax.nn.silu(_expert_proj(lp, plan_layers, "we_gate", buf, cd)) \
        * _expert_proj(lp, plan_layers, "we_up", buf, cd)
    eout = _expert_proj(lp, plan_layers, "we_down", hidden, cd)
    eout = eout.reshape(e * cap, d)
    # combine: gather each (t, k) slot, weight by gate
    y = eout[slot].reshape(t, k, d)
    y = (y * (gate.astype(cd) * valid)[..., None]).sum(axis=1)
    if cfg.n_shared_experts:
        g = jax.nn.silu(_proj(lp, plan_layers, "ws_gate", xf, cd)) \
            * _proj(lp, plan_layers, "ws_up", xf, cd)
        y = y + _proj(lp, plan_layers, "ws_down", g, cd)
    return y, aux


def _block(cfg: ModelConfig, mesh, h: Array, lp, positions: Array,
           kv_override=None, plan_layers=None):
    """One transformer block. Returns (h, (k, v), aux_loss)."""
    attn_out, kv = _attn(cfg, lp, h, positions, mesh, kv_override=kv_override,
                         plan_layers=plan_layers)
    h = h + attn_out.astype(h.dtype)
    if cfg.family == "moe":
        mlp_out, aux = _moe(cfg, lp, h, mesh, plan_layers=plan_layers)
    else:
        mlp_out, aux = _mlp(cfg, lp, h, plan_layers=plan_layers), \
            jnp.float32(0.0)
    h = h + mlp_out.astype(h.dtype)
    if mesh is not None and kv_override is None:
        h = shd.with_hidden_sharding(mesh, h)
    return h, kv, aux


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def _embed_tokens(cfg: ModelConfig, params, batch, mesh) -> Array:
    tokens = batch["tokens"]
    h = jnp.take(params["embed"], tokens, axis=0).astype(_cdtype(cfg))
    if cfg.frontend and "frontend_embed" in batch:
        fe = batch["frontend_embed"].astype(_cdtype(cfg))
        proj = fe @ params["frontend_proj"].astype(_cdtype(cfg))
        n = proj.shape[1]
        h = jnp.concatenate([proj, h[:, n:]], axis=1)
    if mesh is not None and h.shape[1] > 1:
        h = shd.with_hidden_sharding(mesh, h)
    return h


# ---------------------------------------------------------------------------
# Bundle
# ---------------------------------------------------------------------------

@register_family("transformer")
def build(cfg: ModelConfig, mesh=None) -> ModelBundle:
    remat_policy = jax.checkpoint_policies.nothing_saveable
    uspecs = use_specs(cfg, mesh) if (mesh is not None and
                                      cfg.zero3_gather) else None

    def _use(lp):
        if uspecs is None:
            return lp
        return gather_for_use(cfg, mesh, lp, uspecs)

    def _serving_plan(params):
        return serving_plan(cfg, params)

    def init(rng):
        return init_params(cfg, rng)

    def _backbone(params, batch, h, positions):
        """scan over stacked blocks; returns (h, aux_total)."""
        def body(carry, lp):
            h, aux = carry
            h, _, a = _block(cfg, mesh, h, _use(lp), positions)
            return (h, aux + a), None
        body_fn = jax.checkpoint(body, policy=remat_policy) if cfg.remat else body
        (h, aux), _ = jax.lax.scan(body_fn, (h, jnp.float32(0.0)),
                                   params["blocks"])
        return h, aux

    def train_loss(params, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h = _embed_tokens(cfg, params, batch, mesh)
        h, aux = _backbone(params, batch, h, positions)
        h = _norm(cfg, h, params["final_norm"])
        labels, mask = causal_lm_labels(tokens)
        if cfg.frontend and "frontend_embed" in batch:
            n = batch["frontend_embed"].shape[1]
            mask = mask.at[:, :max(n - 1, 0)].set(0.0)
        loss = chunked_cross_entropy(h, params["embed"], labels,
                                     chunk=min(cfg.loss_chunk, s), mask=mask)
        return loss + cfg.router_aux_weight * aux

    def prefill(params, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h = _embed_tokens(cfg, params, batch, mesh)
        plan = _serving_plan(params)

        def body(carry, xs):
            lp, plp = xs if plan is not None else (xs, None)
            h, = carry
            h, (k, v), _ = _block(cfg, mesh, h, lp, positions,
                                  plan_layers=plp)
            # cache leaves leave prefill in plane layout [B*KH, S, dh] —
            # the end-to-end decode/serving cache layout
            return (h,), (to_planes(k).astype(jnp.bfloat16),
                          to_planes(v).astype(jnp.bfloat16))
        body_fn = jax.checkpoint(body, policy=remat_policy) if cfg.remat else body
        xs = (params["blocks"], plan.layers) if plan is not None \
            else params["blocks"]
        (h,), (ks, vs) = jax.lax.scan(body_fn, (h,), xs)
        logits = _lm_head(cfg, params, h)
        return logits, {"k": ks, "v": vs}

    def init_cache(batch_size, max_len):
        l, kh, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        # plane layout: [L, B*KH, Smax, dh] — plane b*KH + h owns one
        # sequence/head's rows, so decode's indexed write touches [1, dh]
        # rows with no relayout (kernels/kv_cache_update.py)
        shape = (l, batch_size * kh, max_len, dh)
        return {"k": jnp.zeros(shape, jnp.bfloat16),
                "v": jnp.zeros(shape, jnp.bfloat16)}

    def decode_step(params, batch, cache):
        """One decode step of ``s >= 1`` tokens per live sequence: s == 1
        is classic decode, s > 1 a prefill chunk (tokens attend to the
        cached prefix + causally within the chunk)."""
        tokens, clen = batch["tokens"], batch["cache_len"]
        b, s = tokens.shape
        positions = clen[:, None] + jnp.arange(s)[None, :]
        h = _embed_tokens(cfg, params, batch, mesh)
        plan = _serving_plan(params)

        def body(h, xs):
            if plan is not None:
                lp, kc, vc, plp = xs
            else:
                (lp, kc, vc), plp = xs, None
            h, (kc, vc), _ = _block(cfg, mesh, h, lp, positions,
                                    kv_override=(kc, vc, clen),
                                    plan_layers=plp)
            return h, (kc, vc)
        xs = (params["blocks"], cache["k"], cache["v"])
        if plan is not None:
            xs = xs + (plan.layers,)
        h, (ks, vs) = jax.lax.scan(body, h, xs)
        logits = _lm_head(cfg, params, h)
        return logits, {"k": ks, "v": vs}

    def specs():
        return param_specs(cfg, mesh)

    def cache_specs(batch_size):
        if mesh is None:
            return {"k": P(), "v": P()}
        # planes (B*KH) over dp then model when divisible; rows replicated
        # so the per-plane indexed write stays partition-local
        kv_spec = shd.kv_plane_spec(mesh, batch_size * cfg.n_kv_heads,
                                    lead_dims=1)
        return {"k": kv_spec, "v": kv_spec}

    return ModelBundle(cfg=cfg, init=init, train_loss=train_loss,
                       prefill=prefill, decode_step=decode_step,
                       init_cache=init_cache, param_specs=specs,
                       cache_specs=cache_specs)
