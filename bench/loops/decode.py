"""The loop for ``kind: decode`` mixes: greedy decode steps back to back.

Set-up draws the weights, builds the plan, prefills the batch's prompts
(``bundle.prefill``, the wide kernel) into a cache of ``capacity`` rows
and compiles the decode step.  The window then runs
``bundle.decode_step`` (the skinny kernel at M = batch) with the greedy
token and the cache length fed back on the device, one step in flight
while the host waits for the one before; nothing crosses from the host
to the device inside the window.  Should the cache fill, every row starts
over from its prompt, so the shapes never change and nothing compiles:
``cache_len`` goes back to the prompt length (the rows past it of a leaf
with a sequence axis, as K/V, are masked and then overwritten), and every
leaf with no sequence axis (a recurrent state, as an SSM's) gets back the
value it had after the prefill, kept in set-up for that and put back by
a function compiled in set-up.  The dense family's cache has no such leaf,
so it keeps no copy.  The check reads the rows of the first pass, the
longest.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, traffic, work
from bench.weights import make_params


def merge_prefill(cache, prefill_cache):
    """Write a prefill's cache into an empty decode cache at offset 0."""
    return jax.tree.map(
        lambda z, pf: jax.lax.dynamic_update_slice(
            z, pf.astype(z.dtype), (0,) * z.ndim), cache, prefill_cache)


def recurrent_leaves(init_cache, batch: int, capacity: int) -> tuple:
    """Positions, in the flattened cache, of the leaves with no sequence
    axis: those whose shapes are the same at ``capacity`` rows and at one
    more."""
    a, b = (jax.tree.leaves(jax.eval_shape(lambda: init_cache(batch, n)))
            for n in (capacity, capacity + 1))
    return tuple(i for i, (x, y) in enumerate(zip(a, b))
                 if x.shape == y.shape)


def rewinder(cache, which: tuple, donate: bool = False):
    """A copy of the leaves ``which`` of ``cache`` as they are now, and
    ``restore(cache, copy)``, compiled here, which puts them back into a
    later cache (donated, with ``donate``) and leaves the other leaves
    alone.  ``(None, None)`` where ``which`` is empty."""
    if not which:
        return None, None
    tree = jax.tree.structure(cache)
    saved = jax.jit(lambda ls: [jnp.copy(x) for x in ls])(
        [jax.tree.leaves(cache)[i] for i in which])

    def restore(c, kept):
        leaves = jax.tree.leaves(c)
        for i, x in zip(which, kept):
            leaves[i] = jnp.copy(x)     # the copy is kept for the next pass
        return jax.tree.unflatten(tree, leaves)
    restore = jax.jit(restore, donate_argnums=(0,) if donate else ()).lower(
        cache, saved).compile()
    return saved, restore


def build(ctx, params):
    """The program under test: plan, prefill, decode step.  Returns the
    serving params and the plan."""
    from repro.engine.plan import plan_model

    m, mix = ctx.spec["model"], ctx.mix
    cfg = ctx.model_config()
    t0 = time.monotonic()
    plan = plan_model(cfg, params, sparsity=m["w_sparsity"],
                      m_hint=mix["batch"] * mix["prompt_len"],
                      decode_m=mix["batch"], tune="off")
    jax.block_until_ready(plan)
    ctx.counters["plan_build_s"] = time.monotonic() - t0
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[bench] plan built in {ctx.counters['plan_build_s']!r} s: "
          f"impl_mix={plan.impl_mix()} degraded={plan.degraded_mix()} "
          f"peak_bytes={stats.get('peak_bytes_in_use')} "
          f"in_use_bytes={stats.get('bytes_in_use')}", flush=True)
    blocks = {k: v for k, v in params["blocks"].items()
              if k not in plan.layers}
    return {**params, "blocks": blocks, "sparse_plan": plan}


def run(ctx) -> dict:
    from repro.models import build_model

    spec, mix = ctx.spec, ctx.mix
    m = spec["model"]
    bsz, plen, cap = mix["batch"], mix["prompt_len"], mix["capacity"]
    bundle = build_model(ctx.model_config())
    sp = build(ctx, make_params(m, ctx.seed))
    prompts = traffic.decode_prompts(mix, ctx.seed, m["vocab_size"])

    logits, pcache = jax.jit(bundle.prefill)(
        sp, {"tokens": jnp.asarray(prompts)})
    first = jnp.argmax(logits, -1).astype(jnp.int32)
    del logits
    cache = jax.jit(lambda pc: merge_prefill(bundle.init_cache(bsz, cap),
                                             pc))(pcache)
    del pcache
    on_chip = jax.default_backend() != "cpu"     # the CPU cannot donate
    kept, restore = rewinder(
        cache, recurrent_leaves(bundle.init_cache, bsz, cap), on_chip)

    def step(p, tok, clen, c):
        lg, c = bundle.decode_step(
            p, {"tokens": tok[:, None],
                "cache_len": jnp.full((bsz,), clen, jnp.int32)}, c)
        return jnp.argmax(lg, -1).astype(jnp.int32), clen + 1, c
    prompt_end = jax.device_put(np.int32(plen))
    step = jax.jit(step, donate_argnums=(3,) if on_chip else ()).lower(
        sp, first, prompt_end, cache).compile()
    jax.block_until_ready((first, cache, prompt_end, kept))

    per_tok = work.token_flops(spec, np.arange(cap + 1))
    spmm_step = work.spmm_forward(spec, bsz).least_s(ctx.peak)
    served = [first]                  # the first pass's tokens, for the check
    tok, clen, n, passes, steps, flops = first, prompt_end, plen, 1, 0, 0.0
    ctx.end_setup()
    with ctx.window():
        t0 = t_done = time.monotonic()
        prev, longest = None, (0.0, 0)
        while True:
            if n == cap:
                clen, n, passes = prompt_end, plen, passes + 1
                if restore is not None:
                    with ctx.span("bench.rewind"):
                        cache = restore(cache, kept)
            with ctx.span("bench.decode_step"):
                nxt, clen, cache = step(sp, tok, clen, cache)
            if passes == 1:
                served.append(nxt)
            if prev is not None:
                with ctx.span("bench.sync"):
                    prev.block_until_ready()
                t = time.monotonic()
                longest = max(longest, (t - t_done, steps))
                t_done = t
            prev, tok = nxt, nxt
            flops += bsz * per_tok[n + 1]
            n += 1
            steps += 1
            if time.monotonic() - t0 >= ctx.seconds:
                break
        with ctx.span("bench.sync"):
            prev.block_until_ready()
        window_s = time.monotonic() - t0
    stats = jax.devices()[0].memory_stats() or {}
    ctx.counters.update(
        memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)),
        compiles_in_window=len(ctx.compiles)
        - ctx.counters["compiles_at_window"],
        steps=steps, window_s=window_s, flops=flops,
        spmm_least_s=steps * spmm_step, attempted=bsz * passes, failed=0)
    print(f"[bench] window: {steps} steps x {bsz} rows in {window_s!r} s, "
          f"{passes} pass(es) over the cache, compiles inside: "
          f"{ctx.counters['compiles_in_window']}, longest step "
          f"{longest[0]!r} s (step {longest[1]})", flush=True)

    toks = np.stack([np.asarray(t) for t in served], 1)
    del sp, cache, step, tok, nxt, prev, first, clen, prompt_end, served, \
        kept, restore
    ctx.counters["ref_s"] = check(ctx, prompts, toks)
    return {"decode_tok_s": steps * bsz / window_s,
            "peak_hbm_gb": ctx.counters["memory_peak_bytes"] / 1e9}


def check(ctx, prompts, toks) -> float:
    """Compare every row of the first pass with the reference; returns
    the seconds the check took.  With ``ctx.control`` the number compared
    is the fp8 control's, read at the same positions."""
    from bench.run import free_device_memory
    free_device_memory()
    t0 = time.monotonic()
    reqs = [reference.Served(np.concatenate([prompts[i], toks[i, :-1]]),
                             prompts.shape[1] - 1, toks[i])
            for i in range(prompts.shape[0])]
    out = reference.compare(ctx.spec, ctx.seed, reqs, control=ctx.control)
    ctx.counters["max_logit_gap"] = out["max_logit_gap"]
    gap = out["control_max_logit_gap"] if ctx.control \
        else out["max_logit_gap"]
    ctx.check("max_logit_gap", gap, ctx.limit("max_logit_gap"))
    print(f"[bench] reference: {out['tokens_checked']} served tokens "
          f"checked in {time.monotonic() - t0!r} s", flush=True)
    return time.monotonic() - t0
