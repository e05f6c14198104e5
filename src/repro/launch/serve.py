"""Serving launcher: batched prefill+decode with Sense sparse weights.

``python -m repro.launch.serve --arch olmo-1b --smoke --sparsity 0.5``

Demonstrates the paper's deployment story on an LM through the layer-plan
engine: one offline pass (`engine.plan.plan_model` — every family: dense /
MoE / audio / vlm transformers, RWKV6, Zamba2) balanced-prunes every
covered projection (equal NZE per output channel — the load-balance
invariant), picks the per-layer dataflow mode (§V-C) and kernel impl
(§VI-F), and pre-encodes the weights to the kernel-native format; prefill
and decode then *execute the plan* — the balanced-sparse kernels run on the
real token path, asserted via the engine's dispatch stats (no more timing
dense matmuls on zeroed weights).  MoE expert tensors additionally assert
the per-expert path (`expert_balanced_spmm`) dispatched.  Reports tokens/s
dense vs sparse, the per-family RIF/RWF/ON_CHIP mode mix and kernel-impl
mix, a sparse-vs-masked-dense logits parity check, and the compressed
weight footprint (bitmap format, Fig.8).

``--tune cached|sweep`` routes every layer's `BlockChoice` through the
measured autotuner (`kernels/autotune.py`): warm cache entries win, cold
keys fall back to the static VMEM model ("cached") or are swept and
persisted ("sweep"); the report lists tuned-vs-static choice deltas and
the per-source mix.  Only the Pallas impl consumes block sizes, so tuning
bites with ``--impl pallas`` (or auto on a TPU backend).

The "dense" throughput baseline runs the masked-dense reference weights
(the plan's pruned weights densified — same shapes and FLOPs as the
unpruned model); the unpruned projections are released once the plan and
the reference exist.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs import ARCHS, get_config, get_smoke
from ..core.compression import compressed_bits
from ..engine import execute as engine_execute
from ..engine import plan as engine_plan
from . import cost_model


def greedy_generate(bundle, params, prompt, steps: int, max_len: int, *,
                    prefill_fn=None, decode_fn=None):
    """Greedy decode; pass prejitted fns to keep compile out of timed runs.

    ``max_len`` must cover every KV slot actually written: prompt rows
    0..p-1 plus one row per decode step (step i writes at ``p + i``), so
    the bound is ``prompt_len + steps <= max_len`` — the final sampled
    token is never fed back and needs no slot.  Past it, the decode cache
    write's out-of-range scatter index *clamps silently* under XLA's
    default semantics — tokens past the cache end would quietly overwrite
    the last slot instead of erroring.  Guard it here, loudly.
    """
    if prompt.shape[1] + steps > max_len:
        raise ValueError(
            f"KV cache overrun: prompt_len={prompt.shape[1]} + "
            f"steps={steps} > max_len={max_len} — decode would scatter "
            "past the cache end (silently clamped, corrupting the last "
            "slot); raise max_len or shorten the generation")
    prefill_fn = prefill_fn or jax.jit(bundle.prefill)
    decode_fn = decode_fn or jax.jit(bundle.decode_step)
    b = prompt.shape[0]
    from ..models.api import merge_prefill_cache
    logits, pf_cache = prefill_fn(params, {"tokens": prompt})
    cache = merge_prefill_cache(bundle.init_cache(b, max_len), pf_cache)
    toks = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out = [toks]
    clen = jnp.full((b,), prompt.shape[1], jnp.int32)
    for _ in range(steps):
        logits, cache = decode_fn(params, {"tokens": toks,
                                           "cache_len": clen}, cache)
        toks = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        clen = clen + 1
        out.append(toks)
    return jnp.concatenate(out, axis=1)


def _parity_check(prefill_fn, sparse_params, ref_params, prompt, *,
                  tol: float):
    """Sparse-plan logits must match the masked-dense reference."""
    logits_s, _ = prefill_fn(sparse_params, {"tokens": prompt})
    logits_r, _ = prefill_fn(ref_params, {"tokens": prompt})
    diff = float(jnp.max(jnp.abs(logits_s.astype(jnp.float32)
                                 - logits_r.astype(jnp.float32))))
    np.testing.assert_allclose(np.asarray(logits_s, np.float32),
                               np.asarray(logits_r, np.float32),
                               rtol=tol, atol=tol)
    return diff


def guarded_generate(bundle, plan, params, prompt, steps: int, max_len: int,
                     *, prefill_fn, decode_fn, ref_blocks=None):
    """One guarded serving pass: check logits finiteness after prefill and
    after every decode step; on a trip, bisect the plan against the dense
    reference (`engine.guard.locate_poisoned`), quarantine the culprit
    layer(s) to dense, and restart the pass under the repaired plan.

    Returns ``(tokens, plan, events)`` — the possibly-quarantined plan plus
    a list of guard-report events.  Untimed by design: each finiteness
    check is a host sync, so this runs once before the timed loops (the
    ``--guard`` serving pass), never inside them.
    """
    from ..engine import guard as engine_guard

    def eval_finite(cand_plan) -> bool:
        # the oracle must cover prefill AND a decode step: flash prefill
        # attention masks non-finite scores (its fully-masked-row guard),
        # so a NaN q/k projection only surfaces through decode_attention's
        # plain softmax
        p = {**params, "sparse_plan": cand_plan}
        lg, pfc = prefill_fn(p, {"tokens": prompt})
        if not bool(jnp.isfinite(lg).all()):
            return False
        from ..models.api import merge_prefill_cache
        cache = merge_prefill_cache(
            bundle.init_cache(prompt.shape[0], max_len), pfc)
        toks = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        clen = jnp.full((prompt.shape[0],), prompt.shape[1], jnp.int32)
        lg2, _ = decode_fn(p, {"tokens": toks, "cache_len": clen}, cache)
        return bool(jnp.isfinite(lg2).all())

    events = []
    for attempt in range(4):  # each repair round quarantines >= 1 layer
        p = {**params, "sparse_plan": plan}
        tripped_at = None
        b = prompt.shape[0]
        cache = bundle.init_cache(b, max_len)
        logits, _ = prefill_fn(p, {"tokens": prompt})
        if not bool(jnp.isfinite(logits).all()):
            tripped_at = "prefill"
        else:
            toks = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            out = [toks]
            clen = jnp.full((b,), prompt.shape[1], jnp.int32)
            for step in range(steps):
                logits, cache = decode_fn(p, {"tokens": toks,
                                              "cache_len": clen}, cache)
                if not bool(jnp.isfinite(logits).all()):
                    tripped_at = f"decode_step_{step}"
                    break
                toks = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
                clen = clen + 1
                out.append(toks)
            if tripped_at is None:
                return jnp.concatenate(out, axis=1), plan, events
        poisoned, attributable = engine_guard.locate_poisoned(
            plan, eval_finite, ref_blocks=ref_blocks)
        events.append({"event": "nan_trip", "at": tripped_at,
                       "poisoned_layers": list(poisoned),
                       "attributable": attributable})
        if not attributable or not poisoned:
            raise engine_guard.GuardError(
                f"non-finite logits at {tripped_at} not attributable to "
                f"any planned sparse layer (bisection blamed "
                f"{list(poisoned)}) — the poison is outside the plan "
                "(component: model params / dense path)")
        print(f"[serve/guard] non-finite logits at {tripped_at}; bisection "
              f"blames {list(poisoned)}; quarantined to dense, restarting "
              "the guarded pass")
        plan = engine_guard.quarantine_layers(plan, poisoned, ref_blocks)
    raise engine_guard.GuardError(
        "guarded serving did not stabilize after 4 quarantine rounds")


def traffic_mode(bundle, serve_params, cfg, args) -> dict:
    """``--traffic``: the continuous-batching runtime under a seeded
    Poisson arrival scenario, A/B'd against the static batch loop at
    equal load, plus the paged-vs-contiguous bitwise parity gate.

    Returns the report dict committed as BENCH_serve.json's ``traffic``
    section: ``continuous`` / ``static`` metric blocks (p50/p99 latency,
    TTFT, sustained tok/s) and ``parity_max_abs_diff`` (must be 0.0 —
    the paged pool is a copy-exact rearrangement of the contiguous
    cache, see serving/paged_kv.py).
    """
    from ..serving import ServingEngine, contiguous_engine
    from ..serving import traffic as tr
    from .mesh import make_host_mesh
    # shard the pool planes + page-table lookups over this process's
    # devices; on one device the degenerate (1, 1) mesh keeps the
    # NamedSharding path exercised (values are identical either way —
    # parity holds)
    mesh = make_host_mesh()
    rng = np.random.default_rng(args.seed)
    prompt_lens = (args.prompt_len // 2, args.prompt_len)
    gen_steps = (max(args.gen_steps // 4, 2), args.gen_steps)
    reqs = tr.make_requests(args.requests, rng, vocab=cfg.vocab_size,
                            prompt_lens=prompt_lens, gen_steps=gen_steps)
    arrivals = tr.poisson_arrivals(len(reqs), args.rate, rng)
    ps = args.page_size
    budget = max(r["prompt"].shape[0] + r["max_new_tokens"] - 1
                 for r in reqs)
    view_pages = -(-budget // ps)
    max_len = view_pages * ps        # shared padded width -> exact parity
    slots = args.slots

    shared_steps: dict = {}      # compiled steps shared across paged engines

    def paged(**kw):
        return ServingEngine(bundle, serve_params,
                             num_pages=slots * view_pages + 1, page_size=ps,
                             max_slots=slots, max_pages_per_slot=view_pages,
                             prefill_chunk=args.prefill_chunk,
                             step_cache=shared_steps, mesh=mesh, **kw)

    # chunk widths this scenario can produce: full prefill chunks, each
    # prompt length's remainder chunk, and single-token decode
    pc = args.prefill_chunk
    widths = {1} | {pc for p in prompt_lens if p >= pc} \
        | {p % pc for p in prompt_lens if p % pc} \
        | {p for p in prompt_lens if p < pc}

    # -- parity gate: replay a slice through both cache structures ---------
    n_par = min(len(reqs), 2 * slots)
    diff = 0.0
    traces = {}
    for mk in ("paged", "contig"):
        eng = paged(record_logits=True) if mk == "paged" else \
            contiguous_engine(bundle, serve_params, max_slots=slots,
                              max_len=max_len,
                              prefill_chunk=args.prefill_chunk,
                              mesh=mesh, record_logits=True)
        for r in reqs[:n_par]:
            eng.submit(r["prompt"], r["max_new_tokens"])
        eng.run()
        traces[mk] = eng.logits_trace
    for rid, rows in traces["paged"].items():
        ref = traces["contig"][rid]
        assert len(rows) == len(ref), f"rid {rid} step count diverged"
        diff = max(diff, max(float(np.max(np.abs(a - b)))
                             for a, b in zip(rows, ref)))
    assert diff == 0.0, \
        f"paged KV diverged from the contiguous cache: max|dlogit|={diff}"
    print(f"[serve/traffic] paged-vs-contiguous parity over {n_par} "
          f"requests: max |dlogit| = {diff} (gate: exact)")

    # -- equal-load A/B: continuous runtime vs the static batch loop -------
    # both sides pre-compile off the timed path: the engine warms every
    # (batch bucket, chunk width) step, the static loop warms its two fns
    eng = paged()
    n_fns = eng.warmup(chunk_widths=widths)
    print(f"[serve/traffic] warmed {n_fns} step fns "
          f"(buckets x chunk widths {sorted(widths)})")
    prefill_fn = jax.jit(bundle.prefill)
    decode_fn = jax.jit(bundle.decode_step)
    from ..models.api import merge_prefill_cache
    for p in prompt_lens:
        wtoks = jnp.zeros((slots, p), jnp.int32)
        lg, pfc = prefill_fn(serve_params, {"tokens": wtoks})
        cache = merge_prefill_cache(bundle.init_cache(slots, max_len), pfc)
        decode_fn(serve_params,
                  {"tokens": jnp.zeros((slots, 1), jnp.int32),
                   "cache_len": jnp.full((slots,), p, jnp.int32)}, cache)
    cont = tr.run_continuous(eng, reqs, arrivals)
    static = tr.run_static(bundle, serve_params, reqs, arrivals,
                           batch=slots, max_len=max_len,
                           prefill_fn=prefill_fn, decode_fn=decode_fn)
    for name, m in (("continuous", cont), ("static", static)):
        print(f"[serve/traffic/{name}] {m['sustained_tok_per_s']:.1f} tok/s "
              f"sustained; latency p50={m['latency_s']['p50']:.3f}s "
              f"p99={m['latency_s']['p99']:.3f}s; "
              f"ttft p50={m['ttft_s']['p50']:.3f}s")
    return {"scenario": {"requests": args.requests, "rate_per_s": args.rate,
                         "seed": args.seed, "prompt_lens": list(prompt_lens),
                         "gen_steps": list(gen_steps), "page_size": ps,
                         "slots": slots, "prefill_chunk": args.prefill_chunk,
                         "max_len": max_len},
            "parity_max_abs_diff": diff, "parity_requests": n_par,
            "continuous": cont, "static": static,
            "speedup_sustained": cont["sustained_tok_per_s"]
            / max(static["sustained_tok_per_s"], 1e-9)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="olmo-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-steps", type=int, default=32)
    ap.add_argument("--sparsity", type=float, default=0.5)
    ap.add_argument("--impl", choices=["auto", "pallas", "xla", "xla_gather"],
                    default="auto",
                    help="force the sparse kernel impl (auto: pallas on "
                         "TPU, xla densify+dot fallback on CPU)")
    ap.add_argument("--attn-only", action="store_true",
                    help="plan only the attention projections, not the MLP")
    ap.add_argument("--quant", choices=["none", "int8", "int4"],
                    default="none",
                    help="tile-local block quantization of the sparse "
                         "encodings: per bn-block symmetric absmax scales, "
                         "int8 or nibble-packed int4 values, dequantized "
                         "in-kernel right before the MXU dot")
    ap.add_argument("--tune", choices=["off", "cached", "sweep"],
                    default="off",
                    help="block-choice policy (kernels.autotune): 'cached' "
                         "uses warm measured winners and falls back to the "
                         "static VMEM model, 'sweep' measures candidates on "
                         "cache misses and persists the winners")
    ap.add_argument("--tune-cache", default=None,
                    help="autotune cache path (default "
                         "~/.cache/repro/autotune.json or "
                         "$REPRO_AUTOTUNE_CACHE)")
    ap.add_argument("--guard", action="store_true",
                    help="guarded execution (engine.guard): validate the "
                         "plan, probe-harden every layer down the impl "
                         "ladder, and run one untimed serving pass with "
                         "per-step logits finiteness checks — a NaN trip "
                         "bisects to the poisoned layer and quarantines it "
                         "to dense.  Off the timed hot path either way")
    ap.add_argument("--inject-nan", action="store_true",
                    help="fault injection: poison one planned layer's "
                         "values with NaN after the parity reference is "
                         "built (chaos-testing --guard; refused without it)")
    ap.add_argument("--report", default=None,
                    help="write the serve report (incl. guard/degradation "
                         "events) to this JSON file")
    ap.add_argument("--objective", choices=list(cost_model.OBJECTIVES),
                    default="latency",
                    help="plan objective (launch.cost_model, DESIGN.md "
                         "§14): 'latency' keeps the paper's §V-C/§VI-F "
                         "rules and only annotates cost provenance; "
                         "'dram'/'energy'/'balanced' co-optimize the "
                         "dataflow mode + impl against the analytical "
                         "DRAM/energy model for the chosen deployment")
    ap.add_argument("--deployment", choices=sorted(cost_model.DEPLOYMENTS),
                    default=None,
                    help="deployment profile the cost objective evaluates "
                         "against (buffer sizes, DRAM bandwidth, energy "
                         "table; default zcu102)")
    ap.add_argument("--traffic", action="store_true",
                    help="continuous-batching serving under a seeded "
                         "Poisson arrival scenario (serving/): paged-KV "
                         "runtime vs the static batch loop at equal load, "
                         "plus the paged-vs-contiguous exact parity gate")
    ap.add_argument("--requests", type=int, default=12,
                    help="traffic: number of requests in the scenario")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="traffic: Poisson arrival rate (req/s)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="traffic: KV pool page size (tokens per page)")
    ap.add_argument("--slots", type=int, default=4,
                    help="traffic: live-request slots (max batch)")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="traffic: prompt tokens cached per prefill tick")
    ap.add_argument("--seed", type=int, default=0,
                    help="traffic: scenario seed (arrivals + shapes)")
    args = ap.parse_args(argv)
    if args.inject_nan and not args.guard:
        ap.error("--inject-nan poisons the serving path by design; it is "
                 "only meaningful (and only safe) under --guard")

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(cfg, sparse_serving=True)
    from ..models import build_model
    bundle = build_model(cfg)
    params = jax.jit(bundle.init)(jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    max_len = args.prompt_len + args.gen_steps

    # ---- the offline pass: build the plan once, serve from it ------------
    plan_kwargs = dict(sparsity=args.sparsity,
                       impl=None if args.impl == "auto" else args.impl,
                       m_hint=args.batch * args.prompt_len,
                       tune=args.tune, tune_cache=args.tune_cache,
                       quant=args.quant, objective=args.objective,
                       deployment=args.deployment)
    from ..models.api import TRANSFORMER_FAMILIES
    if cfg.family in TRANSFORMER_FAMILIES:
        plan_kwargs["include_mlp"] = not args.attn_only
    elif args.attn_only:
        print(f"[serve] --attn-only is inapplicable to family {cfg.family} "
              "(no separate attention projections are planned); planning "
              "the full projection family")
    obs.reset()
    t_plan = time.monotonic()
    plan = engine_plan.plan_model(cfg, params, **plan_kwargs)
    jax.block_until_ready(plan)
    plan_build_s = time.monotonic() - t_plan
    plan_phases_s = {name: s for name, (_, s) in obs.totals().items()
                     if name.startswith("plan.")}
    print(f"[serve] family={cfg.family} layer plan ({len(plan.layers)} "
          f"projection groups x {cfg.n_layers} layers, built in "
          f"{plan_build_s:.1f}s; "
          + ", ".join(f"{k} {v:.1f}s" for k, v in plan_phases_s.items())
          + "):")
    print(plan.summary())
    if args.tune != "off":
        deltas = plan.tune_deltas()
        print(f"[serve] tune={args.tune}: block sources {plan.tuned_mix()}; "
              f"{len(deltas)} tuned choice(s) differ from the static model"
              + ("".join(f"\n[serve]   {nm}: tuned (bm,bo,bn)={t} "
                         f"static={s}" for nm, t, s in deltas)))
    assert plan.sparse_layer_count > 0, \
        "plan produced no sparse-kernel layers — sparsity below §VI-F " \
        "thresholds?"

    # ---- guarded execution: validate + harden before anything runs -------
    guard_report = None
    if args.guard:
        from ..engine import guard as engine_guard
        report = engine_guard.validate_plan(plan, strict=True)
        plan, degradations = engine_guard.harden_plan(plan)
        guard_report = {"validated_layers": len(report.layers),
                        "degradations": [dataclasses.asdict(d)
                                         for d in degradations],
                        "events": []}
        print(f"[serve/guard] {report.summary()}")
        for d in degradations:
            print(f"[serve/guard] ladder: {d.layer} {d.from_impl} -> "
                  f"{d.to_impl} ({d.action}: {d.reason})")

    # The unpruned projection weights are dead once the plan exists: sparse
    # serving reads the plan and the dense baseline reads the masked-dense
    # reference (same shapes, same FLOPs).  Dropping them first frees a
    # whole f32 copy of the projections (4.3 GB at olmo-1b), and the
    # reference then keeps the plan's value dtype — exactly what the
    # model's matmuls cast every weight to — so three weight copies never
    # coexist on a 16 GiB chip.
    params = {**params, "blocks": {nm: w for nm, w in params["blocks"].items()
                                   if nm not in plan.layers}}
    ref_params = engine_plan.masked_dense_params(params, plan)
    sparse_params = {**params, "sparse_plan": plan}

    # one jitted pair shared by the parity check and both throughput modes:
    # jax.jit caches per argument pytree structure, so dense, masked-dense
    # ref, and plan-carrying sparse params each compile exactly once
    prefill_fn = jax.jit(bundle.prefill)
    decode_fn = jax.jit(bundle.decode_step)

    # ---- the guarded serving pass (untimed; NaN bisection + quarantine) --
    if args.guard:
        if args.inject_nan:
            from ..testing import faults
            plan, poisoned_name = faults.inject_nan_output(plan)
            print(f"[serve/guard] fault injection: poisoned layer "
                  f"{poisoned_name!r} values with NaN")
            guard_report["injected"] = poisoned_name
        _, plan, events = guarded_generate(
            bundle, plan, params, prompt, 2, max_len,
            prefill_fn=prefill_fn, decode_fn=decode_fn,
            ref_blocks=ref_params["blocks"])
        guard_report["events"] = events
        guard_report["quarantined"] = list(plan.quarantined())
        sparse_params = {**params, "sparse_plan": plan}
        if plan.degraded_mix() or plan.quarantined():
            print(f"[serve/guard] serving a degraded mix: "
                  f"{plan.degraded_mix()}; quarantined "
                  f"{list(plan.quarantined())}")

    # ---- correctness: sparse plan == masked dense, and the balanced
    # kernels are actually on the traced token path ------------------------
    # The gate runs an f32-compute twin of the model on the same params and
    # plan, every matmul at HIGHEST precision.  In bf16 the rounding of
    # the hidden state puts a noise floor under the logits (about sqrt(d)
    # bf16 ulps: 0.02-0.07 at d_model 2048) that any change of summation
    # order crosses, so a bf16 end-to-end gate cannot tell a wrong kernel
    # from a reordered sum; the bf16 gap is reported, not gated.
    # Quantized plans compare against the *dequantized* masked-dense
    # reference (`masked_dense_params` densifies through the scales), so
    # the parity diff measures kernel-vs-reference round-off, not the
    # quantization error itself; the wider tol covers accumulation-order
    # spread of the in-kernel dequant across layers
    pcfg = dataclasses.replace(cfg, compute_dtype="float32")
    tol = 1e-4
    if args.quant != "none":
        tol = max(tol, 5e-2)
    engine_execute.reset_stats()
    with jax.default_matmul_precision("highest"):
        diff = _parity_check(jax.jit(build_model(pcfg).prefill),
                             sparse_params, ref_params, prompt, tol=tol)
    stats = engine_execute.stats()
    bf16_diff = float(jnp.max(jnp.abs(
        prefill_fn(sparse_params, {"tokens": prompt})[0].astype(jnp.float32)
        - prefill_fn(ref_params, {"tokens": prompt})[0].astype(
            jnp.float32))))
    if args.guard and not stats.get("balanced_spmm"):
        # the guarded pass already compiled this params structure, so the
        # jitted parity calls hit the executable cache without re-tracing
        # and the trace-time counters stayed at zero — re-count with an
        # abstract trace (no compile, no execution; the fresh lambda defeats
        # the tracing cache, which is keyed on function identity)
        engine_execute.reset_stats()
        jax.eval_shape(lambda p, b: bundle.prefill(p, b), sparse_params,
                       {"tokens": prompt})
        stats = engine_execute.stats()
    if plan.sparse_layer_count > 0:
        assert stats.get("balanced_spmm", 0) > 0, \
            f"balanced_spmm never dispatched — sparse path is a no-op " \
            f"({stats})"
    if any(lp.spec.experts and lp.spec.is_sparse
           for lp in plan.layers.values()):
        # planned expert tensors must run the per-expert balanced kernels,
        # not a dense einsum on densified experts (--attn-only plans carry
        # no expert layers, and guard-quarantined expert layers are
        # legitimately dense)
        assert stats.get("expert_balanced_spmm", 0) > 0, \
            f"MoE expert layers never hit the per-expert path ({stats})"
    print(f"[serve] parity sparse vs masked-dense (f32 compute): max "
          f"|dlogit| = {diff:.2e} (tol {tol:g}); served {cfg.compute_dtype} "
          f"logits differ by {bf16_diff:.2e} (not gated);  engine "
          f"dispatches: {stats}")

    # ---- throughput ------------------------------------------------------
    results = {}
    if args.traffic:
        # continuous-batching runtime (serving/) under Poisson load, served
        # from the plan-carrying params — the paged pool + scheduler around
        # the same decode_step the static loop uses
        if cfg.family not in TRANSFORMER_FAMILIES:
            ap.error(f"--traffic serves the transformer families "
                     f"{TRANSFORMER_FAMILIES}; {cfg.family} has O(1) "
                     "recurrent state (nothing to page)")
        results["traffic"] = traffic_mode(bundle, sparse_params, cfg, args)
    for mode, p in () if args.traffic else (("dense", ref_params),
                                            ("sparse", sparse_params)):
        # warm up (compile) outside the timed region
        greedy_generate(bundle, p, prompt, 1, max_len,
                        prefill_fn=prefill_fn, decode_fn=decode_fn)
        t0 = time.monotonic()
        toks = greedy_generate(bundle, p, prompt, args.gen_steps, max_len,
                               prefill_fn=prefill_fn, decode_fn=decode_fn)
        jax.block_until_ready(toks)
        dt = time.monotonic() - t0
        tps = args.batch * args.gen_steps / dt
        results[mode] = {"tokens_per_s": tps, "wall_s": dt,
                         "sample": toks[0, :8].tolist()}
        print(f"[serve/{mode}] {tps:.1f} tok/s ({dt:.2f}s)")

    # ---- storage story: compressed weight footprint (paper Fig.8) --------
    total_numel = total_nnz = 0
    for lp in plan.layers.values():
        s = lp.spec
        # each projection group repeats per layer, and per expert for MoE
        # expert tensors
        mult = cfg.n_layers * max(s.experts, 1)
        total_numel += s.n_in * s.n_out * mult
        total_nnz += s.k * s.n_out * mult
    dense_bits = total_numel * 16
    comp_bits = compressed_bits(total_numel, total_nnz, elem_bits=16)
    cost = plan.cost_summary()
    results["plan"] = {
        "family": cfg.family, "quant": args.quant,
        "mode_mix": plan.mode_mix(), "impl_mix": plan.impl_mix(),
        "sparse_layers": plan.sparse_layer_count,
        "degraded_mix": plan.degraded_mix(),
        "quarantined": list(plan.quarantined()),
        "build_s": plan_build_s, "phases_s": plan_phases_s,
        "parity_max_abs_diff": diff, "parity_tol": tol,
        "served_logits_max_abs_diff": bf16_diff, "engine_stats": stats,
        "tune": {"mode": args.tune, "sources": plan.tuned_mix(),
                 "deltas": [[nm, list(t), list(s)]
                            for nm, t, s in plan.tune_deltas()]},
        "cost": cost,
    }
    print(f"[serve/cost] objective={cost['objective']} "
          f"deployment={cost['deployment'] or 'zcu102'}: modeled DRAM "
          f"{cost['total_dram_bytes'] / 1e6:.2f} MB, energy "
          f"{cost['total_energy_pj'] / 1e9:.3f} mJ, weight stream "
          f"{cost['total_w_stream_bytes'] / 1e6:.2f} MB "
          f"(modes {cost['modes']})")
    if guard_report is not None:
        guard_report["degraded_mix"] = plan.degraded_mix()
        results["guard"] = guard_report
    print(f"[serve] family={cfg.family} planned weight sparsity "
          f"{1 - total_nnz / max(total_numel, 1):.2f}, "
          f"bitmap compression {dense_bits / comp_bits:.2f}x;  "
          f"dataflow mode mix {plan.mode_mix()}  "
          f"impl mix {plan.impl_mix()}")
    if args.report:
        import json
        import pathlib
        out = pathlib.Path(args.report)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(results, indent=1, default=str) + "\n")
        print(f"[serve] report -> {out}")
    return results


if __name__ == "__main__":
    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
