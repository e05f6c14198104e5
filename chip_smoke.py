"""Smoke test of the sparse serving path on one TPU chip, at olmo-1b's
published width (16 layers, d_model 2048, d_ff 8192, vocab 50304, 50%
balanced weight sparsity), through the entry points a user calls.

    python chip_smoke.py              # one chip: phases 1 and 2
    python chip_smoke.py --chips 4    # four chips: the sharded-plan check

Phase 1 runs ``repro.launch.serve.main`` on the full config: the offline
plan, the strict parity check against masked dense, and greedy prefill
(the wide Pallas kernel, M = batch x prompt) plus decode (the skinny
kernel, M = batch), dense and sparse.  Phase 2 runs a short ``--traffic``
scenario: the continuous-batching ``ServingEngine`` answers a handful of
requests and passes the exact paged-vs-contiguous parity gate.  The
scenario (one slot, prompts of 8 and 16 tokens in chunks of 8, at most 4
new tokens) keeps the number of compiled step shapes small.

``--chips 4`` instead shards the olmo-1b plan over a 2x2 (data, model) mesh
of the host's four chips and checks its prefill and decode logits against
the same requests served on one device, within serve's parity tolerance
(both sides in serve's parity twin: f32 compute, HIGHEST matmul precision).
Width stays published; depth is cut to `SHARDED_LAYERS` (every layer
shards and communicates alike, and the host-side plan build would
otherwise hold four chips idle for minutes).

The script needs a TPU: with none (or without the rest of the repository
next to it) it exits nonzero and prints no result.  Its last line is the
JSON result ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "olmo-1b"
TOL = 1e-4        # serve's parity tolerance (its f32-compute twin)
SHARDED_LAYERS = 2


def _check_plan(report: dict, phase: str) -> None:
    """Every sparse layer runs the compiled Pallas kernel, the kernels were
    on the traced path, and nothing was degraded or quarantined."""
    plan = report["plan"]
    impls = plan["impl_mix"]
    sparse = {k: v for k, v in impls.items() if k != "dense"}
    if set(sparse) != {"pallas"}:
        raise SystemExit(f"{phase}: sparse layers not all pallas: {impls}")
    stats = plan["engine_stats"]
    if stats.get("balanced_spmm", 0) <= 0:
        raise SystemExit(f"{phase}: balanced_spmm never dispatched: {stats}")
    if plan["degraded_mix"] or plan["quarantined"] \
            or stats.get("degraded_dispatch", 0):
        raise SystemExit(f"{phase}: degraded serving: {plan['degraded_mix']}"
                         f" quarantined {plan['quarantined']}")


def _peak_bytes(jax) -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def one_chip(jax, compile_s: list) -> None:
    from repro.launch import serve

    t0 = time.monotonic()
    r1 = serve.main(["--arch", ARCH, "--tune", "off", "--batch", "4",
                     "--prompt-len", "32", "--gen-steps", "8"])
    _check_plan(r1, "phase 1")
    p = r1["plan"]
    print(f"[chip_smoke] phase 1 (serve): impl_mix={p['impl_mix']} "
          f"mode_mix={p['mode_mix']} parity_max_abs_diff="
          f"{p['parity_max_abs_diff']!r} (f32 twin, tol {p['parity_tol']}) "
          f"served_bf16_logits_max_abs_diff="
          f"{p['served_logits_max_abs_diff']!r} plan_build_s="
          f"{p['build_s']:.1f} compile_s={compile_s[0]:.1f} "
          f"dense_tok_s={r1['dense']['tokens_per_s']:.1f} "
          f"sparse_tok_s={r1['sparse']['tokens_per_s']:.1f} "
          f"wall_s={time.monotonic() - t0:.1f} "
          f"peak_bytes_in_use={_peak_bytes(jax)}", flush=True)

    gc.collect()        # phase 1's weights must be gone before phase 2's
    t0, c0 = time.monotonic(), compile_s[0]
    r2 = serve.main(["--arch", ARCH, "--tune", "off", "--traffic",
                     "--batch", "1", "--prompt-len", "16",
                     "--prefill-chunk", "8", "--gen-steps", "4",
                     "--requests", "4", "--slots", "1", "--rate", "50"])
    _check_plan(r2, "phase 2")
    tr = r2["traffic"]
    if tr["parity_max_abs_diff"] != 0.0:
        raise SystemExit(f"phase 2: paged KV diverged: {tr}")
    cont = tr["continuous"]
    print(f"[chip_smoke] phase 2 (traffic): requests={cont['requests']} "
          f"generated_tokens={cont['generated_tokens']} "
          f"paged_vs_contiguous_diff="
          f"{tr['parity_max_abs_diff']!r} parity_requests="
          f"{tr['parity_requests']} plan_build_s={r2['plan']['build_s']:.1f} "
          f"compile_s={compile_s[0] - c0:.1f} "
          f"wall_s={time.monotonic() - t0:.1f} "
          f"peak_bytes_in_use={_peak_bytes(jax)}", flush=True)


def sharded_vs_single(jax, cfg, *, batch: int = 4, prompt_len: int = 32,
                      steps: int = 4, tol: float = TOL) -> float:
    """Serve the same prompt through the plan on one device and through the
    plan sharded over a (data, model) mesh of every device; returns the
    max |dlogit| over prefill and ``steps`` teacher-forced decode steps and
    raises when it exceeds ``tol``."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from repro.distributed.sharding import tree_shardings
    from repro.engine import plan as engine_plan
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.models.api import merge_prefill_cache

    cfg = dataclasses.replace(cfg, sparse_serving=True)
    pcfg = dataclasses.replace(cfg, compute_dtype="float32")
    one = jax.devices()[0]
    bundle = build_model(pcfg)
    with jax.default_device(one):
        params = jax.jit(build_model(cfg).init)(jax.random.key(0))
        plan = engine_plan.plan_model(cfg, params, sparsity=0.5,
                                      m_hint=batch * prompt_len,
                                      decode_m=batch)
        params = {**params, "blocks": {
            nm: w for nm, w in params["blocks"].items()
            if nm not in plan.layers}}
        prompt = jax.random.randint(jax.random.key(1), (batch, prompt_len),
                                    0, cfg.vocab_size)
    mesh = make_host_mesh()
    bundle_m = build_model(pcfg, mesh)
    specs = bundle_m.param_specs()
    specs = {**specs, "blocks": {nm: s for nm, s in specs["blocks"].items()
                                 if nm in params["blocks"]}}
    params_m = jax.device_put(params, tree_shardings(mesh, specs))
    plan_m = engine_plan.shard_plan(plan, mesh)
    shard_devs = {s.device for lp in plan_m.layers.values()
                  for s in lp.weights.values.addressable_shards}

    def run(b, p, pl):
        sp = {**p, "sparse_plan": pl}
        prefill, decode = jax.jit(b.prefill), jax.jit(b.decode_step)
        logits, pfc = prefill(sp, {"tokens": prompt})
        out = [np.asarray(logits, np.float32)]
        cache = merge_prefill_cache(b.init_cache(batch, prompt_len + steps),
                                    pfc)
        clen = jnp.full((batch,), prompt_len, jnp.int32)
        return sp, decode, out, cache, clen

    with jax.default_matmul_precision("highest"):
        sp1, dec1, out1, c1, clen = run(bundle, params, plan)
        spm, decm, outm, cm, _ = run(bundle_m, params_m, plan_m)
        for _ in range(steps):
            toks = jnp.argmax(jnp.asarray(out1[-1]), -1)[:, None].astype(
                jnp.int32)
            l1, c1 = dec1(sp1, {"tokens": toks, "cache_len": clen}, c1)
            lm, cm = decm(spm, {"tokens": toks, "cache_len": clen}, cm)
            out1.append(np.asarray(l1, np.float32))
            outm.append(np.asarray(lm, np.float32))
            clen = clen + 1
    diff = max(float(np.max(np.abs(a - b))) for a, b in zip(out1, outm))
    print(f"[chip_smoke] sharded plan over mesh {dict(mesh.shape)} "
          f"({len(shard_devs)} shard devices) vs one device: prefill + "
          f"{steps} decode steps, max |dlogit| = {diff!r} (tol {tol})",
          flush=True)
    if len(shard_devs) < 2:
        raise SystemExit("sharded plan is not distributed across devices")
    if not diff <= tol:
        raise SystemExit(f"sharded logits diverged: {diff} > {tol}")
    return diff


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving phases on one chip; 4: the sharded-plan "
                         "check on a four-chip host")
    args = ap.parse_args(argv)

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX sees {devices[0].platform} devices",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"[chip_smoke] compile cache: {enable_compile_cache()}", flush=True)
    compile_s = [0.0]

    def on_event(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration
    jax.monitoring.register_event_duration_secs_listener(on_event)

    if args.chips == 4:
        import dataclasses
        sharded_vs_single(jax, dataclasses.replace(get_config(ARCH),
                                                   n_layers=SHARDED_LAYERS))
    else:
        one_chip(jax, compile_s)
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {"platform": d[0].platform,
                                             "kind": d[0].device_kind,
                                             "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
