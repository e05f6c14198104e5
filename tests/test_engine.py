"""Layer-plan engine: pytree/checkpoint round-trips, plan-vs-masked-dense
parity on the small CNN and every smoke model family (dense transformer,
MoE incl. expert tensors, RWKV6, Zamba2), plan determinism, shard-aware
plan specs, the Fig.22b dataflow mode-mix regression, and the
no-call-time-cache contract."""
import dataclasses
import gc
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pruning import (balanced_prune_conv, balanced_prune_rows,
                                random_prune)
from repro.engine import execute as engine_execute
from repro.engine import plan as engine_plan
from repro.launch.mesh import make_mesh


def _fc_plan(key=0, o=48, n=96, sparsity=0.6, **kw):
    w = jax.random.normal(jax.random.key(key), (o, n))
    _, mask = balanced_prune_rows(w, sparsity)
    return w, mask, engine_plan.build_layer_plan("fc", w, mask=mask,
                                                 m_hint=32, **kw)


# ---------------------------------------------------------------------------
# ModelPlan as a pytree / checkpoint artifact
# ---------------------------------------------------------------------------

def test_model_plan_pytree_roundtrip():
    w, mask, lp_xla = _fc_plan(impl="xla")
    _, _, lp_pal = _fc_plan(key=1, impl="pallas")
    mp = engine_plan.ModelPlan(layers={"a": lp_xla, "b": lp_pal},
                               meta=(("model", "test"),))
    leaves, treedef = jax.tree_util.tree_flatten(mp)
    mp2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert mp2.layers.keys() == mp.layers.keys()
    for k in mp.layers:
        assert mp2.layers[k].spec == mp.layers[k].spec
    # static decisions are jit aux data: a plan-typed argument traces
    x = jax.random.normal(jax.random.key(2), (5, 96))
    y = jax.jit(lambda p, x: engine_execute.apply_named(x, p, "a"))(mp2, x)
    want = x @ (w * mask).T
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_model_plan_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint.store import restore_checkpoint, save_checkpoint
    _, _, lp_xla = _fc_plan(impl="xla")
    _, _, lp_pal = _fc_plan(key=1, impl="pallas")
    mp = engine_plan.ModelPlan(layers={"a": lp_xla, "b": lp_pal},
                               meta=(("sparsity", 0.6),))
    save_checkpoint(tmp_path, 7, mp, extra={"note": "plan"})
    got, extra = restore_checkpoint(tmp_path, 7, mp)
    assert extra == {"note": "plan"}
    for l1, l2 in zip(jax.tree.leaves(mp), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=0)
    # aux (the frozen PlanSpec decisions) survives via the tree structure
    assert got.layers["b"].spec == mp.layers["b"].spec
    assert got.meta == mp.meta


def test_plan_path_skips_encoding_caches():
    """Acceptance: the id()-keyed weakref caches in kernels/ops.py are off
    the plan-driven path — plans carry pre-encoded weights."""
    from repro.kernels import ops
    ops._ENC_CACHE.clear()
    ops._KB_CACHE.clear()
    _, _, lp = _fc_plan(impl="pallas")
    x = jax.random.normal(jax.random.key(3), (9, 96))
    jax.block_until_ready(engine_execute.apply_fc(x, lp))
    assert not ops._ENC_CACHE and not ops._KB_CACHE
    # ...while the eager ad-hoc balanced_spmm entry point still works
    from repro.core.pruning import to_balanced_sparse
    sp = to_balanced_sparse(jax.random.normal(jax.random.key(4), (16, 64)),
                            k=8)
    y = ops.balanced_spmm(x[:, :64], sp.values, sp.indices, n_in=64,
                          impl="pallas")
    assert y.shape == (9, 16)


def test_engine_stats_counters():
    engine_execute.reset_stats()
    _, _, lp = _fc_plan(impl="xla")
    x = jax.random.normal(jax.random.key(5), (4, 96))
    engine_execute.apply_fc(x, lp)
    s = engine_execute.stats()
    assert s["balanced_spmm"] == 1 and s["impl_xla"] == 1


# ---------------------------------------------------------------------------
# Plan-vs-masked-dense parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_smallcnn_plan_matches_masked_dense(impl):
    from repro.models.cnn import SmallCNNConfig, smallcnn_apply, smallcnn_init
    cfg = SmallCNNConfig()
    params = smallcnn_init(cfg, jax.random.key(0))
    masks = {}
    for i in range(len(cfg.channels)):
        _, masks[f"conv{i}"] = balanced_prune_conv(params[f"conv{i}"], 0.5)
    _, masks["fc1"] = balanced_prune_rows(params["fc1"], 0.8)  # balanced fc
    _, masks["fc2"] = random_prune(params["fc2"], 0.8)         # unbalanced
    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    got = smallcnn_apply(cfg, params, x, masks=masks, impl=impl)
    mparams = {k: (v * masks[k] if k in masks else v)
               for k, v in params.items()}
    want = smallcnn_apply(cfg, mparams, x, masks=None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # the plan really used sparse kernels for the balanced layers
    plan = engine_plan.plan_smallcnn(cfg, params, masks, impl=impl)
    assert plan.layers["conv0"].spec.impl == impl
    assert plan.layers["fc1"].spec.impl == impl
    assert plan.layers["fc2"].spec.impl == "dense"   # unbalanced mask


def test_smallcnn_plan_grads_trainable_under_jit():
    """The plan path must stay differentiable inside a jitted train step
    (mask structure concrete, values traced)."""
    from repro.models.cnn import SmallCNNConfig, smallcnn_init, smallcnn_loss
    cfg = SmallCNNConfig(channels=(8, 16), img=16, fc_hidden=32)
    params = smallcnn_init(cfg, jax.random.key(0))
    masks = {}
    for i in range(len(cfg.channels)):
        _, masks[f"conv{i}"] = balanced_prune_conv(params[f"conv{i}"], 0.5)
    batch = {"image": jax.random.normal(jax.random.key(1), (2, 16, 16, 3)),
             "label": jnp.zeros((2,), jnp.int32)}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: smallcnn_loss(cfg, p, batch, masks=masks)))(params)
    assert np.isfinite(float(loss))
    for g in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(g, np.float32)).all()


def test_transformer_plan_matches_masked_dense():
    from repro.configs import get_smoke
    from repro.models import build_model
    cfg = dataclasses.replace(get_smoke("olmo-1b"), sparse_serving=True)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    plan = engine_plan.plan_transformer(cfg, params, sparsity=0.5)
    assert plan.sparse_layer_count > 0
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                cfg.vocab_size)
    sparse_params = {**params, "sparse_plan": plan}
    ref_params = engine_plan.masked_dense_params(params, plan)

    engine_execute.reset_stats()
    logits_s, cache_s = jax.jit(m.prefill)(sparse_params, {"tokens": tokens})
    assert engine_execute.stats().get("balanced_spmm", 0) > 0
    logits_r, cache_r = jax.jit(m.prefill)(ref_params, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(logits_s), np.asarray(logits_r),
                               rtol=2e-2, atol=2e-2)

    # decode: same cache prefix, one step, same logits
    cache = m.init_cache(2, 24)
    cache["k"] = cache["k"].at[:, :, :16].set(cache_s["k"])
    cache["v"] = cache["v"].at[:, :, :16].set(cache_s["v"])
    batch = {"tokens": tokens[:, :1],
             "cache_len": jnp.full((2,), 16, jnp.int32)}
    ld_s, _ = jax.jit(m.decode_step)(sparse_params, batch, cache)
    cache2 = m.init_cache(2, 24)
    cache2["k"] = cache2["k"].at[:, :, :16].set(cache_r["k"])
    cache2["v"] = cache2["v"].at[:, :, :16].set(cache_r["v"])
    ld_r, _ = jax.jit(m.decode_step)(ref_params, batch, cache2)
    np.testing.assert_allclose(np.asarray(ld_s), np.asarray(ld_r),
                               rtol=2e-2, atol=2e-2)


def _family_parity(arch, *, impl=None, expect_expert=False, seq=16):
    """Shared harness: plan-vs-masked-dense prefill parity for one smoke
    arch, returning the engine dispatch stats observed on the sparse
    trace."""
    from repro.configs import get_smoke
    from repro.models import build_model
    cfg = dataclasses.replace(get_smoke(arch), sparse_serving=True)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    plan = engine_plan.plan_model(cfg, params, sparsity=0.5, impl=impl)
    assert plan.sparse_layer_count > 0
    tokens = jax.random.randint(jax.random.key(1), (2, seq), 0,
                                cfg.vocab_size)
    sparse_params = {**params, "sparse_plan": plan}
    ref_params = engine_plan.masked_dense_params(params, plan)
    engine_execute.reset_stats()
    logits_s, _ = jax.jit(m.prefill)(sparse_params, {"tokens": tokens})
    stats = engine_execute.stats()
    assert stats.get("balanced_spmm", 0) > 0
    if expect_expert:
        assert stats.get("expert_balanced_spmm", 0) > 0
    logits_r, _ = jax.jit(m.prefill)(ref_params, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(logits_s, np.float32),
                               np.asarray(logits_r, np.float32),
                               rtol=2e-2, atol=2e-2)
    return cfg, m, params, plan, stats


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_moe_plan_matches_masked_dense(impl):
    """MoE expert tensors [L, E, d, f] run the per-expert balanced kernel
    path (apply_expert_fc) and match the masked-dense einsum reference."""
    cfg, m, params, plan, stats = _family_parity(
        "deepseek-moe-16b", impl=impl, expect_expert=True)
    # every expert tensor is planned, per-expert, with a shared BlockChoice
    for nm in engine_plan.MOE_EXPERT_NAMES:
        lp = plan.layers[nm]
        assert lp.spec.experts == cfg.n_experts
        assert lp.spec.impl == impl
        lead = lp.weights.values.shape[:2]
        assert lead == (cfg.n_layers, cfg.n_experts)
    # shared experts ride the plain stacked path
    assert plan.layers["ws_gate"].spec.experts == 0


def test_moe_plan_decode_step_parity():
    from repro.configs import get_smoke
    from repro.models import build_model
    cfg = dataclasses.replace(get_smoke("deepseek-moe-16b"),
                              sparse_serving=True)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    plan = engine_plan.plan_model(cfg, params, sparsity=0.5)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                cfg.vocab_size)
    sparse_params = {**params, "sparse_plan": plan}
    ref_params = engine_plan.masked_dense_params(params, plan)
    _, cache_s = jax.jit(m.prefill)(sparse_params, {"tokens": tokens})
    _, cache_r = jax.jit(m.prefill)(ref_params, {"tokens": tokens})
    batch = {"tokens": tokens[:, :1],
             "cache_len": jnp.full((2,), 16, jnp.int32)}
    for cache in (cache_s, cache_r):
        c0 = m.init_cache(2, 24)
        for key in ("k", "v"):
            cache[key] = c0[key].at[:, :, :16].set(
                cache[key].astype(c0[key].dtype))
    ld_s, _ = jax.jit(m.decode_step)(sparse_params, batch, cache_s)
    ld_r, _ = jax.jit(m.decode_step)(ref_params, batch, cache_r)
    np.testing.assert_allclose(np.asarray(ld_s, np.float32),
                               np.asarray(ld_r, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_rwkv6_plan_matches_masked_dense():
    """The R/K/V/G/O + channel-mix family runs through the plan; the WKV
    recurrence stays dense."""
    _, _, _, plan, _ = _family_parity("rwkv6-3b")
    assert set(plan.layers) == set(engine_plan.RWKV6_PROJ_NAMES)


def test_zamba2_plan_matches_masked_dense():
    """Mamba-block in/out projections run through the plan; SSD recurrence,
    convs and the shared attention block stay dense."""
    _, _, _, plan, _ = _family_parity("zamba2-1.2b")
    assert set(plan.layers) == set(engine_plan.ZAMBA2_PROJ_NAMES)


def test_rwkv6_zamba2_decode_step_parity():
    from repro.configs import get_smoke
    from repro.models import build_model
    for arch in ("rwkv6-3b", "zamba2-1.2b"):
        cfg = dataclasses.replace(get_smoke(arch), sparse_serving=True)
        m = build_model(cfg)
        params = m.init(jax.random.key(0))
        plan = engine_plan.plan_model(cfg, params, sparsity=0.5)
        tokens = jax.random.randint(jax.random.key(1), (2, 8), 0,
                                    cfg.vocab_size)
        sparse_params = {**params, "sparse_plan": plan}
        ref_params = engine_plan.masked_dense_params(params, plan)
        _, cache_s = jax.jit(m.prefill)(sparse_params, {"tokens": tokens})
        _, cache_r = jax.jit(m.prefill)(ref_params, {"tokens": tokens})
        if arch == "zamba2-1.2b":
            for cache in (cache_s, cache_r):
                c0 = m.init_cache(2, 16)
                for key in ("k", "v"):
                    cache[key] = c0[key].at[:, :, :8].set(
                        cache[key].astype(c0[key].dtype))
        batch = {"tokens": tokens[:, :1],
                 "cache_len": jnp.full((2,), 8, jnp.int32)}
        ld_s, _ = jax.jit(m.decode_step)(sparse_params, batch, cache_s)
        ld_r, _ = jax.jit(m.decode_step)(ref_params, batch, cache_r)
        np.testing.assert_allclose(np.asarray(ld_s, np.float32),
                                   np.asarray(ld_r, np.float32),
                                   rtol=2e-2, atol=2e-2, err_msg=arch)


# ---------------------------------------------------------------------------
# Plan determinism (plans are safe to cache/ship)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["deepseek-moe-16b", "rwkv6-3b"])
def test_plan_determinism_across_builds_and_checkpoint(arch, tmp_path):
    """Identical params + config -> byte-identical ModelPlan, with leaves
    compared after a checkpoint save/restore round-trip."""
    from repro.checkpoint.store import restore_checkpoint, save_checkpoint
    from repro.configs import get_smoke
    from repro.models import build_model
    cfg = get_smoke(arch)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    p1 = engine_plan.plan_model(cfg, params, sparsity=0.5)
    p2 = engine_plan.plan_model(cfg, params, sparsity=0.5)
    save_checkpoint(tmp_path, 1, p1)
    got, _ = restore_checkpoint(tmp_path, 1, p2)
    # identical static decisions and tree structure...
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(p2)
    for nm in p2.layers:
        assert got.layers[nm].spec == p2.layers[nm].spec
    # ...and byte-identical leaves post round-trip
    for l1, l2 in zip(jax.tree.leaves(got), jax.tree.leaves(p2)):
        a1, a2 = np.asarray(l1), np.asarray(l2)
        assert a1.dtype == a2.dtype and a1.shape == a2.shape
        assert a1.tobytes() == a2.tobytes()


# ---------------------------------------------------------------------------
# Shard-aware plans
# ---------------------------------------------------------------------------

def test_plan_specs_encoded_values_not_replicated():
    """Encoded plan leaves carry real PartitionSpecs: output channels over
    the FSDP axes, the expert axis over ``model``, stacked L replicated."""
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_smoke
    from repro.models import build_model
    cfg = get_smoke("deepseek-moe-16b")
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    plan = engine_plan.plan_model(cfg, params, sparsity=0.5)
    mesh = make_mesh((1, 1), ("data", "model"))
    specs = engine_plan.plan_specs(plan, mesh)
    assert jax.tree_util.tree_structure(
        specs, is_leaf=lambda x: isinstance(x, P)).num_leaves == \
        len(jax.tree.leaves(plan))
    for nm, lp in specs.layers.items():
        vspec = lp.weights.values if hasattr(lp.weights, "values") \
            else lp.weights
        assert any(d is not None for d in vspec), (nm, vspec)
        assert vspec[0] is None, "stacked L axis must stay replicated"
        # tiled leaves are [L, (E,) NB, O, KB]; dense ones [L, (E,) O, N]
        tiled = plan.layers[nm].weights.__class__.__name__ == "TiledBalanced"
        if plan.layers[nm].spec.experts:
            assert vspec[1] == "model", "expert axis is expert-parallel"
            o_axis = 3 if tiled else 2
        else:
            o_axis = 2 if tiled else 1
        assert vspec[o_axis] == "data", "O axis is FSDP-sharded"
        if tiled:
            assert vspec[o_axis - 1] is None, "NB axis stays whole"


SHARDED_PLAN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.configs import get_smoke
    from repro.models import build_model
    from repro.engine import plan as engine_plan
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_smoke("deepseek-moe-16b")
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    plan = engine_plan.plan_model(cfg, params, sparsity=0.5)
    sharded = engine_plan.shard_plan(plan, mesh)
    vals = sharded.layers["we_gate"].weights.values
    nshards = len({s.device for s in vals.addressable_shards})
    assert nshards > 1, f"expert values replicated ({nshards} shard devices)"
    # densified parity survives resharding
    import numpy as np
    d1 = np.asarray(plan.layers["we_gate"].dense_weights(), np.float32)
    d2 = np.asarray(sharded.layers["we_gate"].dense_weights(), np.float32)
    np.testing.assert_allclose(d1, d2, atol=0)
    print("SHARDED_PLAN_OK")
""")


@pytest.mark.slow
def test_sharded_plan_multidevice_subprocess():
    """On a >=2-device mesh the encoded values are actually distributed
    (more than one shard device), not replicated.  Runs in a subprocess
    because device count locks at first jax init."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SHARDED_PLAN_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert "SHARDED_PLAN_OK" in out.stdout, out.stderr[-3000:]


# ---------------------------------------------------------------------------
# Eager-path encoding cache lifecycle
# ---------------------------------------------------------------------------

def test_enc_cache_evicts_on_weight_gc():
    """The id()-keyed weakref caches drop entries when the source weights
    are garbage-collected (id reuse would otherwise serve stale encodings),
    and never fire on plan-driven paths."""
    from repro.core.pruning import to_balanced_sparse
    from repro.kernels import ops
    ops._ENC_CACHE.clear()
    ops._KB_CACHE.clear()
    x = jax.random.normal(jax.random.key(0), (4, 64))
    sp = to_balanced_sparse(jax.random.normal(jax.random.key(1), (16, 64)),
                            k=8)
    jax.block_until_ready(ops.balanced_spmm(x, sp.values, sp.indices,
                                            n_in=64, impl="pallas"))
    assert len(ops._ENC_CACHE) == 1 and len(ops._KB_CACHE) == 1
    # a second call on live weights is a hit, not a second entry
    jax.block_until_ready(ops.balanced_spmm(x, sp.values, sp.indices,
                                            n_in=64, impl="pallas"))
    assert len(ops._ENC_CACHE) == 1
    del sp
    gc.collect()
    assert not ops._ENC_CACHE, "entry must evict when source weights die"
    assert not ops._KB_CACHE
    # the planned path never touches either cache
    _, _, lp = _fc_plan(impl="pallas")
    jax.block_until_ready(engine_execute.apply_fc(
        jax.random.normal(jax.random.key(2), (4, 96)), lp))
    assert not ops._ENC_CACHE and not ops._KB_CACHE


def test_serve_smoke_sparse_path_end_to_end():
    """The acceptance gate in-tree: serve executes the balanced-sparse
    kernels (plan stats > 0) and reports the dataflow mode mix."""
    from repro.launch import serve
    results = serve.main(["--arch", "olmo-1b", "--smoke", "--batch", "2",
                          "--prompt-len", "16", "--gen-steps", "2",
                          "--sparsity", "0.5"])
    assert results["plan"]["sparse_layers"] > 0
    assert results["plan"]["engine_stats"].get("balanced_spmm", 0) > 0
    assert "ON_CHIP" in results["plan"]["mode_mix"]
    assert results["sparse"]["tokens_per_s"] > 0
    # where start-up goes: the plan build's phases beside its total
    phases = results["plan"]["phases_s"]
    assert {"plan.prune", "plan.blocks", "plan.encode"} <= set(phases)
    assert sum(phases.values()) <= results["plan"]["build_s"]


def test_serve_moe_expert_path_end_to_end():
    """Acceptance: serve on an MoE config dispatches the per-expert
    balanced kernels (engine stats != 0) with sparse-vs-masked-dense
    logits parity (checked inside serve.main)."""
    from repro.launch import serve
    results = serve.main(["--arch", "deepseek-moe-16b", "--smoke",
                          "--batch", "2", "--prompt-len", "16",
                          "--gen-steps", "2", "--sparsity", "0.5"])
    assert results["plan"]["family"] == "moe"
    assert results["plan"]["engine_stats"].get("expert_balanced_spmm", 0) > 0
    assert results["plan"]["sparse_layers"] > 0
    assert results["sparse"]["tokens_per_s"] > 0


@pytest.mark.parametrize("arch", ["rwkv6-3b", "zamba2-1.2b"])
def test_serve_recurrent_families_end_to_end(arch):
    """RWKV6 / Zamba2 no longer fall back to dense-only serving: the plan
    executes the balanced kernels on the real token path."""
    from repro.launch import serve
    results = serve.main(["--arch", arch, "--smoke", "--batch", "2",
                          "--prompt-len", "16", "--gen-steps", "2",
                          "--sparsity", "0.5"])
    assert results["plan"]["engine_stats"].get("balanced_spmm", 0) > 0
    assert results["sparse"]["tokens_per_s"] > 0


# ---------------------------------------------------------------------------
# Fig.22b — per-layer RIF/RWF mode mix on the paper networks
# ---------------------------------------------------------------------------

def test_fig22b_mode_mix_regression():
    """Pin the adaptive dataflow's per-layer mode mix (frac_rwf) and the
    DRAM reduction vs fixed-RIF on the four paper networks."""
    from repro.core.dataflow import network_dram_access
    from repro.core.systolic import SystolicConfig
    from repro.models.cnn import network_layers
    cfg = SystolicConfig()
    expect = {
        # net: (n_layers, frac_rwf, min_reduction_vs_fixed_rif)
        "alexnet": (8, 3 / 8, 1.23),
        "vgg16": (16, 6 / 16, 1.88),
        "resnet50": (54, 0.0, 1.0),
        "googlenet": (58, 0.0, 1.0),
    }
    for net, (n_layers, frac_rwf, min_red) in expect.items():
        layers = network_layers(net, "sense")
        assert len(layers) == n_layers
        a = network_dram_access(layers, adaptive=True, n_is=cfg.n_is,
                                n_pe=cfg.n_pe,
                                weight_buffer_bits=cfg.weight_buffer_bits)
        f = network_dram_access(layers, adaptive=False, n_is=cfg.n_is,
                                n_pe=cfg.n_pe,
                                weight_buffer_bits=cfg.weight_buffer_bits)
        assert a["frac_rwf"] == pytest.approx(frac_rwf), net
        red = f["total_bits"] / a["total_bits"]
        assert red >= min_red, (net, red)
        # adaptive never loses to the fixed dataflow (it subsumes it)
        assert a["total_bits"] <= f["total_bits"], net
