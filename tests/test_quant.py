"""Quantized tile-local format, engine level: int8/int4 block-quant plans
through planning, dispatch, gradients, the degradation ladder, guard
probing, STATS, and checkpoint round-trip (DESIGN.md §13)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pruning import balanced_prune_rows, to_balanced_sparse
from repro.engine import execute as engine_execute
from repro.engine import guard as engine_guard
from repro.engine import plan as engine_plan
from repro.kernels import ops
from repro.kernels.tile_format import (QUANT_QMAX, TiledBalanced,
                                       dequantize_tiled, encode_tiled,
                                       quantize_tiled, tiled_storage_bits,
                                       tiled_to_dense)

QUANTS = ("int8", "int4")


def _quant_tb(o=48, n=96, k=None, bn=16, quant="int8", seed=0):
    k = k or n // 2
    w = jax.random.normal(jax.random.key(seed), (o, n))
    sp = to_balanced_sparse(w, k=k)
    tb = encode_tiled(sp.values, sp.indices, n, bn=bn)
    return quantize_tiled(tb, quant)


def _fc_plan(key=0, o=48, n=96, sparsity=0.6, **kw):
    w = jax.random.normal(jax.random.key(key), (o, n))
    _, mask = balanced_prune_rows(w, sparsity)
    lp = engine_plan.build_layer_plan("fc", w, mask=mask, m_hint=32, **kw)
    return w * mask, lp


# ---------------------------------------------------------------------------
# kernel parity: in-VMEM dequant vs the densified dequant reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", QUANTS)
@pytest.mark.parametrize("impl", ["pallas", "xla", "xla_gather"])
def test_tiled_spmm_quant_matches_dequant_reference(impl, quant):
    """Every impl's quant path must match ``x @ dequant(W).T`` — same
    reconstructed values, only contraction order differs (f32: 1e-5)."""
    qt = _quant_tb(quant=quant)
    x = jax.random.normal(jax.random.key(1), (9, 96))
    want = x @ tiled_to_dense(qt).T
    got = ops.tiled_spmm(x, qt, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quant", QUANTS)
def test_tiled_spmm_quant_skinny_decode_path(quant):
    """Decode-shaped M (<= ops.SKINNY_M) routes the skinny quant kernel;
    parity must hold there too."""
    qt = _quant_tb(quant=quant)
    m = min(4, ops.SKINNY_M)
    x = jax.random.normal(jax.random.key(2), (m, 96))
    want = x @ tiled_to_dense(qt).T
    got = ops.tiled_spmm(x, qt, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quant", QUANTS)
def test_tiled_spmm_batched_quant_parity(quant):
    """The batched-expert entry dequantizes per expert group."""
    g, o, n = 3, 32, 64
    w = jax.random.normal(jax.random.key(3), (g, o, n))
    tbs = []
    for e in range(g):
        sp = to_balanced_sparse(w[e], k=n // 2)
        tbs.append(encode_tiled(sp.values, sp.indices, n, bn=16))
    stack = TiledBalanced(jnp.stack([t.values for t in tbs]),
                          jnp.stack([t.indices for t in tbs]),
                          jnp.stack([t.counts for t in tbs]),
                          n_in=n, bn=16)
    qt = quantize_tiled(stack, quant)
    x = jax.random.normal(jax.random.key(4), (g, 5, n))
    got = ops.tiled_spmm_batched(x, qt, impl="pallas")
    for e in range(g):
        lane = TiledBalanced(qt.values[e], qt.indices[e], qt.counts[e],
                             n_in=n, bn=16, scales=qt.scales[e], quant=quant)
        want = x[e] @ tiled_to_dense(lane).T
        np.testing.assert_allclose(np.asarray(got[e]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quant", QUANTS)
def test_tiled_spmm_quant_grad_straight_through(quant):
    """d/dx flows through the dequantized weights (straight-through): the
    gradient equals the dense dequant matmul's gradient exactly."""
    qt = _quant_tb(quant=quant)
    x = jax.random.normal(jax.random.key(5), (6, 96))
    dense = tiled_to_dense(qt)
    g = jax.grad(lambda a: jnp.sum(ops.tiled_spmm(a, qt, impl="xla") ** 2))(x)
    g_ref = jax.grad(lambda a: jnp.sum((a @ dense.T) ** 2))(x)
    # 1e-4: the skinny forward factors the block scale out of the slot
    # reduction, so y (and thus dL/dx through the squared loss) is the
    # same sum reassociated
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_quant_storage_bits_shrink_monotonically():
    tb = _quant_tb(quant="int8")
    f32 = dequantize_tiled(tb)
    bits = {q: tiled_storage_bits(_quant_tb(quant=q)) for q in QUANTS}
    assert bits["int4"] < bits["int8"] < tiled_storage_bits(f32,
                                                            elem_bits=32)


# ---------------------------------------------------------------------------
# planning: quant threads plan -> weights -> spec
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", QUANTS)
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_plan_stores_quantized_tiles_for_all_sparse_impls(impl, quant):
    """Sparse impls keep the tiled format when quantized (scales are
    tile-local), even the xla fallbacks that store flat f32 unquantized."""
    _, lp = _fc_plan(impl=impl, quant=quant)
    assert lp.spec.quant == quant
    assert isinstance(lp.weights, TiledBalanced)
    assert lp.weights.quant == quant
    assert lp.weights.scales is not None
    want_dtype = jnp.int8 if quant == "int8" else jnp.uint8
    assert lp.weights.values.dtype == want_dtype


def test_plan_dense_impl_never_quantizes():
    _, lp = _fc_plan(impl="dense", quant="int8")
    assert lp.spec.quant == "none"
    assert not isinstance(lp.weights, TiledBalanced)


def test_plan_rejects_unknown_quant():
    with pytest.raises(ValueError, match="quant"):
        _fc_plan(impl="xla", quant="int3")


@pytest.mark.parametrize("quant", QUANTS)
def test_quant_plan_parity_within_block_bound(quant):
    """apply_layer on a quant plan matches the masked-dense weights within
    the per-block absmax bound, and matches its own dequant reference
    (lp.dense_weights()) to f32 round-off."""
    wm, lp = _fc_plan(impl="xla", quant=quant)
    x = jax.random.normal(jax.random.key(6), (7, 96))
    got = np.asarray(engine_execute.apply_layer(x, lp))
    ref = np.asarray(x @ lp.dense_weights().T)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # error vs the unquantized masked weights is bounded by the quant grid:
    # sum over blocks of |x|_block * scale/2
    scales = np.asarray(lp.weights.scales)
    xa = np.abs(np.asarray(x))
    bn, nb = lp.weights.bn, lp.weights.nb
    xpad = np.zeros((x.shape[0], nb * bn), np.float32)
    xpad[:, :x.shape[1]] = xa
    xb = xpad.reshape(x.shape[0], nb, bn).sum(-1)          # [M, NB]
    bound = xb @ (scales / 2) + 1e-5                       # [M, O]
    err = np.abs(got - np.asarray(x @ wm.T))
    assert (err <= bound * (1 + 1e-5)).all()


@pytest.mark.parametrize("quant", QUANTS)
def test_guard_probe_accepts_quant_plan(quant):
    """validate_plan's probe must pass a healthy quant plan under the
    widened per-quant tolerance — and f32 plans keep the exact bound."""
    _, lp = _fc_plan(impl="xla", quant=quant)
    plan = engine_plan.ModelPlan(layers={"fc": lp}, meta=())
    report = engine_guard.validate_plan(plan, strict=True, probe=True)
    assert report.ok
    assert report.layers["fc"].probe_max_diff is not None


def test_probe_tol_per_quant_regression():
    """f32 unquantized probes keep the tight 1e-4 parity; quant probes get
    5e-2 so round-off never spuriously demotes a healthy quant plan."""
    assert engine_guard._probe_tol(jnp.float32) == pytest.approx(1e-4)
    assert engine_guard._probe_tol(jnp.bfloat16) == pytest.approx(2e-2)
    for q in QUANTS:
        assert engine_guard._probe_tol(jnp.float32, q) == pytest.approx(5e-2)


def test_harden_quant_plan_no_spurious_demotions():
    layers = {}
    for i, q in enumerate(QUANTS):
        _, layers[f"l{i}_{q}"] = _fc_plan(key=i, impl="pallas", quant=q)
    plan = engine_plan.ModelPlan(layers=layers, meta=())
    hardened, events = engine_guard.harden_plan(plan)
    assert not events
    assert hardened.degraded_mix() == {}


# ---------------------------------------------------------------------------
# dispatch accounting + the degradation ladder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", QUANTS)
def test_stats_count_quant_dispatches(quant):
    _, lp = _fc_plan(impl="xla", quant=quant)
    x = jax.random.normal(jax.random.key(7), (4, 96))
    engine_execute.reset_stats()
    engine_execute.apply_layer(x, lp)
    stats = engine_execute.stats()
    assert stats.get(f"quant_{quant}", 0) == 1
    assert stats.get("balanced_spmm", 0) == 1


@pytest.mark.parametrize("quant", QUANTS)
def test_demote_quant_keeps_tiles_on_sparse_rungs(quant):
    """pallas -> xla/xla_gather demotion keeps the packed quant encoding
    (scales are tile-local — no re-encode, no precision churn); the dense
    floor dequantizes and drops quant from the spec."""
    wm, lp = _fc_plan(impl="pallas", quant=quant)
    x = jax.random.normal(jax.random.key(8), (5, 96))
    want = np.asarray(engine_execute.apply_layer(x, lp))
    for impl in ("xla", "xla_gather"):
        lp_d = engine_execute.demote_layer(lp, to_impl=impl)
        assert lp_d.spec.impl == impl
        assert lp_d.spec.quant == quant
        assert isinstance(lp_d.weights, TiledBalanced)
        assert lp_d.weights.quant == quant
        np.testing.assert_allclose(
            np.asarray(engine_execute.apply_layer(x, lp_d)), want,
            rtol=1e-5, atol=1e-5)
    lp_dense = engine_execute.demote_layer(lp, to_impl="dense")
    assert lp_dense.spec.impl == "dense"
    assert lp_dense.spec.quant == "none"
    assert not isinstance(lp_dense.weights, TiledBalanced)
    np.testing.assert_allclose(
        np.asarray(engine_execute.apply_layer(x, lp_dense)), want,
        rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# checkpoint round-trip: packed narrow leaves survive the store
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", QUANTS)
def test_checkpoint_roundtrips_quant_plan(tmp_path, quant):
    from repro.checkpoint.store import restore_checkpoint, save_checkpoint
    _, lp = _fc_plan(impl="xla", quant=quant)
    plan = engine_plan.ModelPlan(layers={"fc": lp}, meta=())
    save_checkpoint(tmp_path, 1, {"sparse_plan": plan})
    _, template_lp = _fc_plan(impl="xla", quant=quant)
    template = {"sparse_plan": engine_plan.ModelPlan(
        layers={"fc": template_lp}, meta=())}
    tree, _ = restore_checkpoint(tmp_path, 1, template)
    got = tree["sparse_plan"].layers["fc"].weights
    assert got.quant == quant
    assert got.values.dtype == lp.weights.values.dtype
    np.testing.assert_array_equal(np.asarray(got.values),
                                  np.asarray(lp.weights.values))
    np.testing.assert_array_equal(np.asarray(got.scales),
                                  np.asarray(lp.weights.scales))
    x = jax.random.normal(jax.random.key(9), (3, 96))
    np.testing.assert_array_equal(
        np.asarray(engine_execute.apply_layer(x, tree["sparse_plan"]
                                              .layers["fc"])),
        np.asarray(engine_execute.apply_layer(x, lp)))


# ---------------------------------------------------------------------------
# model-level: plan_model(quant=) end to end on the transformer family
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("quant", QUANTS)
def test_plan_model_quant_serves_with_parity(quant):
    from repro.configs import get_smoke
    from repro.models import build_model
    cfg = dataclasses.replace(get_smoke("olmo-1b"), sparse_serving=True)
    bundle = build_model(cfg)
    params = bundle.init(jax.random.key(0))
    plan = engine_plan.plan_model(cfg, params, sparsity=0.5, m_hint=32,
                                  decode_m=2, quant=quant)
    assert dict(plan.meta).get("quant") == quant
    assert all(lp.spec.quant == quant for lp in plan.layers.values()
               if lp.spec.impl != "dense")
    prompt = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                cfg.vocab_size)
    ref = engine_plan.masked_dense_params(params, plan)
    engine_execute.reset_stats()
    logits, _ = bundle.prefill({**params, "sparse_plan": plan},
                               {"tokens": prompt})
    want, _ = bundle.prefill(ref, {"tokens": prompt})
    diff = float(jnp.max(jnp.abs(logits - want)))
    assert diff <= 5e-2, f"quant={quant} parity {diff}"
    stats = engine_execute.stats()
    assert stats.get(f"quant_{quant}", 0) > 0
