"""The plain float32 references against the program's masked-dense path at
smoke width (CPU), and the reference's independence from the program."""
import dataclasses
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.reference import common
from bench.weights import make_params

from .smoke import smoke_spec

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**33 + 17


def program(spec):
    from repro.configs import ModelConfig
    from repro.engine.plan import masked_dense_params, plan_model
    from repro.models import build_model
    cfg = ModelConfig(**spec["model"], sparse_serving=True)
    params = make_params(spec["model"], SEED)
    plan = plan_model(cfg, params, sparsity=0.5, m_hint=16, decode_m=2)
    dense = dataclasses.replace(cfg, sparse_serving=False)
    return build_model(dense), masked_dense_params(params, plan), plan


@pytest.mark.parametrize("arch", ["olmo-1b"])
def test_reference_matches_masked_dense_prefill_and_decode(arch):
    spec = smoke_spec(arch)
    bundle, params, _ = program(spec)
    rng = np.random.default_rng(0)
    bsz, plen, steps = 2, 12, 4
    toks = rng.integers(0, spec["model"]["vocab_size"],
                        (bsz, plen + steps)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, pc = jax.jit(bundle.prefill)(params,
                                             {"tokens": toks[:, :plen]})
        cache = jax.tree.map(
            lambda z, p: z.at[tuple(slice(0, n) for n in p.shape)].set(p),
            bundle.init_cache(bsz, plen + steps), pc)
        got = [logits]
        for i in range(steps - 1):
            lg, cache = jax.jit(bundle.decode_step)(
                params, {"tokens": toks[:, plen + i:plen + i + 1],
                         "cache_len": jnp.full((bsz,), plen + i,
                                               jnp.int32)}, cache)
            got.append(lg)
        got = np.stack([np.asarray(g) for g in got], 1)
        fn = reference.hidden_fn(spec["model"]["family"])
        h = fn(spec, make_params(spec["model"], SEED),
               jnp.asarray(toks[:, :plen + steps - 1]), False)
        ref = np.asarray(h[:, plen - 1:] @ make_params(
            spec["model"], SEED)["embed"].T)
    # prefill's logits come from f32 activations: summation order only
    np.testing.assert_allclose(got[:, 0], ref[:, 0], atol=2e-4, rtol=0)
    # decode reads K/V back from the cache, which holds them in bf16
    np.testing.assert_allclose(got, ref, atol=1e-2, rtol=0)
    assert np.abs(got - ref).max() > 0


@pytest.mark.parametrize("arch", ["olmo-1b"])
def test_reference_prunes_as_the_plan_does(arch):
    spec = smoke_spec(arch)
    _, _, plan = program(spec)
    params = make_params(spec["model"], SEED)
    for name in spec["planned"]:
        mine = jax.vmap(lambda w: common.prune_balanced(w, 0.5))(
            params["blocks"][name])
        theirs = jnp.swapaxes(plan.layers[name].dense_weights(), -1, -2)
        np.testing.assert_array_equal(np.asarray(mine) != 0,
                                      np.asarray(theirs) != 0)


def test_reference_reads_nothing_of_the_program():
    code = ("import sys; sys.path.insert(0, %r); import bench.reference, "
            "bench.weights; print(sorted(m for m in sys.modules "
            "if m.split('.')[0] == 'repro'))" % str(ROOT))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={"JAX_PLATFORMS": "cpu",
                                         "PATH": "/usr/bin:/bin"},
                         check=True).stdout.strip()
    assert out == "[]"
    for f in (ROOT / "bench" / "reference").glob("*.py"):
        assert "repro" not in f.read_text().replace("reproduce", ""), f


def test_fp8_control_rounds_harder_than_bf16():
    x = jax.random.normal(jax.random.key(0), (4096,))
    e8 = float(jnp.abs(common.fp8(x) - x).max())
    e16 = float(jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x).max())
    assert e8 > 4 * e16


@pytest.mark.parametrize("arch", ["olmo-1b"])
def test_seed_changes_the_values_not_the_pattern(arch):
    spec = smoke_spec(arch)
    a = make_params(spec["model"], 5)
    b = make_params(spec["model"], 2**40 + 5)
    for name in spec["planned"]:
        wa, wb = a["blocks"][name], b["blocks"][name]
        np.testing.assert_array_equal(jnp.abs(wa), jnp.abs(wb))
        assert float(jnp.mean(jnp.sign(wa) != jnp.sign(wb))) > 0.4
    np.testing.assert_array_equal(a["embed"], make_params(spec["model"],
                                                          5)["embed"])
