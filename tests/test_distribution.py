"""Distribution-layer tests.

Sharding rule units run on a 1-device mesh; the lower+compile integration
(real 4x4 mesh, collectives in HLO) runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=16, because device count
locks at first jax init and the main pytest process must stay at 1 device
for the smoke tests.
"""
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (dim_spec, dp_axes, logical_spec,
                                        shard_batch)
from repro.launch.mesh import make_mesh


def mesh1():
    return make_mesh((1, 1), ("data", "model"))


def test_dim_spec_divisibility_guard():
    m = mesh1()
    assert dim_spec(m, 7, "data") == "data"     # axis size 1 divides all
    assert dim_spec(m, 7, "missing_axis") is None


def test_logical_spec_no_axis_reuse():
    m = mesh1()
    spec = logical_spec(m, (4, 4), [["data"], ["data"]])
    # second dim must not reuse the already-used axis
    assert spec == P("data", None)


def test_shard_batch_prefix():
    m = mesh1()
    assert shard_batch(m, 8) == ("data",)
    assert dp_axes(m) == ("data",)


SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke
    from repro.models import build_model
    from repro.models.api import batch_partition_spec, input_specs
    from repro.distributed.sharding import tree_shardings
    from repro.configs.base import ShapeSpec
    from repro.launch import hlo_cost
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 4), ("data", "model"))
    failures = []
    for arch in ["olmo-1b", "rwkv6-3b", "zamba2-1.2b", "deepseek-moe-16b"]:
        cfg = get_smoke(arch)
        bundle = build_model(cfg, mesh)
        shape = ShapeSpec("t", "train", 32, 8)
        params_sds = jax.eval_shape(bundle.init,
                                    jax.ShapeDtypeStruct((2,), jnp.uint32))
        p_sh = tree_shardings(mesh, bundle.param_specs())
        b_sh = tree_shardings(mesh, batch_partition_spec(cfg, shape, mesh))
        lowered = jax.jit(bundle.train_loss,
                          in_shardings=(p_sh, b_sh)).lower(
            params_sds, input_specs(cfg, shape))
        compiled = lowered.compile()
        cost = hlo_cost.analyze(compiled.as_text())
        if cost.coll_bytes <= 0:
            failures.append(f"{arch}: no collectives in sharded train HLO")
    assert not failures, failures
    print("SUBPROCESS_OK")
""")


@pytest.mark.slow
def test_sharded_lower_compile_16dev_subprocess():
    """Every model family lowers+compiles on a real 4x4 mesh and the HLO
    contains collective traffic (the sharding annotations are live)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SUBPROCESS_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert "SUBPROCESS_OK" in out.stdout, out.stderr[-3000:]


@pytest.mark.slow
def test_dryrun_results_complete():
    """The dry-run campaign must cover all 40 cells x 2 meshes with no
    errors (compile failures are bugs in the distribution config)."""
    import glob
    import json
    root = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "results", "dryrun")
    files = glob.glob(os.path.join(root, "*__v0_baseline.json"))
    if len(files) < 80:
        pytest.skip("dry-run campaign incomplete — run "
                    "benchmarks/run_dryrun_campaign.sh")
    recs = [json.load(open(f)) for f in files]
    errs = [r["cell"] for r in recs if r["status"] == "error"]
    assert not errs, errs
    ok = [r for r in recs if r["status"] == "ok"]
    assert len(ok) >= 64
    for r in ok:
        assert r["flops_per_device"] > 0
        assert r["roofline"]["dominant"] in ("compute", "memory",
                                             "collective")


def test_host_mesh_is_auto_over_local_devices():
    """The serving mesh covers this process's devices as (data, model) with
    ``Auto`` axes — the axis type `with_sharding_constraint` needs."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.devices.size == len(jax.devices())
    assert all(t == AxisType.Auto for t in mesh.axis_types)


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """Entry points keep the persistent compile cache in
    $JAX_COMPILATION_CACHE_DIR when set (JAX reads it; nothing is set in
    code), else in the one fixed in-checkout directory."""
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir is None:
            assert got == str(compile_cache.DEFAULT_DIR)
            assert jax.config.jax_compilation_cache_dir == got
            assert compile_cache.DEFAULT_DIR.parent == \
                compile_cache.REPO_ROOT
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
