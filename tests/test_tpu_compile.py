"""Compile-only checks of the main-path Pallas kernels for a TPU v5e chip
that is described, not attached (the TPU compiler is installed with JAX).

Each test compiles — never runs — one raw ``pallas_call`` with
``interpret=False`` at olmo-1b widths (d_model 2048, d_ff 8192, bn 128, a
50%-sparse KB of 88 slots) and checks the compiled program holds the Mosaic
kernel (``tpu_custom_call``).  This is what interpret-mode tests cannot
see: block shapes Mosaic refuses, primitives it has no lowering for, and
VMEM overflows.  Each compiled kernel also carries its name in the
``kernel_metadata`` the benchmark credits its device time by.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library.  The persistent
compilation cache is off around these compiles (an entry written for a
described chip cannot be read back without one).  Mosaic dumps each
kernel's lowering into a module temp directory, from which one test reads
the skinny kernel's LLO.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.balanced_spmm import (tiled_balanced_spmm_batched_pallas,
                                         tiled_balanced_spmm_pallas,
                                         tiled_balanced_spmm_skinny_pallas)
from repro.kernels.tile_format import TiledBalanced

BN, KB = 128, 88
# (O, N) of olmo-1b's projections: attention, MLP up/gate, MLP down
SHAPES = [(2048, 2048), (8192, 2048), (2048, 8192)]
QUANTS = ["none", "int8", "int4"]


@pytest.fixture(scope="module")
def mosaic_dump(tmp_path_factory):
    return tmp_path_factory.mktemp("mosaic")


@pytest.fixture(scope="module")
def one_chip(mosaic_dump):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        # read once, where the TPU library loads: before the topology
        mp.setenv("LIBTPU_INIT_ARGS", " ".join(filter(None, [
            os.environ.get("LIBTPU_INIT_ARGS"),
            f"--xla_mosaic_dump_to={mosaic_dump}"])))
        from jax.experimental import topologies
        from jax.experimental.compilation_cache import compilation_cache
        from jax.sharding import SingleDeviceSharding
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _weights(sharding, lead, o, n, quant):
    """ShapeDtypeStructs of one tiled encoding ([*lead, NB, O, KB])."""
    nb = n // BN
    vdt = {"none": jnp.bfloat16, "int8": jnp.int8, "int4": jnp.uint8}[quant]
    kbp = KB // 2 if quant == "int4" else KB
    vals = _sds(sharding, (*lead, nb, o, kbp), vdt)
    idx = _sds(sharding, (*lead, nb, o, KB), jnp.int32)
    scales = None if quant == "none" else \
        _sds(sharding, (*lead, nb, o), jnp.float32)
    return vals, idx, scales


def _assert_mosaic(kernel, fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert re.search(r'kernel_metadata=\{\s*"kernel":"%s"\s*\}' % kernel, text)


@pytest.mark.parametrize("quant", QUANTS)
@pytest.mark.parametrize("o,n", SHAPES)
def test_wide_kernel_compiles_for_v5e(one_chip, o, n, quant):
    vals, idx, scales = _weights(one_chip, (), o, n, quant)

    def f(x, v, i, s):
        tb = TiledBalanced(v, i, None, n_in=n, bn=BN, scales=s, quant=quant)
        return tiled_balanced_spmm_pallas(x, tb, bm=128, bo=128,
                                          interpret=False)
    _assert_mosaic("balanced_spmm_wide", f,
                   _sds(one_chip, (128, n), jnp.bfloat16), vals, idx, scales)


@pytest.mark.parametrize("quant", QUANTS)
@pytest.mark.parametrize("o,n", SHAPES)
def test_skinny_kernel_compiles_for_v5e(one_chip, o, n, quant):
    vals, idx, scales = _weights(one_chip, (), o, n, quant)

    def f(x, v, i, s):
        tb = TiledBalanced(v, i, None, n_in=n, bn=BN, scales=s, quant=quant)
        return tiled_balanced_spmm_skinny_pallas(x, tb, bo=128,
                                                 interpret=False)
    _assert_mosaic("balanced_spmm_skinny", f,
                   _sds(one_chip, (8, n), jnp.bfloat16), vals, idx, scales)


@pytest.mark.parametrize("quant", QUANTS)
def test_batched_expert_kernel_compiles_for_v5e(one_chip, quant):
    e, o, n = 8, 2048, 2048
    vals, idx, scales = _weights(one_chip, (e,), o, n, quant)

    def f(x, v, i, s):
        return tiled_balanced_spmm_batched_pallas(
            x, v, i, bn=BN, bm=128, bo=128, scales=s, quant=quant,
            interpret=False)
    _assert_mosaic("balanced_spmm_batched", f,
                   _sds(one_chip, (e, 128, n), jnp.bfloat16), vals, idx,
                   scales)


def test_skinny_kernel_decode_has_no_cross_lane_permutes(one_chip,
                                                         mosaic_dump):
    """The skinny kernel's LLO (olmo-1b's MLP up shape): one compare per
    slot and vreg of the [128, 128] f32 tile, KB x 16, and no cross-lane
    permute: each slot is a row that reaches the tile by a sublane
    broadcast.  Every skinny dump of this module is read, whatever quant
    its compile used."""
    o, n = 8192, 2048
    vals, idx, _ = _weights(one_chip, (), o, n, "none")

    def f(x, v, i):
        tb = TiledBalanced(v, i, None, n_in=n, bn=BN)
        return tiled_balanced_spmm_skinny_pallas(x, tb, bo=128,
                                                 interpret=False)
    _assert_mosaic("balanced_spmm_skinny", f,
                   _sds(one_chip, (8, n), jnp.bfloat16), vals, idx)
    dumps = sorted(mosaic_dump.glob("*balanced_spmm_skinny*finalize-llo*"))
    if not dumps:
        pytest.skip("Mosaic wrote no LLO dump (the TPU library was loaded "
                    "before this module set its flags)")
    for path in dumps:
        text = path.read_text()
        assert "llo.vperm" not in text, path.name
        assert len(re.findall(r"\bllo\.vcmp\.", text)) == KB * 16, path.name
