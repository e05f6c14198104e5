"""Plan execution: dispatch one pre-built `LayerPlan` per call site.

Where `plan.py` decides, this module merely *routes*: every projection and
convolution the plan covers dispatches on `LayerPlan.spec.impl` with the
weights already in the impl's native format — the Pallas path goes through
`kernels.ops.tiled_spmm` (pre-encoded `TiledBalanced`, no id()-keyed
encoding cache), the XLA fallbacks through `kernels.ops.balanced_spmm`
(flat format, no cache consulted because impl != "pallas"), and dense
layers through plain matmul/conv.

STATS counts how many balanced-sparse kernel dispatches were *traced* into
the computation (a trace-time counter: under jit each compiled executable
counts its kernels once, not once per run).  `launch/serve.py` uses it to
assert the sparse serving path is real rather than a dense matmul on
zeroed weights.

BYTE_STATS counts the bytes each traced dispatch streams — the encoded
weights (every leaf of the layer's weight pytree at its stored width,
nibble-packed int4 included) plus the activation operand/result — keyed by
layer name.  Shapes are static at trace time, so the counters are exact
and tracer-safe; `launch.cost_model` mirrors the same accounting
analytically and `tests/test_cost_model.py` pins the two against each
other (the model-vs-measurement contract, DESIGN.md §14).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..core.pruning import BalancedSparse
from ..kernels import ops as kernel_ops
from ..kernels.sparse_conv import sparse_conv2d as _sparse_conv2d
from ..kernels.tile_format import (TiledBalanced, dequantize_tiled,
                                   tiled_to_flat)
# The impl-degradation ladder (most specialized first): when a layer's
# preferred impl fails to trace/compile/lower, `engine.guard.harden_plan`
# steps it down one rung at a time.  Dense is the floor — a plain masked
# matmul that cannot fail for kernel reasons.  Canonically defined next to
# the cost model (plan-time impl co-optimization moves along the same
# ladder) and re-exported here for the execute/guard call sites.
from ..launch.cost_model import IMPL_LADDER, pytree_nbytes
from .plan import LayerPlan, ModelPlan

Array = jax.Array

# trace-time dispatch counters (see module docstring)
STATS: "collections.Counter[str]" = collections.Counter()

# trace-time byte counters, keyed by layer name (see module docstring)
BYTE_STATS: Dict[str, "collections.Counter[str]"] = {}


def reset_stats() -> None:
    STATS.clear()
    BYTE_STATS.clear()


def stats() -> dict:
    return dict(STATS)


def bytes_stats() -> dict:
    """Per-layer streamed-byte counters: ``{layer: {bytes_weights,
    bytes_act_in, bytes_act_out, dispatches}}`` (trace-time, like STATS)."""
    return {nm: dict(c) for nm, c in BYTE_STATS.items()}


def _count_bytes(spec, weights: Any, x: Array, y: Array) -> None:
    """Record one dispatch's streamed bytes.  Leaf shapes/dtypes are static
    under jit, so this counts stored bytes exactly even on tracers.  For
    scanned stacks the weights arrive scan-sliced, so the figure is
    per-dispatch — the quantity `PlanSpec.cost.w_stream_bytes` models."""
    wb = int(pytree_nbytes(weights))
    xb = int(x.size) * x.dtype.itemsize
    yb = int(y.size) * y.dtype.itemsize
    c = BYTE_STATS.setdefault(spec.name, collections.Counter())
    c["bytes_weights"] += wb
    c["bytes_act_in"] += xb
    c["bytes_act_out"] += yb
    c["dispatches"] += 1
    STATS["bytes_weights"] += wb
    STATS["bytes_act_in"] += xb
    STATS["bytes_act_out"] += yb


def _count_dispatch(spec, *extra: str) -> None:
    """Record one balanced-sparse dispatch: the kernel family, the impl,
    and — when the plan's `BlockChoice` came from the measured autotuner
    rather than the static VMEM model — a ``tuned_blocks`` tick, so serve
    (and tests) can observe that tuned choices reached the execute path.
    Layers the guard ladder demoted additionally tick
    ``degraded_dispatch`` so degraded serving is observable in STATS."""
    STATS["balanced_spmm"] += 1
    STATS[f"impl_{spec.impl}"] += 1
    if spec.tuned != "static":
        STATS["tuned_blocks"] += 1
    if spec.degraded_from:
        STATS["degraded_dispatch"] += 1
    if spec.quant != "none":
        STATS[f"quant_{spec.quant}"] += 1
    for name in extra:
        STATS[name] += 1


# ---------------------------------------------------------------------------
# Impl-degradation ladder (the mechanics; policy lives in engine.guard)
# ---------------------------------------------------------------------------

def next_impl(impl: str) -> str | None:
    """The next rung down `IMPL_LADDER` (None below dense)."""
    i = IMPL_LADDER.index(impl)
    return IMPL_LADDER[i + 1] if i + 1 < len(IMPL_LADDER) else None


def _tiled_to_flat_stacked(w: TiledBalanced):
    """`tiled_to_flat` over any leading stacked axes ([*lead, NB, O, KB]):
    lead axes fold into the row axis (every row carries the same K under
    the balance invariant), decode flat, restack.  Packed encodings pass
    their (lead-broadcast, identical per slice) perm through so the flat
    indices come out in original column order, ascending."""
    if w.quant != "none":
        # decode the narrow values back to f32 first: the flat format has
        # no tile-local scale slot to carry them
        w = dequantize_tiled(w)
    lead = w.values.shape[:-3]
    nb, o = w.indices.shape[-3], w.indices.shape[-2]
    perm = w.perm
    if perm is not None and perm.ndim > 1:
        perm = perm.reshape(-1, perm.shape[-1])[0]

    def fold(a):                        # [*lead, NB, O, ...] -> [NB, g*O, ...]
        a = a.reshape(-1, nb, o, *a.shape[len(lead) + 2:])
        return jnp.moveaxis(a, 0, 1).reshape(nb, -1, *a.shape[3:])
    flat = TiledBalanced(fold(w.values), fold(w.indices), fold(w.counts),
                         n_in=w.n_in, bn=w.bn, perm=perm)
    vals, idx = tiled_to_flat(flat)
    k = vals.shape[-1]
    return (vals.reshape(*lead, o, k), idx.reshape(*lead, o, k))


def demote_layer(lp: LayerPlan, *, to_impl: str | None = None,
                 ref_dense: Array | None = None) -> LayerPlan:
    """Re-target one LayerPlan at a lower ladder rung, re-encoding the
    weights to that impl's native format.

    pallas -> xla/xla_gather decodes the tile-local encoding back to the
    flat balanced format; any impl -> dense densifies (or substitutes
    ``ref_dense``, the quarantine path: a known-good [*lead, O, N] masked
    weight replaces the possibly-poisoned encoding).  The original impl is
    recorded in ``spec.degraded_from`` so the degradation stays visible in
    plan summaries and STATS.
    """
    spec = lp.spec
    to_impl = to_impl or next_impl(spec.impl)
    if to_impl is None:
        raise ValueError(f"{spec.name}: no rung below impl {spec.impl!r}")
    if to_impl == spec.impl and ref_dense is None:
        return lp
    origin = spec.degraded_from or spec.impl
    if to_impl == "dense":
        weights = ref_dense if ref_dense is not None else lp.dense_weights()
        if spec.kind == "conv" and weights.ndim == 2:
            # apply_conv's dense path convolves the 4-D layout
            ci = spec.n_in // (spec.hk * spec.wk)
            weights = weights.reshape(spec.n_out, ci, spec.hk, spec.wk)
        # re-encoding invalidates the cost provenance (byte counts change);
        # drop the tag rather than let guard flag a stale one
        new_spec = dataclasses.replace(spec, impl="dense", k=spec.n_in,
                                       blocks=None, block_k=0,
                                       blocks_decode=None, packed=False,
                                       quant="none", degraded_from=origin,
                                       cost=None)
        return LayerPlan(spec=new_spec, weights=weights)
    if isinstance(lp.weights, TiledBalanced) and spec.quant != "none":
        # quantized encodings keep the tiled format on every sparse rung —
        # the per-block scales live tile-locally, and `tiled_spmm` routes
        # xla / xla_gather on them directly
        return LayerPlan(spec=dataclasses.replace(spec, impl=to_impl,
                                                  degraded_from=origin),
                         weights=lp.weights)   # same encoding: tag stays valid
    if isinstance(lp.weights, TiledBalanced):
        vals, idx = _tiled_to_flat_stacked(lp.weights)
        weights: Any = BalancedSparse(vals, idx, spec.n_in)
    else:
        weights = lp.weights             # xla <-> xla_gather share a format
    # the flat format carries no perm: packing provenance ends here (and the
    # re-encoded bytes invalidate the cost tag)
    return LayerPlan(spec=dataclasses.replace(spec, impl=to_impl,
                                              packed=False,
                                              degraded_from=origin,
                                              cost=None),
                     weights=weights)


def _per_shard(fn, x: Array, lp: LayerPlan) -> Array:
    """Run ``fn(x, weights)`` on every device's shard of a sharded plan's
    output channels (`plan.shard_plan`): XLA cannot partition a Mosaic
    kernel, so the call goes through ``shard_map``.  ``x`` enters
    replicated; the output keeps the weights' output-channel sharding."""
    from jax.sharding import PartitionSpec as P

    from .plan import _layer_weight_specs
    mesh = lp.spec.mesh
    wspec = _layer_weight_specs(lp, mesh)
    o_axes = wspec.values[-2]                       # [NB, O, KB]: O axis
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(), wspec),
                         out_specs=P(*([None] * (x.ndim - 1)), o_axes),
                         check_vma=False)(x, lp.weights)


def apply_fc(x: Array, lp: LayerPlan) -> Array:
    """``y = x @ W.T`` for one planned linear layer.

    ``x``: ``[..., N]`` -> ``[..., O]``.  Dispatches on ``lp.spec.impl``:
    ``dense`` is a plain matmul on the masked weights; ``pallas`` runs the
    pre-encoded `kernels.ops.tiled_spmm` at the plan's (possibly autotuned)
    ``spec.blocks`` — or ``spec.blocks_decode`` when M is decode-shaped
    (M <= `kernels.ops.SKINNY_M`; static at trace time, so the routing is
    free and each compiled executable bakes in its shape's blocks).
    ``block_m`` is additionally clamped to the *live* M's power-of-two
    bucket (`kernels.ops.bucket_m`, 8-row sublane floor): the plan's bm
    was resolved at ``m_hint``/``decode_m``, but the serving runtime
    dispatches a spread of batch buckets and chunk widths, and a small
    live M must not pad up to a stale prefill-sized tile.
    ``xla``/``xla_gather`` run the flat-format `kernels.ops.balanced_spmm`
    fallbacks, which route skinny M internally.
    """
    spec = lp.spec
    if spec.impl == "dense":
        STATS["dense_matmul"] += 1
        y = jnp.dot(x, lp.weights.T,
                    preferred_element_type=jnp.float32).astype(x.dtype)
        _count_bytes(spec, lp.weights, x, y)
        return y
    m = 1
    for d in x.shape[:-1]:
        m *= d
    skinny = m <= kernel_ops.SKINNY_M
    _count_dispatch(spec, *(("decode_dispatch",) if skinny else ()))
    if isinstance(lp.weights, TiledBalanced):
        # pallas plans, plus quantized xla/xla_gather plans (the tiled
        # format carries the per-block scales; `tiled_spmm` routes impl)
        blk = spec.blocks_decode if skinny and spec.blocks_decode \
            else spec.blocks
        bm = min(blk.bm, max(8, kernel_ops.bucket_m(m)))

        def run(xx, w):
            return kernel_ops.tiled_spmm(xx, w, block_m=bm, block_o=blk.bo,
                                         impl=spec.impl)
        y = run(x, lp.weights) if spec.mesh is None or spec.impl != "pallas" \
            else _per_shard(run, x, lp)
    else:
        sp = lp.weights
        y = kernel_ops.balanced_spmm(x, sp.values, sp.indices,
                                     n_in=spec.n_in, impl=spec.impl,
                                     block_k=spec.block_k)
    _count_bytes(spec, lp.weights, x, y)
    return y


def apply_expert_fc(x: Array, lp: LayerPlan) -> Array:
    """Per-expert planned projection: ``x [E, ..., N] -> [E, ..., O]``.

    ``lp.weights`` carry a leading expert axis (plan built from a rank-3
    ``[E, d, f]`` MoE tensor, scan-sliced to one layer).  Every impl is a
    single *fused* dispatch over all experts — the Pallas impl runs
    `kernels.ops.tiled_spmm_batched` (E is a grid axis of one batched
    kernel), the XLA fallbacks run `kernels.ops.balanced_spmm_batched`
    (gather+einsum when skinny, unrolled densify+dot when wide).  The
    per-expert `lax.scan` that used to live
    here paid E sequential dispatches per layer, which at decode capacities
    dwarfed the math (the 0.10x MoE decode cliff, BENCH_serve PR 5).
    Counts ``expert_balanced_spmm`` in `STATS` so MoE serving can assert
    the per-expert path dispatched.
    """
    spec = lp.spec
    if spec.impl == "dense":
        STATS["dense_matmul"] += 1
        y = jnp.einsum("e...n,eon->e...o", x,
                       lp.weights.astype(x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
        _count_bytes(spec, lp.weights, x, y)
        return y
    m = 1
    for d in x.shape[1:-1]:
        m *= d
    skinny = m <= kernel_ops.SKINNY_M
    _count_dispatch(spec, "expert_balanced_spmm",
                    *(("decode_dispatch",) if skinny else ()))
    if isinstance(lp.weights, TiledBalanced):
        blk = spec.blocks_decode if skinny and spec.blocks_decode \
            else spec.blocks
        # same live-M clamp as apply_fc: m here is per-expert capacity
        bm = min(blk.bm, max(8, kernel_ops.bucket_m(m)))
        y = kernel_ops.tiled_spmm_batched(x, lp.weights, block_m=bm,
                                          block_o=blk.bo, impl=spec.impl)
    else:
        sp = lp.weights
        y = kernel_ops.balanced_spmm_batched(x, sp.values, sp.indices,
                                             n_in=spec.n_in, impl=spec.impl)
    _count_bytes(spec, lp.weights, x, y)
    return y


def apply_conv(x: Array, lp: LayerPlan) -> Array:
    """NHWC convolution for a planned conv layer: dense plans convolve the
    masked 4-D weights directly; sparse plans lower to the streamed
    im2col + balanced GEMM in `kernels.sparse_conv.sparse_conv2d` with the
    plan's pre-encoded weights and block choice."""
    spec = lp.spec
    if spec.impl == "dense":
        STATS["dense_conv"] += 1
        pad = spec.conv_padding
        if isinstance(pad, int):
            pad = [(pad, pad), (pad, pad)]
        y = jax.lax.conv_general_dilated(
            x, lp.weights.transpose(2, 3, 1, 0).astype(x.dtype),
            (spec.stride, spec.stride), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        _count_bytes(spec, lp.weights, x, y)
        return y
    _count_dispatch(spec)
    if isinstance(lp.weights, TiledBalanced):
        tb = lp.weights
        blk = spec.blocks

        def matmul_fn(flat, values, indices, n_in):
            return kernel_ops.tiled_spmm(flat, tb, block_m=blk.bm,
                                         block_o=blk.bo, impl=spec.impl)
        vals, idx = tb.values, tb.indices
    else:
        sp = lp.weights

        def matmul_fn(flat, values, indices, n_in):
            return kernel_ops.balanced_spmm(flat, values, indices,
                                            n_in=n_in, impl=spec.impl,
                                            block_k=spec.block_k)
        vals, idx = sp.values, sp.indices
    y = _sparse_conv2d(x, vals, idx, spec.n_in, hk=spec.hk, wk=spec.wk,
                       stride=spec.stride, padding=spec.conv_padding,
                       matmul_fn=matmul_fn)
    _count_bytes(spec, lp.weights, x, y)
    return y


def apply_layer(x: Array, lp: LayerPlan) -> Array:
    """Spec-directed dispatch: conv plans expect NHWC, expert plans
    [E, ..., N], fc plans [..., N]."""
    if lp.spec.kind == "conv":
        return apply_conv(x, lp)
    if lp.spec.experts:
        return apply_expert_fc(x, lp)
    return apply_fc(x, lp)


def apply_named(x: Array, plan: ModelPlan, name: str) -> Array:
    return apply_layer(x, plan.layers[name])


__all__ = ["apply_fc", "apply_expert_fc", "apply_conv", "apply_layer",
           "apply_named", "stats", "reset_stats", "bytes_stats", "STATS",
           "BYTE_STATS", "IMPL_LADDER", "next_impl", "demote_layer"]
