"""Pallas TPU kernel: balanced sparse x dense matmul (y = x @ W.T), MXU-native.

W is the *tile-local* balanced format (`tile_format.TiledBalanced`): each
output row's nonzeros are pre-partitioned by ``bn``-wide column blocks of the
input dimension, with block-local indices, stored block-major
``[NB, O, KB]``.  Load balance is what makes the kernel possible with static
shapes — balanced pruning pins the per-row total K and concentrates
per-block counts near K/NB, so every grid step does the same amount of
decode work with no per-row control flow (the TPU-native restatement of the
paper's equal-NZE-per-PE-column invariant, DESIGN.md §3).

Grid ``(M/bm, O/bo, NB)``: each step DMAs one weight block
``values/indices[nb, o-block] -> [bo, KB]`` (block ``(1, bo, KB)``: the last
two dims are a sublane multiple and the whole slot axis, which is what
Mosaic accepts), decodes it to a dense ``[bo, bn]`` f32 tile in VMEM, and
accumulates ``x_tile[bm, bn] @ w_tile.T`` on the MXU.  This is the
column-combining move (Kung et al.): sparse columns packed into dense tiles
the array consumes at full utilization.

The decode is an unrolled compare-select over the KB slots — slot ``k``
adds ``where(col == idx[:, k], val[:, k], 0)`` to the tile.  Mosaic has no
in-kernel scatter and no dynamic lane slicing, so this is the formulation
that lowers; it costs ``KB`` VPU passes over the tile per grid step.
Padded slots carry value 0 / index 0 and real indices are distinct within
a block, so the decode is exact with no count masking.

VMEM per step (f32): bm*bn (x) + bo*KB*2 (vals+idx) + bo*bn (decoded tile)
+ bm*bo (acc) — at bm=bo=bn=128, KB=64: ~0.26 MiB.  `ops.choose_blocks`
picks bm/bo/bn from shapes and a VMEM budget.

``interpret=None`` (the default everywhere) runs the Pallas interpreter on
a CPU backend and the compiled Mosaic kernel anywhere else; the backend is
read at call time (`interpret_mode`), never at import.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tile_format import TiledBalanced

Array = jax.Array


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` flag: an explicit bool wins, None
    means "interpret exactly when the default backend is the CPU" (Pallas
    TPU kernels cannot compile there).  Read per call, so a process that
    sees a TPU never runs a kernel in the interpreter."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


def _packed_kb(kb: int, quant: str) -> int:
    """Stored KB width of the values leaf: nibble-packed for int4."""
    return -(-kb // 2) if quant == "int4" else kb


def _decode_tile(vals, idx, scales, quant: str, bn: int):
    """Decode one weight block to a dense ``[bo, bn]`` f32 VMEM tile.

    ``vals`` is the stored encoding (f32/bf16, int8, or nibble-packed
    uint8), ``idx`` the block-local [bo, KB] indices, ``scales`` the
    per-row [bo, 1] block scales (None when quant == "none").  Quantized
    values dequantize *here*, in VMEM: each tile element receives at most
    one slot's ``q``, so scaling the decoded tile once is exactly
    `tile_format.dequantize_values` (the parity reference) per element.
    """
    bo, kb = idx.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (bo, bn), 1)
    words = vals.astype(jnp.int32 if quant == "int4" else jnp.float32)
    w = jnp.zeros((bo, bn), jnp.float32)
    for k in range(kb):
        if quant == "int4":
            # slot k is nibble k % 2 of byte k // 2; (n ^ 8) - 8 sign-extends
            nib = (words[:, k // 2:k // 2 + 1] >> (4 * (k % 2))) & 0xF
            v = ((nib ^ 8) - 8).astype(jnp.float32)
        else:
            v = words[:, k:k + 1]
        w = w + jnp.where(cols == idx[:, k:k + 1], v, 0.0)
    if scales is not None:
        w = w * scales
    return w


def _kernel(x_ref, v_ref, i_ref, *rest, quant: str, nb_axis: int):
    """One grid step: o_ref += x[bm, bn] @ decode(W block)[bn, bo].

    Shared by the wide, skinny and batched-expert grids; ``nb_axis`` names
    the grid axis that walks the column blocks (the reduction, innermost).
    Every ref carries unit leading block dims, squeezed here."""
    o_ref = rest[-1]
    s_ref = rest[0] if len(rest) == 2 else None
    nb = pl.program_id(nb_axis)

    @pl.when(nb == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].reshape(x_ref.shape[-2:])                    # [bm, bn]
    vals = v_ref[...].reshape(v_ref.shape[-2:])                 # [bo, KBp]
    idx = i_ref[...].reshape(i_ref.shape[-2:])                  # [bo, KB]
    scales = None if s_ref is None else s_ref[...].reshape(s_ref.shape[-2:])
    w_tile = _decode_tile(vals, idx, scales, quant, x.shape[1])
    # f32 activations get an f32 product (bf16 ones are one exact MXU pass)
    precision = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    acc = jax.lax.dot_general(x, w_tile.astype(x.dtype),
                              (((1,), (1,)), ((), ())), precision=precision,
                              preferred_element_type=jnp.float32)
    o_ref[...] += acc.reshape(o_ref.shape)


def named(kernel: str) -> dict:
    """``pallas_call`` keywords that name a kernel: its ``name`` and the
    ``kernel_metadata`` that the TPU custom call carries into the HLO,
    by which `bench/hlo_scopes.py` credits the kernel's device time."""
    return {"name": kernel, "metadata": {"kernel": kernel}}


def _weight_operands(values, indices, scales, quant: str):
    """Kernel operands: values, indices, and the scales as a trailing-unit
    column (``[.., NB, O, 1]``: a ``(bo, 1)`` block is whole in its last
    dim, where a ``[.., NB, O]`` row block would not be)."""
    if quant == "none":
        return (values, indices)
    return (values, indices, scales[..., None])


def tiled_balanced_spmm_pallas(x: Array, tb: TiledBalanced, *, bm: int = 128,
                               bo: int = 128,
                               interpret: bool | None = None) -> Array:
    """Raw pallas_call; shapes must already be tile-aligned (see ops.py).

    x: [M, NB*bn]; tb.values/indices: [NB, O, KB] with M % bm == O % bo == 0
    (int4 values are nibble-packed [NB, O, KB/2]; scales [NB, O] when
    quantized).  Returns f32 [M, O] (accumulator dtype; caller casts).
    """
    m, n = x.shape
    nb, o, kb = tb.indices.shape
    bn = tb.bn
    assert n == nb * bn and m % bm == 0 and o % bo == 0, (x.shape, tb.indices.shape, bm, bo, bn)
    kbp = _packed_kb(kb, tb.quant)
    in_specs = [
        pl.BlockSpec((bm, bn), lambda i, j, b: (i, b)),          # x col-block
        pl.BlockSpec((1, bo, kbp), lambda i, j, b: (b, j, 0)),   # values
        pl.BlockSpec((1, bo, kb), lambda i, j, b: (b, j, 0)),    # local idx
    ]
    if tb.quant != "none":
        in_specs.append(pl.BlockSpec((1, bo, 1), lambda i, j, b: (b, j, 0)))
    return pl.pallas_call(
        functools.partial(_kernel, quant=tb.quant, nb_axis=2),
        **named("balanced_spmm_wide"),
        grid=(m // bm, o // bo, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bo), lambda i, j, b: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, o), jnp.float32),
        interpret=interpret_mode(interpret),
    )(x, *_weight_operands(tb.values, tb.indices, tb.scales, tb.quant))


def tiled_balanced_spmm_skinny_pallas(x: Array, tb: TiledBalanced, *,
                                      bo: int = 128,
                                      interpret: bool | None = None
                                      ) -> Array:
    """Decode-specialized variant of `tiled_balanced_spmm_pallas` for skinny
    M (a decode step's whole batch, padded to the 8-row sublane).  Grid
    ``(O/bo, NB)`` — the whole activation block stays resident, so the
    skinny M never pays a full [128, bn] x-tile load per step.
    """
    m, n = x.shape
    nb, o, kb = tb.indices.shape
    bn = tb.bn
    assert n == nb * bn and o % bo == 0 and m <= 8, (x.shape, tb.indices.shape, bo, bn)
    kbp = _packed_kb(kb, tb.quant)
    in_specs = [
        pl.BlockSpec((m, bn), lambda j, b: (0, b)),              # x col-block
        pl.BlockSpec((1, bo, kbp), lambda j, b: (b, j, 0)),      # values
        pl.BlockSpec((1, bo, kb), lambda j, b: (b, j, 0)),       # local idx
    ]
    if tb.quant != "none":
        in_specs.append(pl.BlockSpec((1, bo, 1), lambda j, b: (b, j, 0)))
    return pl.pallas_call(
        functools.partial(_kernel, quant=tb.quant, nb_axis=1),
        **named("balanced_spmm_skinny"),
        grid=(o // bo, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, bo), lambda j, b: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, o), jnp.float32),
        interpret=interpret_mode(interpret),
    )(x, *_weight_operands(tb.values, tb.indices, tb.scales, tb.quant))


def tiled_balanced_spmm_batched_pallas(x: Array, values: Array,
                                       indices: Array, *, bn: int,
                                       bm: int = 128, bo: int = 128,
                                       scales: Array | None = None,
                                       quant: str = "none",
                                       interpret: bool | None = None
                                       ) -> Array:
    """Fused batched (per-expert) tiled matmul: one grid over all experts.

    x: [E, M, NB*bn]; values/indices: [E, NB, O, KB] with M % bm == 0 and
    O % bo == 0 (int4 values [E, NB, O, KB/2]; ``scales`` [E, NB, O] when
    quantized).  Grid ``(E, M/bm, O/bo, NB)`` replaces a per-expert
    `lax.scan` dispatch (one kernel launch and one trace for the whole MoE
    layer).  Returns f32 [E, M, O].
    """
    e, m, n = x.shape
    _, nb, o, kb = indices.shape
    assert n == nb * bn and m % bm == 0 and o % bo == 0, (x.shape, indices.shape, bm, bo, bn)
    kbp = _packed_kb(kb, quant)
    in_specs = [
        pl.BlockSpec((1, bm, bn), lambda g, i, j, b: (g, i, b)),
        pl.BlockSpec((1, 1, bo, kbp), lambda g, i, j, b: (g, b, j, 0)),
        pl.BlockSpec((1, 1, bo, kb), lambda g, i, j, b: (g, b, j, 0)),
    ]
    if quant != "none":
        in_specs.append(
            pl.BlockSpec((1, 1, bo, 1), lambda g, i, j, b: (g, b, j, 0)))
    return pl.pallas_call(
        functools.partial(_kernel, quant=quant, nb_axis=3),
        **named("balanced_spmm_batched"),
        grid=(e, m // bm, o // bo, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bo), lambda g, i, j, b: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, o), jnp.float32),
        interpret=interpret_mode(interpret),
    )(x, *_weight_operands(values, indices, scales, quant))
