"""Pallas-backed sparse convolution: chunked im2col + balanced-sparse GEMM.

The paper's CONV processing keeps the whole kernel compressed and skips
zero products (§III-C).  The TPU-native form: lower the convolution to a
GEMM over extracted patches (XLA's `conv_general_dilated_patches`, itself a
data movement the TPU does well) and run the contraction through the
`balanced_spmm` Pallas kernel, whose K-per-row invariant comes from the
load-balancing pruning of each Co kernel.

The patch matrix is ``B*Ho*Wo x Ci*Hk*Wk`` — at VGG-16 scale hundreds of
MiB, far beyond VMEM and a needless HBM round-trip.  `sparse_conv2d`
therefore streams it in output-row chunks: the input is padded once, then
each chunk extracts patches for a slab of output rows and feeds them
straight through the GEMM, so only ``B * rows_per_chunk * Wo`` patch rows
are ever materialized (DESIGN.md §3.4).

The patch matrix's column order is (Ci, Hk, Wk) raster order, matching the
flattening used by `core.pruning.balanced_prune_conv`, so pruned-conv
weights convert directly with `to_balanced_sparse(w.reshape(Co, -1))`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

# Patch-chunk budget (elements): bounds the im2col slab at ~8 MiB f32.
_CHUNK_ELEMS = 1 << 21


def _resolve_padding(h: int, w: int, hk: int, wk: int, stride: int,
                     padding) -> tuple[tuple[int, int], tuple[int, int]]:
    """Explicit (lo, hi) pads per spatial dim, matching XLA's SAME/VALID."""
    if isinstance(padding, int):
        return (padding, padding), (padding, padding)
    if padding == "VALID":
        return (0, 0), (0, 0)
    if padding == "SAME":
        def same(dim, k):
            out = -(-dim // stride)
            total = max((out - 1) * stride + k - dim, 0)
            return total // 2, total - total // 2
        return same(h, hk), same(w, wk)
    raise ValueError(f"unsupported padding {padding!r}")


def im2col(x: Array, hk: int, wk: int, *, stride: int = 1,
           padding: str | int = "SAME") -> Array:
    """x [B,H,W,Ci] -> patches [B, Ho, Wo, Ci*Hk*Wk] (Ci-major column order)."""
    if isinstance(padding, int):
        pad = [(padding, padding), (padding, padding)]
    else:
        pad = padding
    patches = jax.lax.conv_general_dilated_patches(
        x, filter_shape=(hk, wk), window_strides=(stride, stride),
        padding=pad, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return patches  # feature dim is Ci*Hk*Wk, Ci-major


def sparse_conv2d(x: Array, values: Array, indices: Array, n_in: int, *,
                  hk: int, wk: int, stride: int = 1,
                  padding: str | int = "SAME",
                  matmul_fn=None, chunk_elems: int = _CHUNK_ELEMS) -> Array:
    """Balanced-sparse conv: x [B,H,W,Ci], kernel (values[Co,K], indices) over
    the flattened (Ci*Hk*Wk) patch axis.  ``matmul_fn`` defaults to the
    Pallas `balanced_spmm` via ops.py (injected to avoid an import cycle).

    The im2col GEMM is streamed in output-row chunks of at most
    ``chunk_elems`` patch elements each (see module docstring); pass a huge
    ``chunk_elems`` to force the old single-piece behavior.
    """
    if matmul_fn is None:
        from . import ops
        matmul_fn = ops.balanced_spmm
    b, h, w, ci = x.shape
    feat = ci * hk * wk
    assert feat == n_in, (feat, n_in)
    ph, pw = _resolve_padding(h, w, hk, wk, stride, padding)
    xp = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))
    hp, wp = xp.shape[1], xp.shape[2]
    ho = (hp - hk) // stride + 1
    wo = (wp - wk) // stride + 1

    rows_per_chunk = max(1, chunk_elems // max(b * wo * feat, 1))
    if rows_per_chunk >= ho:
        patches = im2col(xp, hk, wk, stride=stride, padding="VALID")
        y = matmul_fn(patches.reshape(b * ho * wo, feat), values, indices,
                      n_in=n_in)
        return y.reshape(b, ho, wo, -1)

    outs = []
    for r0 in range(0, ho, rows_per_chunk):
        r1 = min(r0 + rows_per_chunk, ho)
        slab = jax.lax.slice_in_dim(xp, r0 * stride,
                                    (r1 - 1) * stride + hk, axis=1)
        patches = im2col(slab, hk, wk, stride=stride, padding="VALID")
        y = matmul_fn(patches.reshape(b * (r1 - r0) * wo, feat), values,
                      indices, n_in=n_in)
        outs.append(y.reshape(b, r1 - r0, wo, -1))
    return jnp.concatenate(outs, axis=1)
