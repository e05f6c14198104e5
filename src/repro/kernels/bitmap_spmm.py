"""Pallas TPU kernel: bitmap-compressed sparse x dense matmul (y = x @ W.T).

This is the paper's bitmap decompression (§III-C, Fig.10) rethought for the
TPU memory hierarchy: instead of per-element coordinate decode + scalar Psum
scatter (an RTL mechanism with no VPU analogue), each grid step decodes one
``[bo, bn]`` *tile* of W from ``(bitmap, packed NZEs, row-block offsets)``
into a dense VMEM tile via an in-register prefix-sum gather, then feeds the
MXU a dense ``[bm, bn] x [bn, bo]`` matmul.  Zeros are skipped at HBM/DRAM
level (only packed NZEs + 1-bit map are stored/moved), compute is skipped at
tile granularity by the caller (all-zero tiles can be pruned from the grid).

The decode:  pos[r, c] = offsets[r, nb] + exclusive_prefix(bitmap[r, :c])
             w_tile[r, c] = bitmap[r, c] ? packed[r, pos[r, c]] : 0

With Sense's *balanced* pruning K is identical across rows, so ``packed`` is
a rectangle with zero padding waste — the co-design point again.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .balanced_spmm import interpret_mode

Array = jax.Array


def _kernel(x_ref, bmp_ref, pak_ref, off_ref, o_ref):
    """Grid (i: M, j: O, nb: N). Accumulate x_tile @ decode(W_tile).T."""
    nb = pl.program_id(2)

    @pl.when(nb == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                                   # [bm, bn]
    bitmap = bmp_ref[...]                            # [bo, bn] int8
    packed = pak_ref[...]                            # [bo, K]
    off = off_ref[...]                               # [bo, 1] int32
    bits = (bitmap != 0)
    incl = jnp.cumsum(bits.astype(jnp.int32), axis=1)
    pos = off + incl - 1                             # inclusive -> NZE index
    pos = jnp.clip(pos, 0, packed.shape[1] - 1)
    w_tile = jnp.where(bits, jnp.take_along_axis(packed, pos, axis=1), 0)
    acc = jnp.dot(x, w_tile.T, preferred_element_type=jnp.float32)
    o_ref[...] += acc.astype(o_ref.dtype)


def bitmap_spmm_pallas(x: Array, bitmap: Array, packed: Array,
                       offsets: Array, *, bm: int = 128, bo: int = 128,
                       bn: int = 128, interpret: bool | None = None) -> Array:
    """Raw pallas_call; tile-aligned shapes (see ops.py for padding).

    x: [M, N]; bitmap: [O, N] int8; packed: [O, K] NZE rows (raster order);
    offsets: [O, N/bn] int32 — NZE count of row o before column-block nb.
    """
    m, n = x.shape
    o, n2 = bitmap.shape
    assert n == n2 and m % bm == 0 and o % bo == 0 and n % bn == 0
    k = packed.shape[1]
    grid = (m // bm, o // bo, n // bn)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, nb: (i, nb)),
            pl.BlockSpec((bo, bn), lambda i, j, nb: (j, nb)),
            pl.BlockSpec((bo, k), lambda i, j, nb: (j, 0)),
            pl.BlockSpec((bo, 1), lambda i, j, nb: (j, nb)),
        ],
        out_specs=pl.BlockSpec((bm, bo), lambda i, j, nb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, o), jnp.float32),
        interpret=interpret_mode(interpret),
    )(x, bitmap, packed, offsets)


def bitmap_encode(w: Array, bn: int,
                  k: int | None = None) -> tuple[Array, Array, Array]:
    """Encode a dense [O, N] matrix into (bitmap int8, packed [O, Kmax],
    offsets [O, N/bn] int32).  Kmax = max row NZE count (balanced pruning
    makes every row hit Kmax exactly — zero padding waste).

    The packed width must be static.  Pass ``k`` to keep the encoder
    traceable/jittable (e.g. ``k = keep_count(n, sparsity)`` from the
    pruning schedule); with ``k=None`` the width is measured on the host
    via NumPy — no device round-trip, but ``w`` must be concrete.
    """
    w = jnp.asarray(w)
    o, n = w.shape
    assert n % bn == 0, (n, bn)
    bits = (w != 0)
    counts = jnp.sum(bits, axis=1)
    if k is None:
        if isinstance(w, jax.core.Tracer):
            raise ValueError("bitmap_encode under tracing needs a static k "
                             "(the max row NZE count)")
        kmax = int(np.count_nonzero(np.asarray(w), axis=1).max())
    else:
        kmax = int(k)
        if not isinstance(w, jax.core.Tracer):
            true_max = int(np.count_nonzero(np.asarray(w), axis=1).max())
            if true_max > kmax:
                raise ValueError(
                    f"static k={kmax} < max row NZE count {true_max}: "
                    "packed would silently truncate nonzeros")
    kmax = max(kmax, 1)
    # pack nonzeros to the front of each row (stable order)
    order = jnp.argsort(~bits, axis=1, stable=True)
    packed_full = jnp.take_along_axis(w, order, axis=1)
    packed = packed_full[:, :kmax]
    valid = jnp.arange(kmax)[None, :] < counts[:, None]
    packed = jnp.where(valid, packed, 0)
    # offsets: NZEs before each column block
    per_block = bits.reshape(o, n // bn, bn).sum(axis=2)
    offsets = jnp.concatenate(
        [jnp.zeros((o, 1), jnp.int32),
         jnp.cumsum(per_block, axis=1).astype(jnp.int32)[:, :-1]], axis=1)
    return bits.astype(jnp.int8), packed, offsets
