"""Tile-local balanced-sparse weight format (DESIGN.md §3.2).

The flat Sense format ``(values[O, K], indices[O, K])`` stores each output
row's K nonzeros with *global* input indices.  That forces the kernel to
gather across the whole input dimension per output tile — a rank-3
``[bm, bo, bk]`` buffer and a VPU-style einsum, no MXU.

The tile-local format re-partitions each row's nonzeros by ``bn``-wide
column blocks of the input dimension, exactly the blocks a grid-``(M, O,
N/bn)`` kernel walks:

* ``values[NB, O, KB]``  — nonzero values, zero-padded per block
* ``indices[NB, O, KB]`` — *block-local* column indices in ``[0, bn)``
* ``counts[NB, O]``      — true nonzeros per (block, row)

``KB`` is the per-block capacity (max count, rounded up for sublane
alignment).  The block axis leads so that one grid step's weight block
``[bo, KB]`` is a contiguous tile whose last two dims are (rows, slots) —
the layout Mosaic can DMA; a ``[O, NB, KB]`` order would need a
``(bo, 1, KB)`` block, which the TPU compiler refuses.

This is where the model/hardware co-design pays off twice:
Sense's balanced pruning keeps per-row totals equal (K identical), and for
magnitude pruning of i.i.d. weights the split across ``NB`` blocks is
hypergeometric, so per-block counts concentrate near ``K/NB`` — ``KB`` sits
close to the mean and the zero padding stays small (`block_imbalance`
measures the slack).  The kernel decodes one ``[bo, bn]`` dense tile
per grid step and feeds the MXU a rank-2 ``[bm, bn] x [bn, bo]`` product;
padded entries carry value 0 and index 0, so the decode needs no count
masking at runtime (``counts`` is for diagnostics and storage accounting).

**Column-combining packing** (Kung et al., arXiv 1811.04770; the SPOTS
packing move for systolic GEMM): ``KB`` is a *max* over every (row, block)
pair, so one unlucky block sets the padding for the whole matrix.
`pack_columns` computes an input-column permutation that spreads heavily
co-occurring columns across blocks, lowering that max — near-empty sparse
columns merge into denser tiles, so the same NZEs fit a smaller KB and the
VMEM freed lets `ops.choose_blocks`/autotune keep larger (bn, bo) tiles.
A packed encoding stores the permutation in ``TiledBalanced.perm``
(packed column position -> original padded column, length ``NB * bn``);
the matmul wrapper permutes ``x`` into packed space before the kernel and
the output needs no unpermutation (only input columns move).  Packing is
numerics-preserving: `tiled_to_dense` / `tiled_to_flat` invert it exactly.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# Sublane-friendly rounding for the KB axis (f32 min tile is 8 x 128).
_KB_ROUND = 8

# Per-block symmetric quantization grids (ggml-style block quant: one f32
# absmax scale per bn-block, narrow two's-complement values).  int4 packs
# two nibbles per byte along KB — _KB_ROUND keeps KB even, so a block's
# packed byte row is exactly KB/2 wide.
QUANT_QMAX = {"int8": 127, "int4": 7}
QUANT_MODES = ("none",) + tuple(QUANT_QMAX)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass
class TiledBalanced:
    """Block-partitioned balanced-sparse matrix (see module docstring)."""
    values: Array    # [NB, O, KB]
    indices: Array   # [NB, O, KB] int32, block-local in [0, bn)
    counts: Array    # [NB, O] int32, true NZE per block
    n_in: int        # dense input dimension (NB * bn >= n_in)
    bn: int          # column-block width
    # Optional column-combining permutation (see module docstring):
    # perm[p] = original padded column feeding packed position p, length
    # NB * bn.  Stacked plans broadcast it over lead axes ([L, NB*bn],
    # [L, E, NB*bn]) so per-layer pytree slicing stays shape-consistent.
    perm: Array | None = None
    # Block quantization (QUANT_MODES): when quant != "none", ``values``
    # holds the narrow encoding (int8 [NB, O, KB]; int4 packed uint8
    # [NB, O, KB/2], two nibbles per byte) and ``scales`` the per-block f32
    # absmax/qmax factors, shaped like ``counts``.  ``indices`` always keeps
    # the *logical* [NB, O, KB] shape, so geometry reads from it below.
    # Stacked plans prepend lead axes ([L, ...], [L, E, ...]) to every leaf.
    scales: Array | None = None
    quant: str = "none"

    @property
    def n_out(self) -> int:
        return self.indices.shape[-2]

    @property
    def nb(self) -> int:
        return self.indices.shape[-3]

    @property
    def kb(self) -> int:
        return self.indices.shape[-1]

    @property
    def k(self) -> int:
        """Total nonzeros per row (the flat format's K)."""
        return int(np.asarray(jnp.sum(self.counts[..., 0].reshape(
            -1, self.nb)[0])))

    def to_dense(self) -> Array:
        return tiled_to_dense(self)

    def tree_flatten(self):
        # perm/scales ride as children (leaves), not aux data: hashing a
        # few thousand ints per treedef comparison would tax every jitted
        # dispatch.  A None perm/scales stays None through
        # flatten/unflatten (None is an empty subtree, so unquantized
        # unpacked encodings keep their pre-quant treedef).
        return ((self.values, self.indices, self.counts, self.perm,
                 self.scales),
                (self.n_in, self.bn, self.quant))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], aux[0], aux[1],
                   perm=children[3], scales=children[4], quant=aux[2])


jax.tree_util.register_pytree_node(
    TiledBalanced, TiledBalanced.tree_flatten, TiledBalanced.tree_unflatten)


def _block_counts(idx: np.ndarray, nb: int, bn: int) -> np.ndarray:
    """Per-(row, block) entry counts ``[O, NB]`` of a flat host index array."""
    o = idx.shape[0]
    flat = (np.arange(o, dtype=np.int64)[:, None] * nb + idx // bn).ravel()
    return np.bincount(flat, minlength=o * nb).reshape(o, nb)


def max_block_count(indices, n_in: int, bn: int) -> int:
    """Concrete KB for a flat index array: max per-(row, block) entry count,
    rounded up to a sublane multiple.  Host-side (requires concrete data)."""
    counts = _block_counts(np.asarray(indices), -(-n_in // bn), bn)
    return max(_KB_ROUND, _round_up(int(counts.max(initial=0)), _KB_ROUND))


def pack_columns(pattern, bn: int) -> np.ndarray:
    """Column-combining permutation for a sparsity pattern (offline pass).

    Greedy first-fit-decreasing balancer: input columns, heaviest first,
    are assigned to the ``bn``-slot block whose max per-(row, block) count
    grows the least (ties -> emptiest block), so columns whose nonzeros
    co-occur on the same output rows land in *different* blocks.  Leftover
    slots are filled from the padding pool ``[n, NB*bn)``.

    Returns ``perm`` — int32 ``[NB*bn]``, a permutation of the padded
    column space with ``perm[p]`` = original padded column at packed
    position ``p``.  Apply to inputs as ``x_packed = x_padded[:, perm]``
    and remap flat indices as ``invert_perm(perm)[idx]``.  Host-side and
    deterministic (a plan-build step, not a hot-path op).
    """
    mask = np.asarray(pattern) != 0
    o, n = mask.shape
    nb = -(-n // bn)
    npad = nb * bn
    if nb <= 1:
        return np.arange(npad, dtype=np.int32)
    order = np.argsort(-mask.sum(axis=0), kind="stable")
    # Adding column c to block b raises the block's max row count by one
    # exactly when c hits a row already at that max, so the greedy only
    # needs each block's max (``level``) and the set of rows at it
    # (``at_max``), kept as row bitsets: one AND per block per column
    # instead of a pass over every (block, row) count.
    cols_t = np.ascontiguousarray(mask.T)                   # [n, o]

    def bits(rows_bool):                    # [..., o] bool -> [..., w] u64
        b8 = np.packbits(rows_bool, axis=-1)
        pad = -b8.shape[-1] % 8
        if pad:
            b8 = np.concatenate(
                [b8, np.zeros((*b8.shape[:-1], pad), np.uint8)], axis=-1)
        return b8.view(np.uint64)

    col_bits = bits(cols_t)                                 # [n, w]
    block_rows = np.zeros((nb, o), np.int32)   # per-block per-row NZE so far
    level = np.zeros(nb, np.int64)             # per-block max row count
    at_max = np.repeat(bits(np.ones((1, o), bool)), nb, axis=0)
    fill = np.zeros(nb, np.int64)              # slots used per block
    full = np.iinfo(np.int64).max
    slots: list[list[int]] = [[] for _ in range(nb)]
    for c in order:
        hits = (at_max & col_bits[c]).any(axis=1)
        newmax = np.where(fill < bn, level + hits, full)
        b = int(np.lexsort((fill, newmax))[0])
        slots[b].append(int(c))
        col = cols_t[c]
        block_rows[b] += col
        fill[b] += 1
        if hits[b]:
            level[b] += 1
            at_max[b] = bits(block_rows[b] == level[b])
        else:
            at_max[b] |= bits(col & (block_rows[b] == level[b]))
    pad_pool = iter(range(n, npad))
    perm = np.empty(npad, np.int32)
    for b, s in enumerate(slots):
        s = s + [next(pad_pool) for _ in range(bn - len(s))]
        perm[b * bn:(b + 1) * bn] = s
    return perm


def invert_perm(perm) -> np.ndarray:
    """Inverse permutation: ``inv[original column] = packed position``."""
    p = np.asarray(perm)
    inv = np.empty_like(p)
    inv[p] = np.arange(p.shape[0], dtype=p.dtype)
    return inv


def _leaf_perm(perm) -> np.ndarray:
    """Collapse a lead-broadcast perm leaf ([..., NB*bn]) to one row."""
    p = np.asarray(perm)
    return p.reshape(-1, p.shape[-1])[0]


def encode_tiled(values, indices, n_in: int, *, bn: int,
                 kb: int | None = None) -> TiledBalanced:
    """Flat balanced ``(values[O,K], indices[O,K])`` -> `TiledBalanced`.

    Works both eagerly and under tracing: the block structure (slots,
    counts, local indices) is derived from ``indices`` on the host whenever
    they are concrete — the common case, since the sparsity *pattern* is
    fixed at prune time even while values are being trained — and falls back
    to a fully traceable jnp path otherwise.  ``kb`` must be static; when
    not given it is measured from concrete indices, or bounded by
    ``min(K, bn)`` (the worst case a single block can hold) under tracing.
    """
    o, k = values.shape
    nb = -(-n_in // bn)
    idx_concrete = not isinstance(indices, jax.core.Tracer)
    if kb is None:
        if idx_concrete:
            kb = max_block_count(indices, n_in, bn)
        else:
            kb = max(_KB_ROUND, _round_up(min(k, bn), _KB_ROUND))

    if idx_concrete:
        idx = np.asarray(indices)
        # ascending column order (indices from to_balanced_sparse already
        # are; this defends against unsorted callers): it groups each row
        # by block and keeps every block's live indices sorted.
        order = None
        if k > 1 and not (idx[:, 1:] > idx[:, :-1]).all():
            order = np.argsort(idx, axis=1, kind="stable")
            idx = np.take_along_axis(idx, order, axis=1)
        counts = _block_counts(idx, nb, bn).astype(np.int32)   # [O, NB]
        if int(counts.max(initial=0)) > kb:
            raise ValueError(f"kb={kb} < max per-block count {counts.max()}")
        # every tile slot gathers its entry: slot s of block b holds the
        # row's (off[b] + s)-th sorted entry, or position k (a zero pad)
        off = (np.cumsum(counts, axis=1) - counts).astype(np.int32)
        slots = np.arange(kb, dtype=np.int32)
        src = np.where(slots < counts[:, :, None], off[:, :, None] + slots,
                       np.int32(k)).reshape(o, nb * kb)      # [O, NB*KB]
        local = np.pad(idx % bn, ((0, 0), (0, 1))).astype(np.int32)
        ti = np.ascontiguousarray(np.take_along_axis(local, src, axis=1)
                                  .reshape(o, nb, kb).transpose(1, 0, 2))
        if order is not None:           # sorted position -> input position
            src = np.take_along_axis(np.pad(order, ((0, 0), (0, 1)),
                                            constant_values=k), src, axis=1)
        src = src.reshape(o, nb, kb)
        vpad = jnp.pad(jnp.asarray(values), ((0, 0), (0, 1)))
        tv = jnp.take_along_axis(vpad, jnp.asarray(src.reshape(o, -1)),
                                 axis=1).reshape(o, nb, kb)
        return TiledBalanced(jnp.transpose(tv, (1, 0, 2)), jnp.asarray(ti),
                             jnp.asarray(counts.T), n_in=n_in, bn=bn)

    # Fully traced path (indices themselves are being transformed).
    jrows = jnp.arange(o)[:, None]
    order = jnp.argsort(indices, axis=1, stable=True)
    idx_s = jnp.take_along_axis(indices, order, axis=1)
    val_s = jnp.take_along_axis(values, order, axis=1)
    blk = idx_s // bn
    counts = jnp.sum(blk[:, :, None] == jnp.arange(nb)[None, None, :],
                     axis=1).astype(jnp.int32)
    off = jnp.cumsum(counts, axis=1) - counts
    slot = jnp.arange(k)[None, :] - jnp.take_along_axis(off, blk, axis=1)
    tv = jnp.zeros((nb, o, kb), values.dtype).at[blk, jrows, slot].set(
        val_s, mode="drop")
    ti = jnp.zeros((nb, o, kb), jnp.int32).at[blk, jrows, slot].set(
        (idx_s % bn).astype(jnp.int32), mode="drop")
    return TiledBalanced(tv, ti, counts.T, n_in=n_in, bn=bn)


def pack_int4(q: Array) -> Array:
    """Pack int values in [-8, 7] two nibbles per byte along the last axis
    (low nibble = slot 2i, high nibble = slot 2i+1).  Odd-length axes get
    one zero pad slot first — the unpacked tail nibble decodes to 0, the
    same structural zero a padded tile slot carries."""
    kb = q.shape[-1]
    if kb % 2:
        q = jnp.concatenate(
            [q, jnp.zeros((*q.shape[:-1], 1), q.dtype)], axis=-1)
        kb += 1
    u = (q.astype(jnp.int32) & 0xF).astype(jnp.uint8)
    u = u.reshape(*q.shape[:-1], kb // 2, 2)
    return u[..., 0] | (u[..., 1] << 4)


def unpack_int4(packed: Array, kb: int) -> Array:
    """Inverse of `pack_int4`: uint8 ``[..., KB/2]`` -> int8 ``[..., kb]``
    two's-complement values in [-8, 7] (``(n ^ 8) - 8`` sign-extends the
    nibble)."""
    lo = packed & 0xF
    hi = packed >> 4
    q = jnp.stack([lo, hi], axis=-1).reshape(
        *packed.shape[:-1], packed.shape[-1] * 2).astype(jnp.int8)
    return ((q ^ 8) - 8)[..., :kb]


def quantize_tiled(tb: TiledBalanced, quant: str) -> TiledBalanced:
    """Per-block symmetric quantization of a `TiledBalanced` encoding.

    Each (row, block) gets one f32 scale ``absmax / qmax`` (shape ==
    ``counts``); values become ``round(v / scale)`` clipped to the grid —
    int8 one byte per slot, int4 two nibbles per byte along KB.  All-zero
    blocks encode scale 0 with every slot 0 (the encoder never emits a
    nonzero q against a zero scale — `engine.guard` checks that invariant).
    Reconstruction error is bounded by ``scale / 2`` per element.
    Geometry (indices/counts/perm) is untouched; works on stacked leaves
    (lead axes broadcast through).
    """
    if quant == "none":
        return tb
    if quant not in QUANT_QMAX:
        raise ValueError(f"quant must be one of {QUANT_MODES}, got {quant!r}")
    if tb.quant != "none":
        raise ValueError(f"encoding is already {tb.quant}-quantized")
    qmax = QUANT_QMAX[quant]
    vals = tb.values.astype(jnp.float32)
    scales = jnp.max(jnp.abs(vals), axis=-1) / qmax          # counts-shaped
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(jnp.round(vals / safe[..., None]), -qmax, qmax)
    q = jnp.where(scales[..., None] > 0, q, 0.0)
    qv = q.astype(jnp.int8) if quant == "int8" \
        else pack_int4(q.astype(jnp.int8))
    return TiledBalanced(qv, tb.indices, tb.counts, n_in=tb.n_in, bn=tb.bn,
                         perm=tb.perm, scales=scales, quant=quant)


def dequantize_values(values: Array, scales: Array, quant: str,
                      kb: int) -> Array:
    """Narrow block-quant values -> f32, ``q * scale`` per block.  ``kb`` is
    the logical slot count (needed to drop int4's odd-tail pad nibble).
    This exact expression is what the kernels inline in VMEM before the
    MXU dot — keep them in lockstep."""
    if quant == "none":
        return values
    q = unpack_int4(values, kb) if quant == "int4" else values
    return q.astype(jnp.float32) * scales[..., None]


def dequantize_tiled(tb: TiledBalanced) -> TiledBalanced:
    """Quantized encoding -> f32 `TiledBalanced` (quant == "none"), the
    reference the kernels' in-VMEM dequant must match bit-for-bit."""
    if tb.quant == "none":
        return tb
    vals = dequantize_values(tb.values, tb.scales, tb.quant, tb.kb)
    return TiledBalanced(vals, tb.indices, tb.counts, n_in=tb.n_in,
                         bn=tb.bn, perm=tb.perm)


def _leaf_perm_jnp(perm) -> Array:
    """`_leaf_perm` for traced/device perms (collapse lead-broadcast rows)."""
    return perm.reshape(-1, perm.shape[-1])[0] if perm.ndim > 1 else perm


def tiled_to_dense(tb: TiledBalanced) -> Array:
    """Densify one (unstacked) encoding to ``[O, n_in]`` — the reference
    and inverse of `encode_tiled`, dequantized first for quantized tiles.

    The kernel's own decode, in XLA: a loop over the KB slots adds each
    live slot's value where its block-local index matches the column.  No
    scatter and no search, so it lowers to plain elementwise passes on CPU
    and TPU alike.  Packed encodings come back in original column order
    (``perm`` maps packed position -> column).
    """
    nb, o, kb = tb.indices.shape
    bn = tb.bn
    vals = dequantize_values(tb.values, tb.scales, tb.quant, kb)
    live = jnp.arange(kb, dtype=tb.counts.dtype) < tb.counts[..., None]
    vals = jnp.where(live, vals, jnp.zeros((), vals.dtype))
    cols = jnp.arange(bn, dtype=tb.indices.dtype)

    def slot(k, acc):
        idx = jax.lax.dynamic_index_in_dim(tb.indices, k, axis=2)
        val = jax.lax.dynamic_index_in_dim(vals, k, axis=2)
        return acc + jnp.where(idx == cols, val, jnp.zeros((), vals.dtype))
    dense = jax.lax.fori_loop(0, kb, slot,
                              jnp.zeros((nb, o, bn), vals.dtype))
    dense = jnp.transpose(dense, (1, 0, 2)).reshape(o, nb * bn)
    if tb.perm is not None:
        inv = jnp.argsort(_leaf_perm_jnp(tb.perm).astype(jnp.int32))
        dense = jnp.take(dense, inv, axis=1)
    return dense[:, :tb.n_in]


def tiled_to_flat(tb: TiledBalanced):
    """`TiledBalanced` -> flat balanced ``(values[O, K], indices[O, K])``
    with global ascending column indices — the inverse of `encode_tiled`
    for well-formed encodings (every row holds the same total count K).

    Host-side (requires concrete indices/counts): this is the degradation
    ladder's pallas -> xla demotion path, not a hot-path op.  Raises
    ``ValueError`` when the encoding violates the balance invariant (rows
    with unequal totals have no flat [O, K] representation).  Quantized
    encodings are dequantized first — demotion leaves the quant domain
    (the flat consumers carry no scales).
    """
    tb = dequantize_tiled(tb)
    idx = np.asarray(tb.indices).transpose(1, 0, 2)            # [O, NB, KB]
    cnt = np.asarray(tb.counts).T
    o, nb, kb = idx.shape
    totals = cnt.sum(axis=1)
    if o and not (totals == totals[0]).all():
        raise ValueError("unbalanced encoding: per-row totals "
                         f"range {totals.min()}..{totals.max()} — no flat "
                         "[O, K] representation")
    k = int(totals[0]) if o else 0
    valid = np.arange(kb)[None, None, :] < cnt[:, :, None]     # [O, NB, KB]
    gcols = np.arange(nb)[None, :, None] * tb.bn + idx         # global cols
    if tb.perm is not None:
        # unpermute packed positions back to original padded columns
        gcols = _leaf_perm(tb.perm)[gcols]
    # valid slots first, preserving (block, slot) order — which is ascending
    # column order for encode_tiled output
    order = np.argsort(~valid.reshape(o, -1), axis=1, kind="stable")[:, :k]
    flat_idx = np.take_along_axis(gcols.reshape(o, -1), order,
                                  axis=1).astype(np.int32)
    flat_vals = jnp.take_along_axis(
        jnp.transpose(tb.values, (1, 0, 2)).reshape(o, -1),
        jnp.asarray(order), axis=1)
    if tb.perm is not None:
        # packed block order is not ascending in original columns; the flat
        # consumers (searchsorted densify, gather paths) require ascending
        # rows — restore the invariant
        asc = np.argsort(flat_idx, axis=1, kind="stable")
        flat_idx = np.take_along_axis(flat_idx, asc, axis=1)
        flat_vals = jnp.take_along_axis(flat_vals, jnp.asarray(asc), axis=1)
    return flat_vals, jnp.asarray(flat_idx)


def block_imbalance(tb: TiledBalanced) -> float:
    """KB padding slack: capacity / mean block count (1.0 == no waste).

    Balanced pruning keeps this near 1 + O(sqrt(NB/K)); large values mean
    the block width ``bn`` is too fine for the row's nonzero budget.
    """
    mean = float(jnp.mean(tb.counts.astype(jnp.float32)))
    return tb.kb / max(mean, 1e-9)


def tiled_storage_bits(tb: TiledBalanced, *, elem_bits: int = 16,
                       count_bits: int = 16) -> int:
    """DRAM footprint of the tiled format (values + local indices + counts).

    Block-local indices need only ``ceil(log2 bn)`` bits (vs ``log2 N`` for
    flat global indices) — the format's storage edge at equal padding.
    Bit layout matches `core.compression.balanced_tiled_bits` (the shape-
    level model); this measures a concrete weight.  Quantized encodings
    count their narrow element width plus one f32 scale per block.
    """
    idx_bits = max(1, (tb.bn - 1).bit_length())
    n_slots = tb.n_out * tb.nb * tb.kb
    scale_bits = 0
    if tb.quant != "none":
        elem_bits = {"int8": 8, "int4": 4}[tb.quant]
        scale_bits = tb.n_out * tb.nb * 32
    return n_slots * (elem_bits + idx_bits) \
        + tb.n_out * tb.nb * count_bits + scale_bits
