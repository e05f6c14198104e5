"""Jit'd public wrappers around the Pallas kernels.

Responsibilities: tile-alignment padding, the *static* block-size model
(`choose_blocks`: pick bm/bo/bn from shapes and a VMEM budget instead of
hard-coded 128s — the measured sweep-and-cache layer on top lives in
`kernels/autotune.py`), weight encoding into the tile-local balanced format
with a per-weight-id cache, a differentiable path (Pallas forward + jnp
backward via custom_vjp), and XLA fallbacks:

* ``impl="pallas"``     — tile-local decode-and-matmul kernel (MXU-native;
                          interpreted on a CPU backend).  Skinny M (<= `SKINNY_M`,
                          the decode-step shape) dispatches the decode-
                          specialized kernel variant (grid without an M
                          axis, bm pinned to the padded decode batch).
* ``impl="xla"``        — same math without pallas_call: densify the
                          balanced weights + one rank-2 dot.  The densify is
                          gather-only (per-column `searchsorted` into the
                          ascending row indices) — no scatter, so it
                          vectorizes/shards where the scatter formulation
                          serializes.  Skinny M takes the gather+einsum
                          formulation instead (the [M, O, K] buffer is tiny
                          at decode shapes and skips the O*N densify per
                          step).  The production/pjit path.
* ``impl="xla_gather"`` — the seed formulation (gather + rank-3 einsum).
                          Shard-friendly (no scatter) but materializes an
                          [M, O, K] buffer; kept for sharded weights and as
                          the kernel_bench baseline.

Flat-format ``indices`` must be ascending within each row — every encoder
in this repo guarantees it (`to_balanced_sparse`, the plan builders,
`tiled_to_flat`) and the searchsorted densify relies on it.

Interpret mode is not an option: every kernel call asks the backend
(`balanced_spmm.interpret_mode`) — the Pallas interpreter on a CPU backend,
the Mosaic-compiled kernel on a TPU.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
import weakref

import jax
import jax.numpy as jnp

from . import ref
from .balanced_spmm import (tiled_balanced_spmm_batched_pallas,
                            tiled_balanced_spmm_pallas,
                            tiled_balanced_spmm_skinny_pallas)
from .bitmap_spmm import bitmap_encode, bitmap_spmm_pallas
from .tile_format import (TiledBalanced, dequantize_values, encode_tiled,
                          max_block_count, tiled_to_dense, unpack_int4)

# Stored bytes per weight slot under block quantization (None: weights share
# the activation itemsize).  Feeds the VMEM footprint model so the block
# chooser/autotuner can grow (bn, bo) when narrow tiles shrink the working
# set — the whole point of the quantized tile-local format.
QUANT_WBYTES = {"none": None, "int8": 1.0, "int4": 0.5}

Array = jax.Array

# M at or below which the decode-specialized paths dispatch.  8 is the f32
# sublane and covers every decode shape serving produces: a decode step's
# GEMM M is the batch, and the MoE dispatch capacity floor is 8
# (`transformer._moe`'s ``cap = max(8, ...)``).  Static at trace time —
# routing is free.
SKINNY_M = 8

# Widest M at which the quant fallbacks still prefer the gather+einsum
# formulation over densify+dot: the [M, O, SLOTS] gather buffer grows
# linearly in M while the densify cost is M-independent, and measured on
# CPU the crossover sits between M=32 and M=64 across N, O in [64, 1024]
# (grid in DESIGN.md §13).  Static at trace time — routing is free.
GATHER_M = 32


def bucket_m(m: int) -> int:
    """Next power of two at or above ``m`` (minimum 1): the M-bucket a
    live GEMM shape belongs to.  The serving runtime produces a spread of
    decode/chunked-prefill M values (batch buckets x chunk widths);
    bucketing collapses them so autotune cache keys, sweeps, and the
    ``block_m`` an executable bakes in are shared per bucket instead of
    fragmenting per exact M.  Idempotent on powers of two."""
    return 1 << max(int(m) - 1, 0).bit_length()


class InjectedKernelFault(RuntimeError):
    """Raised by an armed fault-injection site (`repro.testing.faults`)."""


# Kernel-dispatch fault-injection sites: impl name -> predicate(ctx) -> bool.
# Armed only by `repro.testing.faults.force_impl_failure`; empty (the
# default) costs one falsy dict check per *trace*, nothing at runtime.
_FORCED_FAULTS: dict = {}


def _fault_trip(site: str, **ctx) -> None:
    if _FORCED_FAULTS:
        pred = _FORCED_FAULTS.get(site)
        if pred is not None and pred(ctx):
            raise InjectedKernelFault(
                f"injected kernel fault at impl {site!r} ({ctx})")


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pick_block(dim: int, preferred: int) -> int:
    """Largest power-of-two block <= preferred that keeps padding sane."""
    b = preferred
    while b > 8 and dim < b // 2:
        b //= 2
    return b


# ---------------------------------------------------------------------------
# Block-size autotuner (shared by both kernels' wrappers)
# ---------------------------------------------------------------------------

# Per-core VMEM is ~16 MiB; leave room for double buffering + the compiler.
_VMEM_BUDGET = int(os.environ.get("REPRO_VMEM_BUDGET", 4 * 1024 * 1024))


@dataclasses.dataclass(frozen=True)
class BlockChoice:
    bm: int
    bo: int
    bn: int
    vmem_bytes: int     # modeled per-step footprint


def _tiled_footprint(bm: int, bo: int, bn: int, kb: int, itemsize: int,
                     w_bytes: float | None = None) -> int:
    """Per-step VMEM bytes of the tiled kernel: x tile + (vals, idx) block +
    decoded w_tile (f32) + f32 accumulator.  ``w_bytes`` overrides the
    stored bytes per value slot for quantized encodings (1.0 int8, 0.5
    int4 — see `QUANT_WBYTES`), which also adds the [bo, 1] f32 scales
    tile."""
    wb = itemsize if w_bytes is None else w_bytes
    scales = 0 if w_bytes is None else bo * 4
    return int(bm * bn * itemsize + bo * kb * (wb + 4) + scales
               + bo * bn * 4 + bm * bo * 4)


def _tiled_kb_est(n: int, k: int, bn: int) -> int:
    """Balanced-invariant KB estimate for the tiled footprint model:
    per-block counts concentrate at K * bn / N, with 50% slack (the
    encoder measures the real value).  Shared with `kernels.autotune`'s
    candidate filter so the two stay one formula."""
    return max(8, min(k, bn, _round_up(int(k * bn / max(n, 1) * 1.5), 8)))


def _bitmap_footprint(bm: int, bo: int, bn: int, k: int, itemsize: int) -> int:
    """Per-step VMEM bytes of the bitmap kernel: x tile + bitmap block (int8)
    + full packed row block + offsets column + decoded w_tile (f32) + f32
    accumulator.  ``packed`` is blocked over O only ([bo, K]), so the whole
    row's K NZEs sit in VMEM every step."""
    return (bm * bn * itemsize + bo * bn + bo * k * itemsize + bo * 4
            + bo * bn * 4 + bm * bo * 4)


@functools.lru_cache(maxsize=512)
def choose_blocks(m: int, o: int, n: int, k: int, *, itemsize: int = 4,
                  vmem_budget: int = _VMEM_BUDGET, kind: str = "tiled",
                  bn: int | None = None,
                  w_bytes: float | None = None) -> BlockChoice:
    """Pick (bm, bo, bn) for the balanced-sparse kernels — the *static
    model* (a closed-form VMEM-occupancy prior; no kernel is ever run).

    Start from MXU-shaped 128s (shrunk toward small dims so padding stays
    sane), then halve the dimension with the largest footprint share until
    the modeled per-step VMEM (double-buffered) fits the budget.

    ``kind`` selects the footprint model: "tiled" (decode-and-matmul; KB is
    estimated from the balanced invariant — per-block counts concentrate at
    K * bn / N — with 50% slack; the encoder measures the real value) or
    "bitmap" (bitmap-decode; ``k`` is the static packed width).  Passing
    ``bn`` pins the column-block width — the bitmap format bakes it into the
    encoding (offsets are per-bn-block), so only bm/bo may shrink there.

    The measured layer on top lives in `kernels.autotune`: this model is
    its fallback and candidate generator, and `autotune.resolve_blocks`
    (the entry `engine.plan` uses) returns either this choice or a cached/
    swept winner, per the caller's ``tune`` policy (DESIGN.md §10).

    ``w_bytes`` (see `QUANT_WBYTES`) narrows the modeled weight-slot width
    for block-quantized encodings, so the same budget admits 2-4x larger
    (bn, bo) tiles than the f32 model would allow.
    """
    bm = _pick_block(m, 128)
    bo = _pick_block(o, 128)
    bn_fixed = bn is not None
    if not bn_fixed:
        bn = _pick_block(n, 128)

    def kb_est(bn_):
        return _tiled_kb_est(n, k, bn_)

    def footprint(bm_, bo_, bn_):
        if kind == "bitmap":
            return _bitmap_footprint(bm_, bo_, bn_, k, itemsize)
        return _tiled_footprint(bm_, bo_, bn_, kb_est(bn_), itemsize,
                                w_bytes)

    wb = itemsize if w_bytes is None else w_bytes
    while 2 * footprint(bm, bo, bn) > vmem_budget:
        # shrink the largest contributor; keep everything >= 8
        if kind == "bitmap":
            shares = {
                "bm": bm * (bn * itemsize + bo * 4),
                "bo": bo * (bn + k * itemsize + 4 + bn * 4 + bm * 4),
            }
        else:
            shares = {
                "bm": bm * (bn * itemsize + bo * 4),
                "bo": bo * (kb_est(bn) * (wb + 4) + bn * 4 + bm * 4),
                "bn": bn * (bm * itemsize + bo * 4),
            }
        if bn_fixed:
            shares.pop("bn", None)
        for name in sorted(shares, key=shares.get, reverse=True):
            if {"bm": bm, "bo": bo, "bn": bn}[name] > 8:
                if name == "bm":
                    bm //= 2
                elif name == "bo":
                    bo //= 2
                else:
                    bn //= 2
                break
        else:
            break   # everything at the floor; accept the overshoot
    return BlockChoice(bm=bm, bo=bo, bn=bn,
                       vmem_bytes=footprint(bm, bo, bn))


def halve_blocks(c: BlockChoice, *, kb: int | None = None,
                 itemsize: int = 4) -> BlockChoice | None:
    """One VMEM-pressure retry step for the degradation ladder: halve
    bm/bo toward the 8-floor.  ``bn`` is untouched — the tile-local format
    bakes the column-block width into the encoding, so changing it would
    force a re-encode mid-recovery.  Returns None when already at the
    floor (nothing left to shrink; the ladder steps down an impl instead).
    ``kb`` (the encoding's real per-block capacity) refreshes the modeled
    footprint; without it the pre-halving model value is carried over
    (it is bookkeeping, not a dispatch parameter)."""
    if c.bm <= 8 and c.bo <= 8:
        return None
    bm = max(8, c.bm // 2)
    bo = max(8, c.bo // 2)
    vmem = _tiled_footprint(bm, bo, c.bn, kb, itemsize) if kb \
        else c.vmem_bytes
    return BlockChoice(bm=bm, bo=bo, bn=c.bn, vmem_bytes=vmem)


# ---------------------------------------------------------------------------
# Tile-format encoding cache (keyed per weight id)
# ---------------------------------------------------------------------------

# id() keys are only valid while the source arrays are alive; entries keep
# weakrefs whose finalizers evict the entry when a source array dies, so a
# training loop creating fresh weights every step cannot pin dead arrays or
# their (larger) encodings.  A bounded FIFO caps it either way.
_ENC_CACHE: "collections.OrderedDict[tuple, tuple]" = collections.OrderedDict()
_ENC_CACHE_MAX = 64
_KB_CACHE: "collections.OrderedDict[tuple, int]" = collections.OrderedDict()


def _cache_put(cache, key, entry, *source_arrays):
    def evict(_ref, cache=cache, key=key):
        cache.pop(key, None)
    refs = []
    for a in source_arrays:
        try:
            refs.append(weakref.ref(a, evict))
        except TypeError:
            return             # non-weakref-able: id() reuse undetectable,
                               # safer not to cache at all
    cache[key] = (refs, entry)
    while len(cache) > _ENC_CACHE_MAX:
        cache.popitem(last=False)


def _cache_get(cache, key):
    hit = cache.get(key)
    if hit is None:
        return None
    refs, entry = hit
    if any(r() is None for r in refs):     # stale id — source array died
        cache.pop(key, None)
        return None
    cache.move_to_end(key)
    return entry


def _encode_cached(values, indices, n_in: int, bn: int,
                   kb: int) -> TiledBalanced:
    concrete = not (isinstance(values, jax.core.Tracer)
                    or isinstance(indices, jax.core.Tracer))
    if not concrete:
        return encode_tiled(values, indices, n_in, bn=bn, kb=kb)
    key = (id(values), id(indices), n_in, bn, kb)
    tb = _cache_get(_ENC_CACHE, key)
    if tb is None:
        tb = encode_tiled(values, indices, n_in, bn=bn, kb=kb)
        _cache_put(_ENC_CACHE, key, tb, values, indices)
    return tb


def _static_kb(values, indices, n_in: int, bn: int,
               block_k: int | None) -> int:
    """Static per-block capacity: caller hint > measured (concrete indices,
    the usual case — patterns are fixed at prune time) > min(K, bn) bound.
    Measurements are cached per indices id so repeated eager calls on the
    same weights do not re-sync the index array to the host."""
    if block_k is not None:
        return max(8, _round_up(block_k, 8))
    if not isinstance(indices, jax.core.Tracer):
        key = (id(indices), n_in, bn)
        kb = _cache_get(_KB_CACHE, key)
        if kb is None:
            kb = max_block_count(indices, n_in, bn)
            _cache_put(_KB_CACHE, key, kb, indices)
        return kb
    return max(8, _round_up(min(values.shape[1], bn), 8))


# ---------------------------------------------------------------------------
# balanced_spmm: y = x @ W.T, W = (values[O,K], indices[O,K]) over N inputs
# ---------------------------------------------------------------------------

def _densify_gather(values: Array, indices: Array, n_in: int) -> Array:
    """Gather-only densify of ascending-index balanced rows -> ``[O, N]``.

    For each dense column j, binary-search the row's sorted ``indices``
    (`searchsorted`), fetch the value at the hit slot, mask the misses.
    No scatter — XLA lowers this to pure gathers, which vectorize on CPU
    and shard cleanly, where the scatter in `ref.balanced_dense`
    serializes.  Requires ascending per-row indices (module invariant).
    """
    o, k = values.shape
    cols = jnp.arange(n_in, dtype=indices.dtype)
    slot = jax.vmap(lambda row: jnp.searchsorted(row, cols))(indices)
    slot = jnp.clip(slot, 0, k - 1)
    hit = jnp.take_along_axis(indices, slot, axis=1) == cols[None, :]
    vals = jnp.take_along_axis(values, slot, axis=1)
    return jnp.where(hit, vals, jnp.zeros((), values.dtype))


def _balanced_spmm_xla(x: Array, values: Array, indices: Array,
                       n_in: int) -> Array:
    """Densify (gather-only) + rank-2 dot — MXU-eligible.  Skinny M takes
    the gather+einsum formulation instead: at decode shapes the [M, O, K]
    buffer is small and the per-step O*N densify dominates the dot.  The
    production fallback."""
    _fault_trip("xla")
    if x.shape[0] <= SKINNY_M:
        _fault_trip("xla_decode")
        return ref.balanced_spmm_gather(x, values, indices)
    w = _densify_gather(values, indices, n_in)
    return jnp.dot(x, w.T,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def _pad_rows(a: Array, extra: int, row_axis: int = -2) -> Array:
    """Zero-pad the output-row axis of a tiled leaf (``-2`` for the
    [.., NB, O, KB] values/indices, ``-1`` for [.., NB, O] counts/scales)."""
    pad = [(0, 0)] * a.ndim
    pad[row_axis] = (0, extra)
    return jnp.pad(a, pad)


def _pad_and_run_tiled(x: Array, tb: TiledBalanced, bm: int, bo: int,
                       skinny: bool = False) -> Array:
    """Pad (M, O, N) to tile multiples, run the kernel, slice back.
    ``skinny`` selects the decode-specialized kernel (M padded to the
    8-row sublane, grid without an M axis)."""
    _fault_trip("pallas", bm=bm, bo=bo, bn=tb.bn)
    if skinny:
        _fault_trip("pallas_decode", bm=bm, bo=bo, bn=tb.bn)
    m = x.shape[0]
    o = tb.n_out
    # skinny: M pads to the 8-row sublane regardless of the plan's bm (the
    # decode kernel has no M grid axis, so bm is not a dispatch parameter)
    mp = _round_up(m, 8) if skinny else _round_up(m, bm)
    op_ = _round_up(o, bo)
    xp = jnp.pad(x, ((0, mp - m), (0, tb.nb * tb.bn - x.shape[1])))
    if op_ != o:
        # zero-padded rows decode to all-zero tiles — harmless (a zero
        # scale against all-zero q slots is the valid empty-block encoding)
        tb = TiledBalanced(
            _pad_rows(tb.values, op_ - o), _pad_rows(tb.indices, op_ - o),
            _pad_rows(tb.counts, op_ - o, row_axis=-1),
            n_in=tb.n_in, bn=tb.bn,
            scales=None if tb.scales is None
            else _pad_rows(tb.scales, op_ - o, row_axis=-1),
            quant=tb.quant)
    if skinny:
        y = tiled_balanced_spmm_skinny_pallas(xp, tb, bo=bo)
    else:
        y = tiled_balanced_spmm_pallas(xp, tb, bm=bm, bo=bo)
    return y[:m, :o].astype(x.dtype)


def _balanced_spmm_pallas_tiled(x: Array, values: Array, indices: Array,
                                n_in: int, blocks: tuple) -> Array:
    bm, bo, bn, kb = blocks
    tb = _encode_cached(values, indices, n_in, bn, kb)
    return _pad_and_run_tiled(x, tb, bm, bo, skinny=x.shape[0] <= SKINNY_M)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _balanced_spmm(x, values, indices, n_in, impl, blocks):
    if impl == "pallas":
        return _balanced_spmm_pallas_tiled(x, values, indices, n_in, blocks)
    if impl == "xla_gather":
        _fault_trip("xla_gather")
        return ref.balanced_spmm_gather(x, values, indices)
    return _balanced_spmm_xla(x, values, indices, n_in)


def _balanced_fwd(x, values, indices, n_in, impl, blocks):
    y = _balanced_spmm(x, values, indices, n_in, impl, blocks)
    return y, (x, values, indices)


def _balanced_bwd(n_in, impl, blocks, res, dy):
    x, values, indices = res
    # dx = dy @ W  (gather-densified);  dvalues[o,j] = sum_m dy[m,o] x[m,idx]
    w = _densify_gather(values, indices, n_in)
    dx = jnp.dot(dy, w, preferred_element_type=jnp.float32).astype(x.dtype)
    xg = jnp.take(x, indices, axis=1)              # [M, O, K]
    dvals = jnp.einsum("mo,mok->ok", dy, xg,
                       preferred_element_type=jnp.float32).astype(values.dtype)
    return dx, dvals, None


_balanced_spmm.defvjp(_balanced_fwd, _balanced_bwd)


def balanced_spmm(x: Array, values: Array, indices: Array, *, n_in: int,
                  impl: str = "pallas", block_k: int | None = None) -> Array:
    """Differentiable balanced-sparse matmul on *flat-format* weights
    (``values[O, K]``, ``indices[O, K]`` over ``n_in`` input columns).
    ``x``: ``[..., N]`` -> ``[..., O]``.

    This is the eager/ad-hoc entry: the pallas impl encodes to the
    tile-local format behind a per-weight-id cache on every cold call.
    Plan-driven serving uses `tiled_spmm` instead (pre-encoded, no cache).

    impl: "pallas" (tiled decode-and-matmul kernel, interpret on CPU) |
    "xla" (densify + dot) | "xla_gather" (seed gather+einsum baseline).
    ``block_k`` optionally pins the static per-block capacity KB (useful
    when tracing with a known pruning pattern).
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    indices = indices.astype(jnp.int32)
    if impl == "pallas":
        c = choose_blocks(x2.shape[0], values.shape[0], n_in,
                          values.shape[1], itemsize=x.dtype.itemsize)
        kb = _static_kb(values, indices, n_in, c.bn, block_k)
        blocks = (c.bm, c.bo, c.bn, kb)
    else:
        blocks = None
    y = _balanced_spmm(x2, values, indices, n_in, impl, blocks)
    return y.reshape(*lead, values.shape[0])


# ---------------------------------------------------------------------------
# tiled_spmm: the pre-encoded (plan-driven) entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _tiled_spmm(x, values, indices, counts, n_in, bn, bm, bo, skinny):
    tb = TiledBalanced(values, indices, counts, n_in=n_in, bn=bn)
    return _pad_and_run_tiled(x, tb, bm, bo, skinny=skinny)


def _tiled_fwd(x, values, indices, counts, n_in, bn, bm, bo, skinny):
    y = _tiled_spmm(x, values, indices, counts, n_in, bn, bm, bo, skinny)
    return y, (x, values, indices, counts)


def _slot_grads(dw: Array, indices: Array, counts: Array, bn: int) -> Array:
    """Gather a dense weight cotangent ``dw [O, NB*bn]`` back into the tile
    slots ``[NB, O, KB]``; padded slots (idx 0 beyond the block count) must
    not pick up ``dw[.., 0]``."""
    nb, o, kb = indices.shape
    cols = jnp.arange(nb)[:, None, None] * bn + indices           # [NB, O, KB]
    gathered = jnp.take_along_axis(
        dw, jnp.transpose(cols, (1, 0, 2)).reshape(o, nb * kb), axis=1)
    gathered = jnp.transpose(gathered.reshape(o, nb, kb), (1, 0, 2))
    valid = jnp.arange(kb)[None, None, :] < counts[..., None]
    return jnp.where(valid, gathered, 0.0)


def _tiled_bwd(n_in, bn, bm, bo, skinny, res, dy):
    x, values, indices, counts = res
    nb = values.shape[0]
    w = tiled_to_dense(TiledBalanced(values, indices, counts,
                                     n_in=n_in, bn=bn))           # [O, N]
    dx = jnp.dot(dy, w, preferred_element_type=jnp.float32).astype(x.dtype)
    # dW[o, n] = sum_m dy[m, o] x[m, n], gathered back into the tile slots
    dw = jnp.einsum("mo,mn->on", dy, x,
                    preferred_element_type=jnp.float32)           # [O, N]
    dw = jnp.pad(dw, ((0, 0), (0, nb * bn - n_in)))
    dvals = _slot_grads(dw, indices, counts, bn).astype(values.dtype)
    return dx, dvals, None, None


_tiled_spmm.defvjp(_tiled_fwd, _tiled_bwd)


def _densify_gather_tiled(values, indices, counts, scales, bn, quant):
    """Densify a (perm-free) tiled encoding -> ``[O, NB*bn]``, dequantized
    (`tile_format.tiled_to_dense` at the padded width: the caller already
    moved ``x`` into packed space)."""
    nb = indices.shape[0]
    return tiled_to_dense(TiledBalanced(values, indices, counts,
                                        n_in=nb * bn, bn=bn, scales=scales,
                                        quant=quant))


def _tiled_gather_spmm(x, values, indices, scales, bn, quant):
    """Gather+einsum on the tiled encoding (no densify) — the decode-shaped
    fallback formulation, mirroring `ref.balanced_spmm_gather`: at skinny M
    the ``[M, O, NB*KB]`` buffer is small and a per-step O*N densify would
    dominate the dot.  Pad slots contribute exactly 0 (their stored value
    word is 0).  Quantized tiles factor the per-block scale *out* of the
    slot reduction (``sum_s x*q*scale == scale * sum_s x*q``, exact per
    block): the scale multiply then costs O(M*O*NB) instead of O(O*SLOTS),
    which at decode M is the difference between matching the f32 gather
    and trailing it by the whole dequant."""
    nb, o, kb = indices.shape
    cols = jnp.arange(nb, dtype=indices.dtype)[:, None, None] * bn + indices
    xp = jnp.pad(x, ((0, 0), (0, nb * bn - x.shape[1])))
    xg = jnp.take(xp, cols, axis=1)                      # [M, NB, O, KB]
    if scales is None or quant == "none":
        vals = dequantize_values(values, scales, quant, kb)
        return jnp.einsum("mnos,nos->mo", xg, vals,
                          preferred_element_type=jnp.float32)
    q = unpack_int4(values, kb) if quant == "int4" else values
    partial = jnp.einsum("mnos,nos->mno", xg, q.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
    return jnp.einsum("mno,no->mo", partial, scales)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _tiled_spmm_q(x, values, indices, counts, scales, n_in, bn, bm, bo,
                  skinny, quant, impl):
    """Quantized tiled matmul with impl routing: "pallas" runs the quant
    kernel variants (in-VMEM dequant), "xla"/"xla_gather" dequantize +
    densify + rank-2 dot — the quantized plan's CPU/sharded fallbacks,
    which unlike the flat-format fallbacks keep the tile-local scales."""
    if impl == "pallas":
        tb = TiledBalanced(values, indices, counts, n_in=n_in, bn=bn,
                           scales=scales, quant=quant)
        return _pad_and_run_tiled(x, tb, bm, bo, skinny=skinny)
    _fault_trip("xla_gather" if impl == "xla_gather" else "xla")
    if impl == "xla_gather":
        return _tiled_gather_spmm(x, values, indices, scales, bn,
                                  quant).astype(x.dtype)
    if skinny:
        _fault_trip("xla_decode")
        return _tiled_gather_spmm(x, values, indices, scales, bn,
                                  quant).astype(x.dtype)
    if x.shape[0] <= GATHER_M:
        return _tiled_gather_spmm(x, values, indices, scales, bn,
                                  quant).astype(x.dtype)
    w = _densify_gather_tiled(values, indices, counts, scales, bn, quant)
    return jnp.dot(x, w[:, :x.shape[1]].T,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def _tiled_q_fwd(x, values, indices, counts, scales, n_in, bn, bm, bo,
                 skinny, quant, impl):
    y = _tiled_spmm_q(x, values, indices, counts, scales, n_in, bn, bm, bo,
                      skinny, quant, impl)
    return y, (x, values, indices, counts, scales)


def _tiled_q_bwd(n_in, bn, bm, bo, skinny, quant, impl, res, dy):
    # Straight-through: dx flows through the *dequantized* weights exactly
    # (the forward's W); the quantized value words and scales get no
    # cotangent — block-quantized weights are a deployment format, not a
    # training parameterization (DESIGN.md §13).
    x, values, indices, counts, scales = res
    w = _densify_gather_tiled(values, indices, counts, scales, bn,
                              quant)[:, :x.shape[1]]
    dx = jnp.dot(dy, w, preferred_element_type=jnp.float32).astype(x.dtype)
    dvals = None if not jnp.issubdtype(values.dtype, jnp.inexact) \
        else jnp.zeros_like(values)
    dscales = None if scales is None else jnp.zeros_like(scales)
    return dx, dvals, None, None, dscales


_tiled_spmm_q.defvjp(_tiled_q_fwd, _tiled_q_bwd)


def tiled_spmm(x: Array, tb: TiledBalanced, *, block_m: int | None = None,
               block_o: int | None = None, impl: str = "pallas") -> Array:
    """Differentiable balanced-sparse matmul on a *pre-encoded*
    `TiledBalanced` weight.  ``x``: ``[..., N]`` -> ``[..., O]``.

    This is the plan-driven entry point (`engine.execute.apply_fc`
    dispatches here for ``impl == "pallas"`` with ``block_m``/``block_o``
    from the plan's — possibly autotuned — `BlockChoice`, decode-shaped
    when M is skinny): the encoding was done once offline, so no per-call
    id()-keyed cache is consulted.  Skinny M (<= `SKINNY_M`) dispatches the
    decode-specialized kernel with bm pinned to the padded decode batch.
    Packed encodings (``tb.perm``) permute ``x`` into packed column space
    *outside* the custom_vjp, so autodiff transposes the gather and the VJP
    below never sees the permutation.  It is also the function
    `kernels.autotune.sweep_blocks` times per candidate.

    Quantized encodings (``tb.quant != "none"``) route through the quant
    custom_vjp — the pallas impl dequantizes in VMEM inside the kernel,
    while ``impl="xla"``/``"xla_gather"`` (the quantized plan's fallback
    impls, which keep the tiled format for its scales) dequantize +
    densify + dot.  Grads are straight-through to the dequantized values.
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    n_eff = tb.n_in
    if tb.perm is not None:
        perm = tb.perm
        if perm.ndim > 1:
            perm = perm.reshape(-1, perm.shape[-1])[0]
        npack = tb.nb * tb.bn
        x2 = jnp.take(jnp.pad(x2, ((0, 0), (0, npack - x2.shape[1]))),
                      perm.astype(jnp.int32), axis=1)
        n_eff = npack
    m = x2.shape[0]
    skinny = m <= SKINNY_M
    bm = _round_up(m, 8) if skinny else _pick_block(m, block_m or 128)
    bo = _pick_block(tb.n_out, block_o or 128)
    if tb.quant == "none" and impl == "pallas":
        y = _tiled_spmm(x2, tb.values, tb.indices, tb.counts, n_eff, tb.bn,
                        bm, bo, skinny)
    else:
        y = _tiled_spmm_q(x2, tb.values, tb.indices, tb.counts, tb.scales,
                          n_eff, tb.bn, bm, bo, skinny, tb.quant, impl)
    return y.reshape(*lead, tb.n_out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _tiled_spmm_batched(x, values, indices, counts, n_in, bn, bm, bo):
    _fault_trip("pallas", bm=bm, bo=bo, bn=bn, batched=True)
    e, m, _ = x.shape
    nb, o = values.shape[1], values.shape[2]
    mp, op_ = _round_up(m, bm), _round_up(o, bo)
    xp = jnp.pad(x, ((0, 0), (0, mp - m), (0, nb * bn - x.shape[2])))
    vp, ip = values, indices
    if op_ != o:
        vp, ip = _pad_rows(values, op_ - o), _pad_rows(indices, op_ - o)
    y = tiled_balanced_spmm_batched_pallas(xp, vp, ip, bn=bn, bm=bm, bo=bo)
    return y[:, :m, :o].astype(x.dtype)


def _tiled_batched_fwd(x, values, indices, counts, n_in, bn, bm, bo):
    y = _tiled_spmm_batched(x, values, indices, counts, n_in, bn, bm, bo)
    return y, (x, values, indices, counts)


def _tiled_batched_bwd(n_in, bn, bm, bo, res, dy):
    x, values, indices, counts = res
    nb = values.shape[1]
    w = jax.vmap(lambda v, i, c: tiled_to_dense(
        TiledBalanced(v, i, c, n_in=n_in, bn=bn)))(values, indices, counts)
    dx = jnp.einsum("emo,eon->emn", dy, w,
                    preferred_element_type=jnp.float32).astype(x.dtype)
    dw = jnp.einsum("emo,emn->eon", dy, x,
                    preferred_element_type=jnp.float32)
    dw = jnp.pad(dw, ((0, 0), (0, 0), (0, nb * bn - n_in)))
    dvals = jax.vmap(lambda d, i, c: _slot_grads(d, i, c, bn))(
        dw, indices, counts).astype(values.dtype)
    return dx, dvals, None, None


_tiled_spmm_batched.defvjp(_tiled_batched_fwd, _tiled_batched_bwd)


def _densify_gather_tiled_b(values, indices, counts, scales, bn, quant, g):
    """One expert group's gather densify (scales may be None when the
    unquantized tiled format rides this fallback)."""
    return _densify_gather_tiled(values[g], indices[g], counts[g],
                                 None if scales is None else scales[g],
                                 bn, quant)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _tiled_spmm_batched_q(x, values, indices, counts, scales, n_in, bn, bm,
                          bo, quant, impl):
    """Quantized twin of `_tiled_spmm_batched` with impl routing (see
    `_tiled_spmm_q`): one fused grid over all experts, per-expert scales."""
    if impl == "pallas":
        _fault_trip("pallas", bm=bm, bo=bo, bn=bn, batched=True)
        e, m, _ = x.shape
        nb, o = indices.shape[1], indices.shape[2]
        mp, op_ = _round_up(m, bm), _round_up(o, bo)
        xp = jnp.pad(x, ((0, 0), (0, mp - m), (0, nb * bn - x.shape[2])))
        vp, ip, sp = values, indices, scales
        if op_ != o:
            vp, ip = _pad_rows(values, op_ - o), _pad_rows(indices, op_ - o)
            sp = _pad_rows(scales, op_ - o, row_axis=-1)
        y = tiled_balanced_spmm_batched_pallas(
            xp, vp, ip, bn=bn, bm=bm, bo=bo, scales=sp, quant=quant)
        return y[:, :m, :o].astype(x.dtype)
    _fault_trip("xla_gather" if impl == "xla_gather" else "xla",
                batched=True)
    skinny_b = x.shape[1] <= SKINNY_M
    if impl != "xla_gather" and skinny_b:
        _fault_trip("xla_decode", batched=True)
    if impl == "xla_gather" or x.shape[1] <= GATHER_M:
        sc = scales if scales is not None else None
        f = lambda xe, v, i, s: _tiled_gather_spmm(xe, v, i, s, bn, quant)
        if sc is None:
            y = jax.vmap(lambda xe, v, i: f(xe, v, i, None))(
                x, values, indices)
        else:
            y = jax.vmap(f)(x, values, indices, sc)
        return y.astype(x.dtype)
    # unrolled over the (static) group axis, mirroring `_balanced_spmm_b`:
    # densify each group right before its dot so [O, N] stays cache-hot
    outs = [jnp.dot(
        x[g],
        _densify_gather_tiled_b(values, indices, counts, scales, bn,
                                quant, g)[:, :x.shape[2]].T,
        preferred_element_type=jnp.float32)
        for g in range(x.shape[0])]
    return jnp.stack(outs).astype(x.dtype)


def _tiled_batched_q_fwd(x, values, indices, counts, scales, n_in, bn, bm,
                         bo, quant, impl):
    y = _tiled_spmm_batched_q(x, values, indices, counts, scales, n_in, bn,
                              bm, bo, quant, impl)
    return y, (x, values, indices, counts, scales)


def _tiled_batched_q_bwd(n_in, bn, bm, bo, quant, impl, res, dy):
    x, values, indices, counts, scales = res
    dx = jnp.stack([jnp.dot(
        dy[g],
        _densify_gather_tiled_b(values, indices, counts, scales, bn,
                                quant, g)[:, :x.shape[2]],
        preferred_element_type=jnp.float32)
        for g in range(x.shape[0])]).astype(x.dtype)
    dvals = None if not jnp.issubdtype(values.dtype, jnp.inexact) \
        else jnp.zeros_like(values)
    dscales = None if scales is None else jnp.zeros_like(scales)
    return dx, dvals, None, None, dscales


_tiled_spmm_batched_q.defvjp(_tiled_batched_q_fwd, _tiled_batched_q_bwd)


def tiled_spmm_batched(x: Array, tb: TiledBalanced, *,
                       block_m: int | None = None,
                       block_o: int | None = None,
                       impl: str = "pallas") -> Array:
    """Fused batched pre-encoded entry: every group's balanced-sparse
    matmul in ONE kernel dispatch.

    ``x``: [G, ..., N]; ``tb`` leaves carry a matching leading group axis
    (values [G, NB, O, KB]).  This is the MoE expert path: G is the expert
    axis of a plan's per-expert encodings (shared BlockChoice, so one set
    of static bm/bo/KB covers the whole grid).  The expert axis is a Pallas
    grid dimension — the previous per-expert `lax.scan` paid E sequential
    dispatches (and on decode shapes the dispatch overhead dwarfed the
    math: the 0.10x MoE decode cliff in BENCH_serve PR 5).  Skinny token
    counts (capacity <= `SKINNY_M`) pin bm to the padded capacity.
    Differentiable via a batched custom VJP (einsum formulation — grad
    parity with the scanned `tiled_spmm` is tested).
    """
    lead = x.shape[1:-1]
    e = x.shape[0]
    o = tb.indices.shape[2]
    x3 = x.reshape(e, -1, x.shape[-1])
    n_eff = tb.n_in
    if tb.perm is not None:
        perm = tb.perm
        npack = tb.indices.shape[1] * tb.bn
        x3 = jnp.pad(x3, ((0, 0), (0, 0), (0, npack - x3.shape[2])))
        if perm.ndim > 1:
            # lead-broadcast leaf: one (identical) perm row per expert
            perm2 = perm.reshape(-1, perm.shape[-1])[:e]
            x3 = jax.vmap(lambda xe, pe: jnp.take(xe, pe.astype(jnp.int32),
                                                  axis=1))(x3, perm2)
        else:
            x3 = jnp.take(x3, perm.astype(jnp.int32), axis=2)
        n_eff = npack
    m = x3.shape[1]
    skinny = m <= SKINNY_M
    bm = _round_up(m, 8) if skinny else _pick_block(m, block_m or 128)
    bo = _pick_block(o, block_o or 128)
    if tb.quant == "none" and impl == "pallas":
        y = _tiled_spmm_batched(x3, tb.values, tb.indices, tb.counts, n_eff,
                                tb.bn, bm, bo)
    else:
        y = _tiled_spmm_batched_q(x3, tb.values, tb.indices, tb.counts,
                                  tb.scales, n_eff, tb.bn, bm, bo,
                                  tb.quant, impl)
    return y.reshape(e, *lead, o)


def _batched_gather_spmm(x: Array, values: Array, indices: Array) -> Array:
    """Per-group gather+einsum: [E, C, N] x [E, O, K] -> [E, C, O]."""
    xg = jax.vmap(lambda xe, ie: jnp.take(xe, ie, axis=1))(x, indices)
    return jnp.einsum("ecok,eok->eco", xg, values,
                      preferred_element_type=jnp.float32).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _balanced_spmm_b(x, values, indices, n_in, impl):
    if impl == "xla_gather":
        _fault_trip("xla_gather", batched=True)
        return _batched_gather_spmm(x, values, indices)
    _fault_trip("xla", batched=True)
    if x.shape[1] <= SKINNY_M:
        _fault_trip("xla_decode", batched=True)
        return _batched_gather_spmm(x, values, indices)
    # Unrolled over the (static) group axis: densify each group's weights
    # right before its dot so the densified [O, N] stays cache-hot.  A
    # batched einsum over a pre-materialised [E, O, N] stack is ~1.5x
    # slower on CPU at prefill shapes, and lax.scan is off the table (the
    # whole point of this path is one dispatch with no sequential carry).
    outs = [jnp.dot(x[g], _densify_gather(values[g], indices[g], n_in).T,
                    preferred_element_type=jnp.float32)
            for g in range(x.shape[0])]
    return jnp.stack(outs).astype(x.dtype)


def _balanced_b_fwd(x, values, indices, n_in, impl):
    y = _balanced_spmm_b(x, values, indices, n_in, impl)
    return y, (x, values, indices)


def _balanced_b_bwd(n_in, impl, res, dy):
    x, values, indices = res
    # same unrolled densify-inline structure as the forward wide path
    dx = jnp.stack([jnp.dot(dy[g], _densify_gather(values[g], indices[g],
                                                   n_in),
                            preferred_element_type=jnp.float32)
                    for g in range(x.shape[0])]).astype(x.dtype)
    xg = jax.vmap(lambda xe, ie: jnp.take(xe, ie, axis=1))(x, indices)
    dvals = jnp.einsum("eco,ecok->eok", dy, xg,
                       preferred_element_type=jnp.float32).astype(values.dtype)
    return dx, dvals, None


_balanced_spmm_b.defvjp(_balanced_b_fwd, _balanced_b_bwd)


def balanced_spmm_batched(x: Array, values: Array, indices: Array, *,
                          n_in: int, impl: str = "xla") -> Array:
    """Fused batched flat-format entry: [G, ..., N] x [G, O, K] -> [G, ..., O]
    in one dispatch (the MoE fallback impls — "xla" / "xla_gather" — used
    when a plan's expert weights are not pallas-tiled or were demoted).
    Replaces the per-expert `lax.scan` over `balanced_spmm`.  Skinny token
    counts route to the gather+einsum formulation.  Differentiable.
    """
    lead = x.shape[1:-1]
    g = x.shape[0]
    x3 = x.reshape(g, -1, x.shape[-1])
    y = _balanced_spmm_b(x3, values, indices.astype(jnp.int32), n_in, impl)
    return y.reshape(g, *lead, values.shape[-2])


# ---------------------------------------------------------------------------
# bitmap_spmm: y = x @ W.T, W bitmap-compressed
# ---------------------------------------------------------------------------

def bitmap_spmm(x: Array, bitmap: Array, packed: Array, offsets: Array, *,
                bn: int = 128, impl: str = "pallas") -> Array:
    """Bitmap-compressed matmul (inference path; not differentiable —
    compressed weights are a deployment format)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m, n = x2.shape
    o = bitmap.shape[0]
    if impl == "xla":
        y = ref.bitmap_spmm_ref(x2, bitmap, packed)
        return y.reshape(*lead, o)
    c = choose_blocks(m, o, n, packed.shape[1], itemsize=x.dtype.itemsize,
                      kind="bitmap", bn=bn)
    bm, bo = c.bm, c.bo
    assert n % bn == 0, (n, bn, "pad N before encoding")
    mp, op_ = _round_up(m, bm), _round_up(o, bo)
    xp = jnp.pad(x2, ((0, mp - m), (0, 0)))
    bmp = jnp.pad(bitmap, ((0, op_ - o), (0, 0)))
    pak = jnp.pad(packed, ((0, op_ - o), (0, 0)))
    off = jnp.pad(offsets, ((0, op_ - o), (0, 0)))
    y = bitmap_spmm_pallas(xp, bmp, pak, off, bm=bm, bo=bo, bn=bn)
    return y[:m, :o].astype(x.dtype).reshape(*lead, o)


def encode_bitmap(w: Array, *, bn: int = 128, k: int | None = None):
    """Dense [O, N] -> (bitmap, packed, offsets); N must be bn-aligned."""
    return bitmap_encode(w, bn, k=k)


__all__ = ["balanced_spmm", "balanced_spmm_batched", "tiled_spmm",
           "tiled_spmm_batched", "bitmap_spmm", "encode_bitmap",
           "choose_blocks", "BlockChoice", "halve_blocks",
           "InjectedKernelFault", "SKINNY_M", "bucket_m", "QUANT_WBYTES"]
