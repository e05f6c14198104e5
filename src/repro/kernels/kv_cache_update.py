"""Pallas TPU kernel: in-place single-token KV-cache write, plane layout.

Decode must insert one token's K/V at a *per-sequence* position.  In plain
XLA this lowers (under SPMD, with the position dynamic per batch element)
to a select + full-cache rewrite — measured at 86% of the decode_32k
memory traffic (EXPERIMENTS.md §Perf C).  The TPU-native fix is an indexed
write with scalar prefetch (the vLLM/PagedAttention pattern): the grid
walks the cache *planes*, each step DMA-writes one [1, dh] row at
``pos[p]`` — traffic is O(P*dh) per layer instead of O(P*S*dh).

The cache is stored in **plane layout** end-to-end: ``[P, S, dh]`` where a
plane is one (sequence, kv-head) pair — for a contiguous batch
``P = B * KH`` (plane ``b * KH + h``), for the paged pool
``P = num_pages * KH`` (plane ``page * KH + h``, see `serving.paged_kv`).
Models/`init_cache` allocate this layout directly, so there is no
transpose/reshape round-trip around the kernel: an earlier revision
accepted ``[B, S, KH, dh]`` and paid an O(B*S*KH*dh) XLA relayout before
*and* after every "in-place" O(B*KH*dh) write, which re-created exactly
the full-cache traffic the kernel exists to delete.

``input_output_aliasing`` makes the update genuinely in place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .balanced_spmm import interpret_mode

Array = jax.Array


def to_planes(kv: Array) -> Array:
    """``[B, S, KH, dh]`` -> plane layout ``[B*KH, S, dh]``."""
    b, s, kh, dh = kv.shape
    return kv.transpose(0, 2, 1, 3).reshape(b * kh, s, dh)


def from_planes(planes: Array, kh: int) -> Array:
    """Plane layout ``[B*KH, S, dh]`` -> ``[B, S, KH, dh]``."""
    p, s, dh = planes.shape
    return planes.reshape(p // kh, kh, s, dh).transpose(0, 2, 1, 3)


def _kernel(pos_ref, new_ref, cache_ref, out_ref):
    """Grid (P,).  cache/out block: [1, S, dh]; new: [1, dh].

    out aliases cache; we only touch the row at pos[p].
    """
    i = pl.program_id(0)
    pos = pos_ref[i]
    out_ref[0, pl.dslice(pos, 1), :] = new_ref[...].astype(out_ref.dtype)


def kv_cache_update_pallas(cache: Array, new: Array, pos: Array, *,
                           interpret: bool | None = None) -> Array:
    """cache: [P, S, dh] planes; new: [P, dh]; pos: [P] int32.

    Returns the cache with ``new[p]`` written at ``cache[p, pos[p]]`` — one
    indexed row write per plane, no relayout.
    """
    p, s, dh = cache.shape
    out = pl.pallas_call(
        _kernel,
        grid=(p,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),          # pos (scalars)
            pl.BlockSpec((1, dh), lambda i: (i, 0)),
            pl.BlockSpec((1, s, dh), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, s, dh), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((p, s, dh), cache.dtype),
        input_output_aliases={2: 0},
        interpret=interpret_mode(interpret),
    )(pos, new, cache)
    return out


def kv_cache_update_xla(cache: Array, new: Array, pos: Array) -> Array:
    """Same contract as the Pallas kernel via one XLA indexed scatter —
    the CPU/donation-friendly twin (`.at[]` is in place under jit when the
    cache is donated/dead after the write)."""
    p = cache.shape[0]
    return cache.at[jnp.arange(p), pos].set(new.astype(cache.dtype))


def kv_cache_write_chunk(cache: Array, new: Array, pos: Array) -> Array:
    """Multi-row plane write: ``new`` [P, C, dh] rows land at
    ``cache[p, pos[p] + i]`` for i < C — the prefill-chunk form of the
    decode write (C = 1 degenerates to `kv_cache_update_xla`)."""
    p, c, _ = new.shape
    rows = pos[:, None] + jnp.arange(c)[None, :]            # [P, C]
    return cache.at[jnp.arange(p)[:, None], rows].set(new.astype(cache.dtype))


def kv_cache_update_ref(cache: Array, new: Array, pos: Array) -> Array:
    """Pure-jnp oracle: the mask-select rewrite, plane layout."""
    p, s, _ = cache.shape
    mask = (jnp.arange(s)[None, :] == pos[:, None])[..., None]
    return jnp.where(mask, new[:, None].astype(cache.dtype), cache)
