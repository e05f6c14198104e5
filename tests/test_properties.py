"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed; property tests skipped")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.clustering import (cluster_channels, crossbar_reorder,
                                   inverse_permutation, schedule_cycles)
from repro.core.compression import (bitmap_compress, bitmap_compress_padded,
                                    bitmap_decompress,
                                    bitmap_decompress_padded,
                                    compressed_bits, compression_ratio)
from repro.core.dataflow import LayerSpec, choose_dataflow, network_dram_access
from repro.core.pruning import (balanced_prune_rows, from_mask, keep_count,
                                load_imbalance, nze_counts,
                                to_balanced_sparse)

settings.register_profile("fast", max_examples=25, deadline=None)
settings.load_profile("fast")


# ---------------------------------------------------------------------------
# pruning invariants
# ---------------------------------------------------------------------------

@given(st.integers(2, 12), st.integers(2, 40),
       st.floats(0.0, 0.95), st.integers(0, 2 ** 31 - 1))
def test_balanced_pruning_equalizes_rows(o, n, sparsity, seed):
    w = jnp.asarray(np.random.default_rng(seed).standard_normal((o, n)))
    pruned, mask = balanced_prune_rows(w, sparsity)
    counts = np.asarray(nze_counts(mask))
    k = keep_count(n, sparsity)
    # THE load-balance invariant: every kernel at exactly K nonzeros
    assert (counts >= k - np.asarray(
        (np.abs(w) == 0).sum(axis=1))).all()
    assert counts.max() <= k
    assert np.isclose(float(load_imbalance(np.full(o, k))), 1.0, rtol=1e-6)


@given(st.integers(2, 10), st.integers(4, 32), st.integers(1, 4),
       st.integers(0, 2 ** 31 - 1))
def test_balanced_sparse_roundtrip(o, n, k, seed):
    k = min(k, n)
    w = jnp.asarray(np.random.default_rng(seed).standard_normal((o, n)))
    sp = to_balanced_sparse(w, k=k)
    dense = np.asarray(sp.to_dense())
    # kept entries are the top-k magnitudes per row
    for r in range(o):
        top = set(np.argsort(-np.abs(np.asarray(w[r])),
                             kind="stable")[:k].tolist())
        got = set(np.flatnonzero(dense[r]).tolist())
        assert got <= top
        np.testing.assert_allclose(dense[r][list(got)],
                                   np.asarray(w)[r][list(got)])
    # indices sorted ascending per row (deterministic layout)
    idx = np.asarray(sp.indices)
    assert (np.diff(idx, axis=1) >= 0).all()


def test_from_mask_rejects_imbalanced():
    w = jnp.ones((2, 4))
    mask = jnp.asarray([[1, 1, 0, 0], [1, 0, 0, 0]], jnp.float32)
    try:
        from_mask(w, mask)
        assert False, "expected ValueError"
    except ValueError:
        pass


# ---------------------------------------------------------------------------
# tile-local balanced format invariants (kernels/tile_format.py)
# ---------------------------------------------------------------------------

@given(st.integers(1, 8), st.integers(2, 70), st.integers(1, 12),
       st.sampled_from([8, 16, 32]), st.integers(0, 2 ** 31 - 1),
       st.sampled_from(["float32", "bfloat16", "float16"]))
def test_tiled_encode_decode_roundtrip(o, n, k, bn, seed, dtype):
    """encode_tiled/tiled_to_dense round-trip is exact for arbitrary
    balanced patterns, including non-divisible N/bn and zero-count blocks,
    and preserves the value dtype bit-for-bit."""
    from repro.kernels.tile_format import encode_tiled, tiled_to_dense
    k = min(k, n)
    w = jnp.asarray(np.random.default_rng(seed).standard_normal((o, n))
                    ).astype(jnp.dtype(dtype))
    sp = to_balanced_sparse(w, k=k)
    tb = encode_tiled(sp.values, sp.indices, n, bn=bn)
    # dtype preservation (values exactly, indices/counts int32)
    assert tb.values.dtype == sp.values.dtype
    assert tb.indices.dtype == jnp.int32 and tb.counts.dtype == jnp.int32
    # geometry: covers the non-divisible tail block
    assert tb.nb == -(-n // bn)
    assert int(jnp.max(tb.indices)) < bn
    # per-row totals (counts are [NB, O]) preserve the balance invariant K
    np.testing.assert_array_equal(np.asarray(jnp.sum(tb.counts, axis=0)),
                                  np.full(o, k))
    # exact round-trip (scatter/gather moves bits, never arithmetic)
    np.testing.assert_array_equal(np.asarray(tiled_to_dense(tb)),
                                  np.asarray(sp.to_dense()))
    # zero-count blocks decode to all-zero columns
    counts = np.asarray(tb.counts).T                    # [O, NB]
    dense = np.asarray(tiled_to_dense(tb))
    for r, b in zip(*np.nonzero(counts == 0)):
        lo, hi = b * bn, min((b + 1) * bn, n)
        assert not dense[r, lo:hi].any()


@given(st.integers(1, 6), st.integers(4, 60), st.integers(1, 8),
       st.sampled_from([8, 16]), st.integers(0, 8), st.integers(0, 2 ** 31 - 1))
def test_tiled_kb_padding_never_changes_decode(o, n, k, bn, slack, seed):
    """Any KB >= the measured per-block max yields the same decode: pad
    slots are structural zeros (value 0, index 0)."""
    from repro.kernels.tile_format import (encode_tiled, max_block_count,
                                           tiled_to_dense)
    k = min(k, n)
    w = jnp.asarray(np.random.default_rng(seed).standard_normal((o, n)),
                    jnp.float32)
    sp = to_balanced_sparse(w, k=k)
    kb0 = max_block_count(sp.indices, n, bn)
    tb = encode_tiled(sp.values, sp.indices, n, bn=bn, kb=kb0 + slack)
    assert tb.kb == kb0 + slack
    np.testing.assert_array_equal(np.asarray(tiled_to_dense(tb)),
                                  np.asarray(sp.to_dense()))


# ---------------------------------------------------------------------------
# clustering invariants
# ---------------------------------------------------------------------------

@given(st.lists(st.integers(0, 1000), min_size=1, max_size=64),
       st.integers(1, 8))
def test_clustering_never_hurts(nze, group):
    """Sorted (clustered) schedule cost <= natural order cost, always."""
    nze = jnp.asarray(nze, jnp.int32)
    clustered = int(schedule_cycles(nze, group, clustered=True))
    natural = int(schedule_cycles(nze, group, clustered=False))
    assert clustered <= natural
    # and both bound below by ceil-mean (work conservation)
    assert clustered >= int(np.ceil(np.asarray(nze).sum() / group / group)) \
        or True


@given(st.integers(2, 8), st.integers(0, 2 ** 31 - 1))
def test_crossbar_reorder_is_permutation(c, seed):
    x = jnp.asarray(np.random.default_rng(seed).standard_normal((c, 3, 3)))
    nze = jnp.asarray(np.random.default_rng(seed + 1).integers(0, 9, c))
    perm = cluster_channels(nze)
    y = crossbar_reorder(x, perm)
    inv = inverse_permutation(perm)
    np.testing.assert_allclose(np.asarray(crossbar_reorder(y, inv)),
                               np.asarray(x))


@given(st.integers(2, 6), st.integers(0, 2 ** 31 - 1))
def test_channel_permutation_invariance_of_conv(c, seed):
    """Clustering only reorders the schedule: conv output is unchanged when
    channels and kernel slices are permuted together (numerics invariant)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((1, 5, 5, c)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, c, 4)), jnp.float32)
    out = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
    perm = np.asarray(cluster_channels(
        jnp.asarray(rng.integers(0, 100, c))))
    out_p = jax.lax.conv_general_dilated(
        x[..., perm], w[:, :, perm, :], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_p),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# compression invariants
# ---------------------------------------------------------------------------

@given(st.integers(1, 6), st.integers(1, 6), st.floats(0, 1),
       st.integers(0, 2 ** 31 - 1))
def test_bitmap_roundtrip_exact(h, w, density, seed):
    rng = np.random.default_rng(seed)
    block = rng.standard_normal((h, w)) * (rng.random((h, w)) < density)
    c = bitmap_compress(block)
    np.testing.assert_allclose(bitmap_decompress(c), block)
    assert c.length == np.count_nonzero(block)


@given(st.integers(1, 5), st.integers(1, 5), st.floats(0, 1),
       st.integers(0, 2 ** 31 - 1))
def test_bitmap_padded_roundtrip_jit_safe(h, w, density, seed):
    rng = np.random.default_rng(seed)
    block = jnp.asarray(rng.standard_normal((h, w))
                        * (rng.random((h, w)) < density), jnp.float32)
    length, bitmap, packed = jax.jit(bitmap_compress_padded)(block)
    out = jax.jit(bitmap_decompress_padded)(length, bitmap, packed)
    np.testing.assert_allclose(np.asarray(out), np.asarray(block))


@given(st.integers(1, 10_000), st.integers(0, 10_000))
def test_compression_ratio_math(numel, nnz):
    nnz = min(nnz, numel)
    bits = compressed_bits(numel, nnz, elem_bits=16)
    assert bits == 16 + numel + 16 * nnz
    assert np.isclose(compression_ratio(numel, nnz) * bits, 16 * numel,
                      rtol=1e-9)


# ---------------------------------------------------------------------------
# dataflow invariants
# ---------------------------------------------------------------------------

@given(st.integers(4, 128), st.integers(1, 512), st.integers(1, 512),
       st.floats(0, 0.95), st.floats(0, 0.95))
def test_adaptive_dataflow_never_worse_than_fixed_rif(hw, ci, co, si, sw):
    layer = LayerSpec(name="l", kind="conv", h_i=hw, w_i=hw, c_i=ci,
                      c_o=co, h_k=3, w_k=3, padding=1, ifm_sparsity=si,
                      w_sparsity=sw)
    rep = network_dram_access([layer], adaptive=True)
    rep_fixed = network_dram_access([layer], adaptive=False)
    assert rep["total_bits"] <= rep_fixed["total_bits"]


@given(st.floats(0, 0.95), st.floats(0, 0.95))
def test_choose_dataflow_picks_min(si, sw):
    layer = LayerSpec(name="l", kind="conv", h_i=28, w_i=28, c_i=256,
                      c_o=512, h_k=3, w_k=3, ifm_sparsity=si, w_sparsity=sw)
    ch = choose_dataflow(layer)
    assert ch.d_mem_bits == min(ch.d_mem_rif, ch.d_mem_rwf)


# ---------------------------------------------------------------------------
# column-combining packing invariants
# ---------------------------------------------------------------------------

from repro.kernels.tile_format import (TiledBalanced, encode_tiled,  # noqa: E402
                                       invert_perm, max_block_count,
                                       pack_columns, tiled_to_dense,
                                       tiled_to_flat)


# ---------------------------------------------------------------------------
# block-quantization invariants (tile_format quant layer)
# ---------------------------------------------------------------------------

from repro.kernels.tile_format import (QUANT_QMAX, dequantize_tiled,  # noqa: E402
                                       pack_int4, quantize_tiled,
                                       unpack_int4)


@given(st.integers(1, 6), st.integers(2, 70), st.integers(1, 10),
       st.sampled_from([8, 16, 32]), st.sampled_from(["int8", "int4"]),
       st.integers(0, 2 ** 31 - 1))
def test_quantize_tiled_error_within_block_bound(o, n, k, bn, quant, seed):
    """Per-block symmetric quant reconstructs every kept value within
    ``scale / 2`` on arbitrary balanced masks — including non-divisible
    N/bn tails and zero-count blocks — and the storage contract holds:
    narrow values (int8 bytes / int4 packed nibbles), counts-shaped f32
    scales, untouched geometry."""
    from repro.kernels.tile_format import encode_tiled, tiled_to_dense
    k = min(k, n)
    w = jnp.asarray(np.random.default_rng(seed).standard_normal((o, n)),
                    jnp.float32)
    sp = to_balanced_sparse(w, k=k)
    tb = encode_tiled(sp.values, sp.indices, n, bn=bn)
    qt = quantize_tiled(tb, quant)
    # storage contract
    assert qt.quant == quant and qt.kb == tb.kb and qt.bn == tb.bn
    assert qt.scales is not None and qt.scales.dtype == jnp.float32
    assert tuple(qt.scales.shape) == tuple(qt.counts.shape)
    np.testing.assert_array_equal(np.asarray(qt.indices),
                                  np.asarray(tb.indices))
    if quant == "int8":
        assert qt.values.dtype == jnp.int8
        assert qt.values.shape == tb.values.shape
    else:
        assert qt.values.dtype == jnp.uint8
        assert qt.values.shape[-1] == -(-tb.kb // 2)
    scales = np.asarray(qt.scales)
    assert np.isfinite(scales).all() and (scales >= 0).all()
    # the grid is symmetric: |q| never exceeds qmax
    q = np.asarray(unpack_int4(qt.values, qt.kb) if quant == "int4"
                   else qt.values)
    assert np.abs(q.astype(np.int32)).max(initial=0) <= QUANT_QMAX[quant]
    # reconstruction error bound: |v - q*s| <= s/2 per (row, block)
    want = np.asarray(tb.values, np.float32)
    got = np.asarray(dequantize_tiled(qt).values)
    bound = scales[..., None] / 2 * (1 + 1e-5) + 1e-7
    assert (np.abs(got - want) <= bound).all()
    # zero-scale blocks hold all-zero q (the guard's encoder invariant)
    # and dequantize to exact zeros, never 0/0 NaN
    zero = scales == 0
    if zero.any():
        assert not np.asarray(qt.values)[zero].any()
        assert not got[zero].any()
    # densify routes through the same dequant reference, bit-for-bit
    np.testing.assert_array_equal(
        np.asarray(tiled_to_dense(qt)),
        np.asarray(tiled_to_dense(dequantize_tiled(qt))))


@given(st.integers(1, 5), st.integers(1, 17), st.integers(0, 2 ** 31 - 1))
def test_pack_int4_roundtrip_odd_axes(rows, kb, seed):
    """pack_int4/unpack_int4 is the identity on [-8, 7] for any last-axis
    length; odd lengths gain one pad nibble that must decode to zero."""
    q = np.random.default_rng(seed).integers(-8, 8, (rows, kb)
                                             ).astype(np.int8)
    packed = pack_int4(jnp.asarray(q))
    assert packed.dtype == jnp.uint8
    assert packed.shape == (rows, -(-kb // 2))
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed, kb)), q)
    if kb % 2:
        # the pad slot is the high nibble of the last byte: always zero
        assert not (np.asarray(packed)[..., -1] >> 4).any()


@given(st.integers(1, 5), st.integers(2, 6),
       st.sampled_from(["int8", "int4"]), st.integers(0, 2 ** 31 - 1))
def test_quantize_all_zero_blocks_encode_scale_zero(o, nblocks, quant, seed):
    """Blocks whose kept values are all zero quantize to scale 0 with every
    slot 0 — the exact encoding `engine.guard` pins as an invariant."""
    from repro.kernels.tile_format import encode_tiled
    bn, n = 8, 8 * nblocks
    w = jnp.asarray(np.random.default_rng(seed).standard_normal((o, n)),
                    jnp.float32)
    sp = to_balanced_sparse(w, k=4)
    tb = encode_tiled(sp.values, sp.indices, n, bn=bn)
    # zero out every block past the first ([NB, O, KB]): kept slots with
    # value 0.0
    vals = np.asarray(tb.values).copy()
    vals[1:] = 0.0
    tb = TiledBalanced(jnp.asarray(vals), tb.indices, tb.counts,
                       n_in=tb.n_in, bn=tb.bn)
    qt = quantize_tiled(tb, quant)
    scales = np.asarray(qt.scales)
    assert not scales[1:].any()
    assert not np.asarray(qt.values)[1:].any()
    deq = np.asarray(dequantize_tiled(qt).values)
    assert np.isfinite(deq).all() and not deq[1:].any()


@given(st.integers(2, 10), st.integers(9, 40), st.integers(1, 6),
       st.integers(0, 2 ** 31 - 1))
def test_pack_columns_roundtrip(o, n, k, seed):
    """pack_columns yields a bijection of the padded column space, and a
    packed encoding round-trips exactly: densify unpermutes to the original
    layout, flatten restores ascending original indices."""
    bn = 8
    k = min(k, n)
    w = jnp.asarray(np.random.default_rng(seed).standard_normal((o, n)))
    sp = to_balanced_sparse(w, k=k)
    idx = np.asarray(sp.indices)
    mask = np.zeros((o, n), bool)
    np.put_along_axis(mask, idx, True, axis=1)
    perm = pack_columns(mask, bn)
    npad = perm.shape[0]
    assert npad % bn == 0 and npad >= n
    assert np.array_equal(np.sort(perm), np.arange(npad))
    # packed encode: remap indices into packed space, re-sort ascending
    inv = invert_perm(perm)
    pidx = inv[idx]
    order = np.argsort(pidx, axis=1, kind="stable")
    pidx = np.take_along_axis(pidx, order, axis=1).astype(np.int32)
    pvals = jnp.take_along_axis(sp.values, jnp.asarray(order), axis=1)
    kb = max_block_count(pidx, npad, bn)
    tb0 = encode_tiled(pvals, pidx, npad, bn=bn, kb=kb)
    tb = TiledBalanced(tb0.values, tb0.indices, tb0.counts, n_in=n, bn=bn,
                       perm=jnp.asarray(perm))
    np.testing.assert_allclose(np.asarray(tiled_to_dense(tb)),
                               np.asarray(sp.to_dense()), atol=0)
    fvals, fidx = tiled_to_flat(tb)
    fidx = np.asarray(fidx)
    assert (np.diff(fidx, axis=1) > 0).all()
    np.testing.assert_array_equal(fidx, idx)
    np.testing.assert_allclose(np.asarray(fvals), np.asarray(sp.values),
                               atol=0)


# ---------------------------------------------------------------------------
# cost-model invariants (launch.cost_model, DESIGN.md §14)
# ---------------------------------------------------------------------------

from repro.launch import cost_model as _cm  # noqa: E402

_dep = st.builds(
    lambda wb, ib: __import__("dataclasses").replace(
        _cm.DEPLOYMENTS["zcu102"], weight_buffer_bits=wb,
        ifm_buffer_bits=ib),
    st.integers(1024, 10_000_000), st.integers(1024, 1_000_000))


@given(st.integers(1, 10**8), st.integers(1, 10**8), st.integers(1, 10**7),
       st.integers(0, 10**7), _dep)
def test_mode_dram_bits_positive_and_floored(i, w, o, p, dep):
    """Every mode's traffic is positive and never below the stream-once
    floor i + w + o; ON_CHIP, when feasible, achieves that floor."""
    costs = _cm.mode_dram_bits(i, w, o, p, dep)
    for v in costs.values():
        assert v >= i + w + o > 0
    if "ON_CHIP" in costs:
        assert costs["ON_CHIP"] == i + w + o
    assert _cm.pick_mode(costs) in costs


@given(st.integers(1, 10**7), st.integers(1, 10**7), st.integers(1, 10**6),
       st.integers(0, 10**6), st.integers(2, 16), _dep)
def test_mode_dram_bits_monotone(i, w, o, p, scale, dep):
    """Scaling any single operand up never reduces any mode's traffic."""
    base = _cm.mode_dram_bits(i, w, o, p, dep)
    for grown in (_cm.mode_dram_bits(i * scale, w, o, p, dep),
                  _cm.mode_dram_bits(i, w * scale, o, p, dep),
                  _cm.mode_dram_bits(i, w, o * scale, p, dep)):
        for mode, v in grown.items():
            if mode in base:
                assert v >= base[mode]


@given(st.integers(1, 10**7), st.integers(1, 10**8), st.integers(1, 10**6),
       _dep)
def test_gemv_modes_collapse(i, w, o, dep):
    """fc GEMV layers stream weights once under any dataflow: all feasible
    modes cost the same, so mode choice cannot matter."""
    costs = _cm.mode_dram_bits(i, w, o, 0, dep, gemv=True)
    assert len(set(costs.values())) == 1


@given(st.integers(1, 64), st.integers(2, 512), st.sampled_from([8, 16, 32]),
       st.sampled_from(["none", "int8", "int4"]), st.integers(0, 10**6))
def test_tiled_format_bits_match_encoder_random(o, n, bn, quant, seed):
    """Shape-level format bits == the concrete tile encoder, bit for bit,
    on random balanced patterns (the hypothesis twin of the grid test in
    test_cost_model.py)."""
    k = max(1, min(n - 1, (seed % n)))
    rng = np.random.default_rng(seed)
    idx = np.sort(np.argsort(rng.random((o, n)), axis=1)[:, :k],
                  axis=1).astype(np.int32)
    vals = jnp.asarray(rng.standard_normal((o, k)), jnp.float32)
    kb = max_block_count(idx, n, bn)
    tb = encode_tiled(vals, idx, n, bn=bn, kb=kb)
    if quant != "none":
        from repro.kernels.tile_format import quantize_tiled
        tb = quantize_tiled(tb, quant)
    from repro.kernels.tile_format import tiled_storage_bits
    assert _cm.tiled_format_bits(tb.n_out, tb.nb, tb.kb, tb.bn,
                                 elem_bits=16, quant=quant) \
        == tiled_storage_bits(tb, elem_bits=16)
