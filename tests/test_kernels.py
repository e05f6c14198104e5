"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes, plus gradient checks for the custom VJP."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pruning import to_balanced_sparse
from repro.kernels import ops
from repro.kernels import ref
from repro.kernels.bitmap_spmm import bitmap_encode
from repro.kernels.sparse_conv import im2col, sparse_conv2d


def rand(key, shape, dtype):
    return jax.random.normal(jax.random.key(key), shape).astype(dtype)


SHAPES = [  # (m, n, o, k)
    (8, 16, 8, 4),
    (16, 64, 32, 16),
    (33, 100, 17, 7),      # deliberately unaligned
    (128, 128, 128, 32),
]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("m,n,o,k", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_balanced_spmm_matches_ref(m, n, o, k, dtype):
    x = rand(0, (m, n), dtype)
    w = rand(1, (o, n), jnp.float32)
    sp = to_balanced_sparse(w, k=k)
    got = ops.balanced_spmm(x, sp.values.astype(dtype),
                            sp.indices, n_in=n, impl="pallas")
    want = ref.balanced_spmm_ref(x, sp.values.astype(dtype), sp.indices)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_balanced_spmm_batched_leading_dims(impl):
    x = rand(2, (2, 5, 32), jnp.float32)
    sp = to_balanced_sparse(rand(3, (16, 32), jnp.float32), k=8)
    y = ops.balanced_spmm(x, sp.values, sp.indices, n_in=32, impl=impl)
    assert y.shape == (2, 5, 16)
    want = ref.balanced_spmm_ref(x.reshape(10, 32), sp.values, sp.indices)
    np.testing.assert_allclose(np.asarray(y).reshape(10, 16),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_balanced_spmm_grads_match_dense():
    """custom_vjp grads == grads of the dense formulation."""
    m, n, o, k = 8, 32, 16, 8
    x = rand(4, (m, n), jnp.float32)
    sp = to_balanced_sparse(rand(5, (o, n), jnp.float32), k=k)

    def f_sparse(x, vals):
        return jnp.sum(ops.balanced_spmm(x, vals, sp.indices, n_in=n,
                                         impl="pallas") ** 2)

    def f_dense(x, vals):
        w = ref.balanced_dense(vals, sp.indices, n)
        return jnp.sum((x @ w.T) ** 2)

    gx1, gv1 = jax.grad(f_sparse, argnums=(0, 1))(x, sp.values)
    gx2, gv2 = jax.grad(f_dense, argnums=(0, 1))(x, sp.values)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gv1), np.asarray(gv2),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Tile-local balanced format + decode-and-matmul path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,o,k", [(37, 96, 50, 24), (37, 96, 50, 7),
                                     (130, 260, 33, 65)])
@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_tiled_balanced_three_way_parity(m, n, o, k, dtype):
    """Tiled Pallas == XLA fallback == dense reference on shapes aligned to
    nothing (M, O, N all off-tile)."""
    x = rand(10, (m, n), dtype)
    sp = to_balanced_sparse(rand(11, (o, n), jnp.float32), k=k)
    vals = sp.values.astype(dtype)
    want = ref.balanced_spmm_ref(x, vals, sp.indices)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    for impl in ("pallas", "xla", "xla_gather"):
        got = ops.balanced_spmm(x, vals, sp.indices, n_in=n, impl=impl)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol, err_msg=impl)


def test_tile_format_roundtrip_and_balance():
    from repro.kernels.tile_format import (block_imbalance, encode_tiled,
                                           tiled_to_dense)
    o, n, k, bn = 12, 200, 40, 64
    sp = to_balanced_sparse(rand(12, (o, n), jnp.float32), k=k)
    tb = sp.to_tiled(bn=bn)
    assert tb.nb == -(-n // bn) and tb.bn == bn
    # block-local indices stay inside their block
    assert int(jnp.max(tb.indices)) < bn
    # counts [NB, O] preserve the per-row total K (the balance invariant)
    np.testing.assert_array_equal(np.asarray(jnp.sum(tb.counts, axis=0)),
                                  np.full(o, k))
    np.testing.assert_allclose(np.asarray(tiled_to_dense(tb)),
                               np.asarray(sp.to_dense()), atol=0)
    assert block_imbalance(tb) >= 1.0
    # explicit kb: padding slots must not change the decode
    tb2 = encode_tiled(sp.values, sp.indices, n, bn=bn, kb=tb.kb + 16)
    np.testing.assert_allclose(np.asarray(tiled_to_dense(tb2)),
                               np.asarray(sp.to_dense()), atol=0)


def test_tiled_grads_match_dense_nonaligned():
    """custom_vjp grads through the tiled Pallas fwd == dense grads, on a
    non-tile-aligned shape."""
    m, n, o, k = 37, 96, 50, 24
    x = rand(13, (m, n), jnp.float32)
    sp = to_balanced_sparse(rand(14, (o, n), jnp.float32), k=k)

    def f_sparse(x, vals):
        return jnp.sum(ops.balanced_spmm(x, vals, sp.indices, n_in=n,
                                         impl="pallas") ** 2)

    def f_dense(x, vals):
        w = ref.balanced_dense(vals, sp.indices, n)
        return jnp.sum((x @ w.T) ** 2)

    gx1, gv1 = jax.grad(f_sparse, argnums=(0, 1))(x, sp.values)
    gx2, gv2 = jax.grad(f_dense, argnums=(0, 1))(x, sp.values)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gv1), np.asarray(gv2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["pallas", "xla", "xla_gather"])
def test_balanced_spmm_grads_match_masked_dense_all_impls(impl):
    """The custom VJP is impl-independent: every impl's grads == the
    masked-dense VJP (w densified from the same balanced pattern)."""
    m, n, o, k = 12, 96, 18, 24
    x = rand(20, (m, n), jnp.float32)
    sp = to_balanced_sparse(rand(21, (o, n), jnp.float32), k=k)

    def f_sparse(x, vals):
        return jnp.sum(ops.balanced_spmm(x, vals, sp.indices, n_in=n,
                                         impl=impl) ** 2)

    def f_masked_dense(x, vals):
        w = ref.balanced_dense(vals, sp.indices, n)
        return jnp.sum((x @ w.T) ** 2)

    gx1, gv1 = jax.grad(f_sparse, argnums=(0, 1))(x, sp.values)
    gx2, gv2 = jax.grad(f_masked_dense, argnums=(0, 1))(x, sp.values)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=1e-4, atol=1e-4, err_msg=impl)
    np.testing.assert_allclose(np.asarray(gv1), np.asarray(gv2),
                               rtol=1e-4, atol=1e-4, err_msg=impl)


def test_tiled_spmm_pad_slot_grads_zero_and_match_masked_dense():
    """The pre-encoded entry's VJP projects gradients off the KB padding
    slots: pad slots (index 0 beyond the block count) get exactly zero
    grad — they are structural zeros, not weights — and valid slots match
    the masked-dense VJP."""
    from repro.kernels.tile_format import (TiledBalanced, encode_tiled,
                                           max_block_count, tiled_to_dense)
    m, n, o, k, bn = 20, 100, 24, 30, 32
    x = rand(22, (m, n), jnp.float32)
    sp = to_balanced_sparse(rand(23, (o, n), jnp.float32), k=k)
    kb = max_block_count(sp.indices, n, bn) + 16          # force pad slots
    tb = encode_tiled(sp.values, sp.indices, n, bn=bn, kb=kb)
    valid = (jnp.arange(kb)[None, None, :]
             < tb.counts[..., None]).astype(jnp.float32)
    assert float(valid.mean()) < 1.0, "test needs real padding slots"

    def f_tiled(x, values):
        t = TiledBalanced(values, tb.indices, tb.counts, n_in=n, bn=bn)
        return jnp.sum(ops.tiled_spmm(x, t) ** 2)

    def f_masked_dense(x, values):
        # the masked-dense reference: pad slots masked out *before* the
        # densify, so its autodiff grads are zero there by construction
        t = TiledBalanced(values * valid, tb.indices, tb.counts,
                          n_in=n, bn=bn)
        return jnp.sum((x @ tiled_to_dense(t).T) ** 2)

    gx1, gv1 = jax.grad(f_tiled, argnums=(0, 1))(x, tb.values)
    gx2, gv2 = jax.grad(f_masked_dense, argnums=(0, 1))(x, tb.values)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gv1), np.asarray(gv2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(gv1 * (1.0 - valid)), 0.0)


def test_tiled_spmm_batched_matches_per_group():
    """The batched pre-encoded entry (expert path) == one tiled_spmm per
    group, forward and backward."""
    from repro.kernels.tile_format import TiledBalanced, encode_tiled
    g, m, n, o, k, bn = 3, 9, 64, 16, 16, 32
    xs = rand(24, (g, m, n), jnp.float32)
    sps = [to_balanced_sparse(rand(25 + i, (o, n), jnp.float32), k=k)
           for i in range(g)]
    kb = 24
    tbs = [encode_tiled(s.values, s.indices, n, bn=bn, kb=kb) for s in sps]
    tb = TiledBalanced(jnp.stack([t.values for t in tbs]),
                       jnp.stack([t.indices for t in tbs]),
                       jnp.stack([t.counts for t in tbs]), n_in=n, bn=bn)
    got = ops.tiled_spmm_batched(xs, tb)
    want = jnp.stack([ops.tiled_spmm(xs[i], tbs[i]) for i in range(g)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    def f_batched(xs, values):
        t = TiledBalanced(values, tb.indices, tb.counts, n_in=n, bn=bn)
        return jnp.sum(ops.tiled_spmm_batched(xs, t) ** 2)

    def f_per_group(xs, values):
        return sum(jnp.sum(ops.tiled_spmm(
            xs[i], TiledBalanced(values[i], tb.indices[i], tb.counts[i],
                                 n_in=n, bn=bn)) ** 2) for i in range(g))

    gx1, gv1 = jax.grad(f_batched, argnums=(0, 1))(xs, tb.values)
    gx2, gv2 = jax.grad(f_per_group, argnums=(0, 1))(xs, tb.values)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gv1), np.asarray(gv2),
                               rtol=1e-4, atol=1e-4)


def test_choose_blocks_respects_vmem_budget():
    c = ops.choose_blocks(4096, 4096, 8192, 4096, itemsize=4,
                          vmem_budget=1 << 20)
    assert 2 * c.vmem_bytes <= (1 << 20)
    assert all(v >= 8 for v in (c.bm, c.bo, c.bn))
    # small dims shrink blocks instead of padding 16x
    c2 = ops.choose_blocks(8, 16, 32, 8, itemsize=4)
    assert c2.bm <= 16 and c2.bo <= 32 and c2.bn <= 64


def test_sparse_conv_chunked_matches_single_piece():
    """Streaming the im2col GEMM in output-row chunks is exact."""
    b, h, w_, ci, co, hk = 2, 16, 16, 4, 6, 3
    x = rand(15, (b, h, w_, ci), jnp.float32)
    sp = to_balanced_sparse(rand(16, (co, ci * hk * hk), jnp.float32), k=10)
    one = sparse_conv2d(x, sp.values, sp.indices, sp.n_in, hk=hk, wk=hk,
                        stride=2, padding="SAME", chunk_elems=1 << 30)
    chunked = sparse_conv2d(x, sp.values, sp.indices, sp.n_in, hk=hk, wk=hk,
                            stride=2, padding="SAME", chunk_elems=512)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(one),
                               rtol=1e-5, atol=1e-5)
    want = ref.sparse_conv2d_ref(
        x, jnp.asarray(np.asarray(ref.balanced_dense(
            sp.values, sp.indices, sp.n_in)).reshape(co, ci, hk, hk)
            .transpose(2, 3, 1, 0)), stride=2, padding="SAME")
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_bitmap_encode_static_k_jittable():
    """bitmap_encode with a static k traces (no host sync on device data)."""
    w = jnp.asarray(np.random.default_rng(3).standard_normal((6, 256))
                    * (np.random.default_rng(4).random((6, 256)) > 0.6))
    kmax = int(np.count_nonzero(np.asarray(w), axis=1).max())
    enc = jax.jit(lambda w: bitmap_encode(w, 128, k=kmax))
    bitmap, packed, offsets = enc(w)
    np.testing.assert_allclose(np.asarray(ref.bitmap_dense(bitmap, packed)),
                               np.asarray(w), atol=0)


@pytest.mark.parametrize("o,n,sparsity", [(8, 128, 0.5), (16, 256, 0.9),
                                          (5, 128, 0.3)])
def test_bitmap_spmm_matches_ref(o, n, sparsity):
    w = np.asarray(rand(6, (o, n), jnp.float32))
    mask = np.random.default_rng(0).random((o, n)) >= sparsity
    w = jnp.asarray(w * mask)
    x = rand(7, (12, n), jnp.float32)
    bitmap, packed, offsets = bitmap_encode(w, bn=128)
    got = ops.bitmap_spmm(x, bitmap, packed, offsets, bn=128, impl="pallas")
    want = jnp.dot(x, w.T)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_bitmap_encode_roundtrip():
    w = jnp.asarray(np.random.default_rng(1).standard_normal((6, 256))
                    * (np.random.default_rng(2).random((6, 256)) > 0.6))
    bitmap, packed, offsets = bitmap_encode(w, bn=128)
    dense = ref.bitmap_dense(bitmap, packed)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(w), atol=0)


@pytest.mark.parametrize("hk,stride,pad", [(3, 1, "SAME"), (3, 2, "SAME"),
                                           (1, 1, "SAME"), (3, 1, 1)])
def test_sparse_conv_matches_dense_oracle(hk, stride, pad):
    b, h, w_, ci, co = 2, 8, 8, 4, 6
    x = rand(8, (b, h, w_, ci), jnp.float32)
    wt = np.asarray(rand(9, (co, ci, hk, hk), jnp.float32))
    # balanced mask: equal NZE per kernel
    keep = max(1, ci * hk * hk // 2)
    flat = wt.reshape(co, -1)
    order = np.argsort(-np.abs(flat), axis=1)
    mask = np.zeros_like(flat)
    np.put_along_axis(mask, order[:, :keep], 1.0, axis=1)
    wt_sparse = jnp.asarray(flat * mask)
    sp = to_balanced_sparse(wt_sparse, k=keep)
    got = sparse_conv2d(x, sp.values, sp.indices, sp.n_in, hk=hk, wk=hk,
                        stride=stride, padding=pad)
    w_dense = np.asarray(wt_sparse).reshape(co, ci, hk, hk) \
        .transpose(2, 3, 1, 0)  # HWIO
    want = ref.sparse_conv2d_ref(x, jnp.asarray(w_dense), stride=stride,
                                 padding=pad)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_im2col_column_order_matches_pruning_layout():
    """im2col feature order must be (Ci, Hk, Wk) raster — the same
    flattening as balanced_prune_conv, or index mapping breaks."""
    b, h, w_, ci, hk = 1, 4, 4, 3, 3
    x = jnp.arange(b * h * w_ * ci, dtype=jnp.float32).reshape(b, h, w_, ci)
    pat = im2col(x, hk, hk, padding="SAME")
    # center patch (1,1) feature vector vs manual window
    manual = []
    for c in range(ci):
        for dy in range(hk):
            for dx in range(hk):
                manual.append(float(x[0, dy, dx, c]))
    np.testing.assert_allclose(np.asarray(pat[0, 1, 1]), manual)


@pytest.mark.parametrize("b,s,kh,dh", [(2, 16, 1, 8), (4, 32, 2, 16),
                                       (3, 17, 5, 4)])
def test_kv_cache_update_kernel(b, s, kh, dh):
    """Plane-layout [P, S, dh] row write: pallas == xla == mask oracle,
    and each plane's position is honoured independently."""
    from repro.kernels.kv_cache_update import (kv_cache_update_pallas,
                                               kv_cache_update_ref,
                                               kv_cache_update_xla)
    p = b * kh
    r = np.random.default_rng(b * 100 + s)
    cache = jnp.asarray(r.standard_normal((p, s, dh)), jnp.float32)
    new = jnp.asarray(r.standard_normal((p, dh)), jnp.float32)
    pos = jnp.asarray(r.integers(0, s, p), jnp.int32)
    want = kv_cache_update_ref(cache, new, pos)
    got = kv_cache_update_pallas(cache, new, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    got_xla = kv_cache_update_xla(cache, new, pos)
    np.testing.assert_allclose(np.asarray(got_xla), np.asarray(want))


def test_kv_cache_update_kernel_carries_its_name():
    """Named like the balanced-spmm kernels, so a device trace credits it
    to the KV write.  It does not lower for Mosaic yet (its [1, dh] row
    block breaks the (8, 128) tiling), so the name is read from the
    jaxpr, not from compiled v5e text."""
    from repro.kernels.kv_cache_update import kv_cache_update_pallas
    jaxpr = jax.make_jaxpr(kv_cache_update_pallas)(
        jnp.zeros((4, 16, 8)), jnp.zeros((4, 8)), jnp.zeros((4,), jnp.int32))
    [eqn] = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert eqn.params["name"] == "kv_cache_update"
    assert dict(eqn.params["metadata"]) == {"kernel": "kv_cache_update"}


def test_kv_cache_plane_roundtrip_and_chunk_write():
    from repro.kernels.kv_cache_update import (from_planes, to_planes,
                                               kv_cache_update_xla,
                                               kv_cache_write_chunk)
    r = np.random.default_rng(7)
    kv = jnp.asarray(r.standard_normal((3, 12, 2, 4)), jnp.float32)
    np.testing.assert_array_equal(np.asarray(from_planes(to_planes(kv), 2)),
                                  np.asarray(kv))
    # a C-token chunk write == C sequential single-row writes
    cache = jnp.asarray(r.standard_normal((6, 12, 4)), jnp.float32)
    new = jnp.asarray(r.standard_normal((6, 3, 4)), jnp.float32)
    pos = jnp.asarray(r.integers(0, 12 - 3, 6), jnp.int32)
    got = kv_cache_write_chunk(cache, new, pos)
    want = cache
    for i in range(3):
        want = kv_cache_update_xla(want, new[:, i], pos + i)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ssd_chunked_matches_scan():
    """The beyond-paper chunk-parallel SSD == the sequential recurrence."""
    from repro.models.zamba2 import _ssd_chunked, _ssd_scan
    r = np.random.default_rng(1)
    b, t, h, dh, n = 2, 64, 2, 8, 4
    x = jnp.asarray(r.standard_normal((b, t, h, dh)), jnp.float32)
    dt = jnp.asarray(r.random((b, t, h)) * 0.5 + 0.1, jnp.float32)
    a = jnp.asarray(np.exp(-r.random((b, t, h)) * 0.8), jnp.float32)
    B = jnp.asarray(r.standard_normal((b, t, n)), jnp.float32)
    C = jnp.asarray(r.standard_normal((b, t, n)), jnp.float32)
    s0 = jnp.asarray(r.standard_normal((b, h, dh, n)) * 0.2, jnp.float32)
    y1, s1 = _ssd_scan(x, dt, a, B, C, s0, chunk=16)
    y2, s2 = _ssd_chunked(x, dt, a, B, C, s0, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


def test_wkv_chunked_matches_scan():
    """Chunk-parallel WKV (rwkv6) == the sequential recurrence."""
    from repro.models.rwkv6 import _wkv_chunked, _wkv_scan
    r_ = np.random.default_rng(2)
    b, t, h, dh = 2, 64, 2, 8
    r = jnp.asarray(r_.standard_normal((b, t, h, dh)), jnp.float32)
    k = jnp.asarray(r_.standard_normal((b, t, h, dh)), jnp.float32)
    v = jnp.asarray(r_.standard_normal((b, t, h, dh)), jnp.float32)
    w = jnp.asarray(np.exp(-np.exp(
        r_.standard_normal((b, t, h, dh)) * 0.5 - 2.0)), jnp.float32)
    u = jnp.asarray(r_.standard_normal((h, dh)) * 0.1, jnp.float32)
    s0 = jnp.asarray(r_.standard_normal((b, h, dh, dh)) * 0.2, jnp.float32)
    y1, s1 = _wkv_scan(r, k, v, w, u, s0, chunk=16)
    y2, s2 = _wkv_chunked(r, k, v, w, u, s0, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)
