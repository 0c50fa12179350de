"""Per-kernel Pallas tests: shape/dtype sweeps, assert_allclose vs the
pure-jnp oracles in ref.py (interpret=True executes the kernel body on
CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _mk_qkv(rng, b, s, h, kv, d, dtype):
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, kv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, kv, d)), dtype)
    return q, k, v


def _ref_out(q, k, v, causal, window):
    b, s, h, d = q.shape
    kv = k.shape[2]
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
    qb = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kb = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vb = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    pos = jnp.arange(s, dtype=jnp.int32)
    o = ref.flash_attention_ref(qb, kb, vb, pos, pos, causal=causal,
                                window=window)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("s,d,h,kv", [
    (256, 64, 4, 4),
    (256, 128, 4, 2),   # GQA
    (512, 64, 2, 1),    # MQA
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(s, d, h, kv, causal):
    rng = np.random.default_rng(s + d)
    q, k, v = _mk_qkv(rng, 2, s, h, kv, d, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    want = _ref_out(q, k, v, causal, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_window():
    rng = np.random.default_rng(7)
    q, k, v = _mk_qkv(rng, 1, 384, 2, 2, 64, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=100,
                              interpret=True)
    want = _ref_out(q, k, v, True, 100)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(8)
    q, k, v = _mk_qkv(rng, 1, 256, 2, 2, 64, jnp.bfloat16)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = _ref_out(q.astype(jnp.float32), k.astype(jnp.float32),
                    v.astype(jnp.float32), True, None)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=3e-2, rtol=3e-2)


def test_flash_attention_block_sweep():
    rng = np.random.default_rng(9)
    q, k, v = _mk_qkv(rng, 1, 512, 2, 2, 64, jnp.float32)
    want = _ref_out(q, k, v, True, None)
    for bq, bk in [(128, 128), (256, 128), (128, 256), (64, 64)]:
        out = ops.flash_attention(q, k, v, causal=True, block_q=bq,
                                  block_k=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"blocks {bq}x{bk}")


@pytest.mark.parametrize("n", [512, 1024, 4096, 5000])
def test_radix_hist_kernel(n):
    rng = np.random.default_rng(n)
    d = jnp.asarray(rng.integers(0, 256, n), jnp.int32)
    rank, hist = ops.bucket_rank_hist(d, interpret=True)
    rr, hr = ref.bucket_rank_hist_ref(d)
    assert np.array_equal(np.asarray(rank), np.asarray(rr))
    assert np.array_equal(np.asarray(hist), np.asarray(hr))


def test_radix_argsort_kernel_matches_core():
    rng = np.random.default_rng(3)
    keys = jnp.asarray(rng.integers(0, 2 ** 32, 3000, dtype=np.uint32))
    perm = ops.radix_argsort_u32(keys, interpret=True)
    srt = np.asarray(keys)[np.asarray(perm)]
    assert np.array_equal(srt, np.sort(np.asarray(keys)))


@pytest.mark.parametrize("l,w", [(100, 1), (1024, 2), (2000, 4)])
def test_bitmap_intersect(l, w):
    rng = np.random.default_rng(l + w)
    m1 = jnp.asarray(rng.integers(0, 2 ** 32, (l, w), dtype=np.uint32))
    m2 = jnp.asarray((rng.integers(0, 2 ** 32, (l, w), dtype=np.uint32)
                      * (rng.random((l, w)) < 0.2)).astype(np.uint32))
    out = ops.bitmap_intersect_any(m1, m2, interpret=True)
    want = ref.bitmap_intersect_any_ref(m1, m2)
    assert np.array_equal(np.asarray(out), np.asarray(want))


def _random_edges(n, m, seed):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n, m).astype(np.int32)
    v = ((u + 1 + rng.integers(0, n - 1, m)) % n).astype(np.int32)
    w = rng.lognormal(0.0, 1.0, m).astype(np.float32)
    return u, v, w


@pytest.mark.parametrize("n,m,p,block", [
    (40, 64, 8, 64),
    (64, 300, 16, 128),
    (100, 257, 4, 128),   # non-block-multiple edge count
    (128, 1000, 1, 512),
])
def test_spmv_kernel_matches_ref(n, m, p, block):
    """Laplacian spmv kernel == plain gather/scatter ref. float32 sums
    accumulate in different orders (one-hot matmul vs scatter-add), so
    allclose, not bit-equal — same contract as flash_attention."""
    u, v, w = _random_edges(n, m, seed=n + m)
    rng = np.random.default_rng(p)
    x = jnp.asarray(rng.standard_normal((n, p)), jnp.float32)
    uj, vj, wj = jnp.asarray(u), jnp.asarray(v), jnp.asarray(w)
    out = ops.laplacian_spmv_edges(uj, vj, wj, x, block=block,
                                   interpret=True)
    want = ref.laplacian_spmv_ref(uj, vj, wj, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    # a Laplacian annihilates constants: L·1 = 0
    ones = jnp.ones((n, p), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.laplacian_spmv_edges(uj, vj, wj, ones,
                                            block=block, interpret=True)),
        0.0, atol=1e-4)


def test_spmv_kernel_degenerate_edges():
    """m == 0 returns zeros; zero-weight slots (the padding convention)
    contribute exactly nothing."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal((16, 4)),
                    jnp.float32)
    z = jnp.zeros((0,), jnp.int32)
    out = ops.laplacian_spmv_edges(z, z, jnp.zeros((0,), jnp.float32), x,
                                   interpret=True)
    assert np.array_equal(np.asarray(out), np.zeros((16, 4), np.float32))
    u, v, w = _random_edges(16, 40, seed=3)
    keep = np.random.default_rng(4).random(40) < 0.5
    wz = np.where(keep, w, 0.0).astype(np.float32)
    out_masked = ops.laplacian_spmv_edges(
        jnp.asarray(u), jnp.asarray(v), jnp.asarray(wz), x,
        block=32, interpret=True)
    want = ref.laplacian_spmv_ref(jnp.asarray(u[keep]),
                                  jnp.asarray(v[keep]),
                                  jnp.asarray(w[keep]), x)
    np.testing.assert_allclose(np.asarray(out_masked), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_spmv_kernel_through_estimator():
    """The estimator's use_spmv_kernel path lands allclose to the
    default segment-sum path at the program level (same probes, same
    filter, different spmv engine)."""
    from repro.core.spectral_probe import probe_edge_resistance

    from repro.core.graph import random_connected_graph

    g = random_connected_graph(48, 96, seed=9)
    a = np.asarray(probe_edge_resistance(g.u, g.v, g.w, g.n,
                                         n_probes=32, n_iters=32, seed=1))
    b = np.asarray(probe_edge_resistance(g.u, g.v, g.w, g.n,
                                         n_probes=32, n_iters=32, seed=1,
                                         use_spmv_kernel=True))
    np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("kernel", ["spmv"])
def test_kernel_vmem_guard_raises_at_trace_time(kernel):
    """A size past the kernel's VMEM regime is refused while tracing,
    with a message naming the kernel — never handed to the compiler."""
    i32 = jax.ShapeDtypeStruct((256,), jnp.int32)
    args = (i32, i32, jax.ShapeDtypeStruct((256,), jnp.float32),
            jax.ShapeDtypeStruct((1 << 16, 64), jnp.float32))
    fn = ops.laplacian_spmv_edges
    with pytest.raises(ValueError, match=fn.__name__):
        jax.eval_shape(lambda *a: fn(*a, interpret=False), *args)
