"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing, slab
from repro.core.packed_model import pack_linear, packed_matmul
from repro.core.slab import SLaBConfig
from repro.kernels import ops, ref

SHAPES = [   # (M, N, K, bm, bn, bk)
    (32, 64, 128, 32, 32, 64),
    (64, 128, 256, 32, 64, 128),
    (128, 96, 320, 64, 32, 64),   # non-square, K not power of two
    (16, 256, 512, 16, 128, 256),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _mk(seed, m, n, k, dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = (jax.random.normal(kx, (m, k), jnp.float32)).astype(dtype)
    w = jax.random.normal(kw, (n, k), jnp.float32) * 0.05
    return x, w


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_binlr_matches_ref(shape, dtype):
    m, n, k, bm, bn, bk = shape
    x, w = _mk(0, m, n, k, dtype)
    dec = slab.slab_decompose(w, None, SLaBConfig(cr=0.5, iters=2))
    pk = pack_linear(dec, None)
    want = ref.binlr_ref(x.astype(jnp.float32), pk.b_packed, pk.u, pk.v)
    got = ops.binlr(x, pk.b_packed, pk.u, pk.v, bm=bm, bn=bn, bk=bk,
                    interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("pattern", ["2:4", "4:8"])
def test_nm_matmul_matches_ref(shape, pattern):
    m, n, k, bm, bn, bk = shape
    x, w = _mk(1, m, n, k, jnp.float32)
    dec = slab.slab_decompose(w, None,
                              SLaBConfig(cr=0.5, iters=2, pattern=pattern))
    pk = pack_linear(dec, pattern)
    want = ref.nm_matmul_ref(x, pk.sparse_vals, pk.sparse_idx, pk.m_pat)
    got = ops.nm_matmul(x, pk.sparse_vals, pk.sparse_idx, pk.m_pat,
                        bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("dtype", DTYPES, ids=("f32", "bf16"))
def test_slab_matmul_fused_matches_ref(shape, dtype):
    m, n, k, bm, bn, bk = shape
    x, w = _mk(2, m, n, k, dtype)
    dec = slab.slab_decompose(w, None, SLaBConfig(cr=0.5, iters=2))
    pk = pack_linear(dec, None)
    ws = dec.w_s.astype(dtype)
    want = ref.slab_matmul_ref(x.astype(jnp.float32),
                               dec.w_s, pk.b_packed, pk.u, pk.v)
    got = ops.slab_matmul(x, ws, pk.b_packed, pk.u, pk.v,
                          bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_slab_nm_matmul_matches_ref(shape):
    m, n, k, bm, bn, bk = shape
    x, w = _mk(3, m, n, k, jnp.float32)
    dec = slab.slab_decompose(w, None,
                              SLaBConfig(cr=0.5, iters=2, pattern="2:4"))
    pk = pack_linear(dec, "2:4")
    want = ref.slab_nm_matmul_ref(x, pk.sparse_vals, pk.sparse_idx,
                                  pk.m_pat, pk.b_packed, pk.u, pk.v)
    got = ops.slab_nm_matmul(x, pk.sparse_vals, pk.sparse_idx, pk.m_pat,
                             pk.b_packed, pk.u, pk.v,
                             bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_kernel_vs_dense_reconstruction():
    """End to end: fused kernel == x @ Ŵᵀ for the real decomposition."""
    x, w = _mk(4, 64, 128, 256, jnp.float32)
    dec = slab.slab_decompose(w, None, SLaBConfig(cr=0.5, iters=4))
    dense = x @ slab.reconstruct(dec).T
    got = packed_matmul(x, pack_linear(dec, None), interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=1e-4, atol=1e-4)


def _rank_factors(seed, n, k, r):
    ku, kv = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(ku, (n, r), jnp.float32) * 0.2,
            jax.random.normal(kv, (k, r), jnp.float32) * 0.2)


@pytest.mark.parametrize("rank", [2, 4])
def test_binlr_rank_r_matches_ref(rank):
    m, n, k, bm, bn, bk = 32, 64, 128, 32, 32, 64
    x, w = _mk(7, m, n, k, jnp.float32)
    bp = packing.pack_sign_bits(jnp.where(w >= 0, 1, -1).astype(jnp.int8))
    u, v = _rank_factors(8, n, k, rank)
    want = ref.binlr_ref(x, bp, u, v)
    got = ops.binlr(x, bp, u, v, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rank", [2, 4])
def test_slab_matmul_rank_r_matches_ref(rank):
    """rank-r SLaB: the fused kernel accumulates r rank-1 binary terms
    against one streamed B tile."""
    m, n, k, bm, bn, bk = 32, 64, 128, 32, 32, 64
    x, w = _mk(9, m, n, k, jnp.float32)
    dec = slab.slab_decompose(w, None, SLaBConfig(cr=0.5, iters=2))
    bp = packing.pack_sign_bits(dec.w_b)
    u, v = _rank_factors(10, n, k, rank)
    want = ref.slab_matmul_ref(x, dec.w_s, bp, u, v)
    got = ops.slab_matmul(x, dec.w_s, bp, u, v, bm=bm, bn=bn, bk=bk,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
@pytest.mark.parametrize("rank", [1, 4])
def test_slab_lr_matmul_matches_ref(shape, rank):
    """Sparse + rank-r low-rank, NO binary (HASSLE-free-style decs)."""
    m, n, k, bm, bn, bk = shape
    x, w = _mk(11, m, n, k, jnp.float32)
    dec = slab.slab_decompose(w, None, SLaBConfig(cr=0.5, iters=2))
    u, v = _rank_factors(12, n, k, rank)
    want = ref.slab_lr_matmul_ref(x, dec.w_s, u, v)
    got = ops.slab_lr_matmul(x, dec.w_s, u, v, bm=bm, bn=bn, bk=bk,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rank", [1, 4])
def test_slab_nm_lr_matmul_matches_ref(rank):
    m, n, k, bm, bn, bk = 32, 64, 128, 32, 32, 64
    x, w = _mk(13, m, n, k, jnp.float32)
    dec = slab.slab_decompose(w, None,
                              SLaBConfig(cr=0.5, iters=2, pattern="2:4"))
    pk = pack_linear(dec, "2:4")
    u, v = _rank_factors(14, n, k, rank)
    want = ref.slab_nm_lr_matmul_ref(x, pk.sparse_vals, pk.sparse_idx,
                                     pk.m_pat, u, v)
    got = ops.slab_nm_lr_matmul(x, pk.sparse_vals, pk.sparse_idx, pk.m_pat,
                                u, v, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_batched_leading_dims():
    """ops wrappers flatten (B, S, K) inputs."""
    x3 = jax.random.normal(jax.random.PRNGKey(5), (4, 8, 128), jnp.float32)
    _, w = _mk(6, 1, 64, 128, jnp.float32)
    dec = slab.slab_decompose(w, None, SLaBConfig(cr=0.5, iters=2))
    pk = pack_linear(dec, None)
    got = ops.slab_matmul(x3, dec.w_s, pk.b_packed, pk.u, pk.v,
                          bm=32, bn=32, bk=64, interpret=True)
    assert got.shape == (4, 8, 64)
    want = ref.slab_matmul_ref(x3.reshape(-1, 128), dec.w_s, pk.b_packed,
                               pk.u, pk.v).reshape(4, 8, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
