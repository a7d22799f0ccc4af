"""Cross-variant packed-serving parity sweep: every PackedLinear
variant × rank ∈ {1, r} × pattern ∈ {2:4, 4:8} against the pure-jnp
oracles in kernels/ref.py, so a kernel or packer edit can't silently
break a (variant, rank, pattern) combination the targeted tests don't
hit. Each case checks three-way agreement: the fused kernel (interpret
mode), the ref oracle fed the PACKED arrays, and the dense-applied
decomposition."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.apply import slab_linear
from repro.core.packed_model import (PACKED_VARIANTS, ExpertPackedStack,
                                     expert_matmul, pack_linear,
                                     packed_matmul, variant_of)
from repro.core.packing import ell_row_nnz_max
from repro.core.slab import SLaBDecomposition
from repro.core.sparsity import prune_mask
from repro.kernels import ref

N, K = 64, 128          # K divisible by 32 (sign bits), 4 and 8 (N:M)
_HAS_LOWRANK = ("slab-nm", "slab-ell", "slab-dense", "binlr",
                "lowrank-nm", "lowrank-ell", "lowrank-dense", "lowrank")


def _dec(seed, variant, rank, pattern):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    w = jax.random.normal(ks[0], (N, K), jnp.float32) * 0.1
    if variant in ("binlr", "lowrank"):
        w_s = jnp.zeros((N, K), jnp.float32)
    elif variant.endswith("-nm"):
        w_s = jnp.where(prune_mask(jnp.abs(w), 0.4, pattern=pattern),
                        w, 0.0)
    else:
        # unstructured: keep 0.4 routes to ELL (bytes win); keep 0.75
        # exceeds the K_max < 2/3·K f32 threshold and stays dense
        keep = 0.4 if variant.endswith("-ell") else 0.75
        w_s = jnp.where(prune_mask(jnp.abs(w), keep), w, 0.0)
    if rank:
        u = jax.random.normal(ks[1], (N, rank), jnp.float32) * 0.2
        v = jax.random.normal(ks[2], (K, rank), jnp.float32) * 0.2
    else:
        u = jnp.zeros((N, 0), jnp.float32)
        v = jnp.zeros((K, 0), jnp.float32)
    if variant.startswith("slab-") or variant == "binlr":
        w_b = jnp.where(jax.random.bernoulli(ks[3], 0.5, (N, K)),
                        1, -1).astype(jnp.int8)
    else:
        w_b = jnp.zeros((0, 0), jnp.int8)
    return SLaBDecomposition(w_s, u, v, w_b)


def _ref_oracle(x, pl):
    """kernels/ref.py oracle for one packed linear, from the packed
    arrays themselves (exercises unpack_nm / unpack_sign_bits too)."""
    if pl.variant == "slab-nm":
        return ref.slab_nm_matmul_ref(x, pl.sparse_vals, pl.sparse_idx,
                                      pl.m_pat, pl.b_packed, pl.u, pl.v)
    if pl.variant == "slab-ell":
        return ref.slab_ell_matmul_ref(x, pl.sparse_vals, pl.sparse_idx,
                                       pl.d_in, pl.b_packed, pl.u, pl.v)
    if pl.variant == "slab-dense":
        return ref.slab_matmul_ref(x, pl.sparse_vals, pl.b_packed,
                                   pl.u, pl.v)
    if pl.variant == "binlr":
        return ref.binlr_ref(x, pl.b_packed, pl.u, pl.v)
    if pl.variant == "lowrank-nm":
        return ref.slab_nm_lr_matmul_ref(x, pl.sparse_vals, pl.sparse_idx,
                                         pl.m_pat, pl.u, pl.v)
    if pl.variant == "lowrank-ell":
        return ref.ell_lr_matmul_ref(x, pl.sparse_vals, pl.sparse_idx,
                                     pl.d_in, pl.u, pl.v)
    if pl.variant == "lowrank-dense":
        return ref.slab_lr_matmul_ref(x, pl.sparse_vals, pl.u, pl.v)
    if pl.variant == "lowrank":
        return ref.lowrank_ref(x, pl.u, pl.v)
    if pl.variant == "sparse-nm":
        return ref.nm_matmul_ref(x, pl.sparse_vals, pl.sparse_idx,
                                 pl.m_pat)
    if pl.variant == "sparse-ell":
        return ref.ell_matmul_ref(x, pl.sparse_vals, pl.sparse_idx,
                                  pl.d_in)
    assert pl.variant == "sparse-dense"
    return x.astype(jnp.float32) @ pl.sparse_vals.astype(jnp.float32).T


def _cases():
    out = []
    for variant in PACKED_VARIANTS:
        ranks = (1, 3) if variant in _HAS_LOWRANK else (0,)
        patterns = (("2:4", "4:8") if variant.endswith("-nm")
                    else (None,))
        for rank in ranks:
            for pattern in patterns:
                out.append(pytest.param(
                    variant, rank, pattern,
                    id=f"{variant}-r{rank}-{pattern or 'unstructured'}"))
    return out


@pytest.mark.parametrize("variant,rank,pattern", _cases())
def test_packed_matches_ref_and_dense_apply(variant, rank, pattern):
    dec = _dec(7, variant, rank, pattern)
    assert variant_of(dec, pattern) == variant
    pl = pack_linear(dec, pattern)
    assert pl.variant == variant and pl.rank == rank
    x = jax.random.normal(jax.random.PRNGKey(8), (8, K), jnp.float32)
    got = packed_matmul(x, pl, interpret=True)
    want_ref = _ref_oracle(x, pl)
    want_dense = slab_linear(x, dec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(want_ref),
                               np.asarray(want_dense),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("variant,rank,pattern", _cases())
def test_stacked_slice_preserves_variant(variant, rank, pattern):
    """Two stacked layers of one variant slice back to per-layer
    PackedLinears with identical aux metadata and numerics — the
    invariant the scanned serving path relies on."""
    pls = [pack_linear(_dec(s, variant, rank, pattern), pattern)
           for s in (11, 12)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *pls)
    assert stacked.variant == variant
    x = jax.random.normal(jax.random.PRNGKey(9), (4, K), jnp.float32)
    for i, pl in enumerate(pls):
        sl = jax.tree.map(lambda a: a[i], stacked)
        np.testing.assert_allclose(
            np.asarray(packed_matmul(x, sl, interpret=True)),
            np.asarray(packed_matmul(x, pl, interpret=True)),
            rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("variant", PACKED_VARIANTS)
def test_expert_matmul_matches_per_expert_loop(variant):
    """Three same-variant experts stacked on a leading expert dim:
    ``expert_matmul`` (the per-linear dispatch under ``jax.vmap``)
    equals a loop of ``packed_matmul`` over the experts, and each
    expert's ref oracle."""
    rank = 3 if variant in _HAS_LOWRANK else 0
    pattern = "2:4" if variant.endswith("-nm") else None
    decs = [_dec(s, variant, rank, pattern) for s in (21, 22, 23)]
    # ELL experts stack at one shared pad width, as pack_expert_stack
    # pads a bucket to its realized max
    nnz = (max(ell_row_nnz_max(d.w_s) for d in decs)
           if variant.endswith("-ell") else None)
    pls = [pack_linear(d, pattern, variant=variant, ell_nnz=nnz)
           for d in decs]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *pls)
    eps = ExpertPackedStack((stacked,), None, ((0, 1, 2),), (), 3)
    x = jax.random.normal(jax.random.PRNGKey(24), (3, 8, K), jnp.float32)
    got = expert_matmul(x, eps, interpret=True)
    assert got.shape == (3, 8, N)
    for e, pl in enumerate(pls):
        np.testing.assert_allclose(
            np.asarray(got[e]),
            np.asarray(packed_matmul(x[e], pl, interpret=True)),
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got[e]),
                                   np.asarray(_ref_oracle(x[e], pl)),
                                   rtol=1e-4, atol=1e-4)
