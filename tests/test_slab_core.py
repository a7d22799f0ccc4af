"""Unit + property tests for the SLaB decomposition (paper Algorithm 1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:        # property tests skip without hypothesis
    from conftest import given, settings, strategies as st

from repro.core import baselines, packing, scores, slab, sparsity
from repro.core.apply import slab_linear
from repro.core.packed_model import pack_linear, packed_matmul
from repro.core.slab import SLaBConfig


def _w(key, d_out, d_in):
    return jax.random.normal(jax.random.PRNGKey(key), (d_out, d_in),
                             jnp.float32) * 0.05


def _an(key, d_in, n=64):
    x = jax.random.normal(jax.random.PRNGKey(key), (n, d_in), jnp.float32)
    return scores.act_col_norms(x)


# ------------------------- Eq. 9/10 accounting -------------------------

@settings(max_examples=25, deadline=None)
@given(cr=st.sampled_from([0.5, 0.6, 0.7, 0.8]),
       d_out=st.sampled_from([64, 128, 160]),
       d_in=st.sampled_from([64, 128, 256]))
def test_cr_accounting_property(cr, d_out, d_in):
    """Achieved compression ratio == requested CR (Eq. 9) within one
    element's worth of rounding."""
    w = _w(0, d_out, d_in)
    dec = slab.slab_decompose(w, None, SLaBConfig(cr=cr, iters=2))
    achieved = slab.compression_ratio(dec, bits=16)
    # floor() in the group top-k can only under-fill -> achieved >= cr
    assert achieved >= cr - 1e-6
    assert achieved - cr < 16.0 / d_in + 1e-6   # one group element slack


def test_keep_fraction_matches_paper_formula():
    f = slab.keep_fraction(0.5, 16, 4096, 4096)
    assert abs(f - (1 - 0.5 - 1 / 16 - 1 / 4096 - 1 / 4096)) < 1e-12
    with pytest.raises(ValueError):
        slab.keep_fraction(0.95, 16, 64, 64)   # infeasible budget


# ----------------------- decomposition invariants ----------------------

def test_lowrank_factors_nonnegative():
    """Prop. 2: rank-1 factors of |Y| are entry-wise >= 0."""
    w = _w(1, 96, 160)
    dec = slab.slab_decompose(w, _an(2, 160), SLaBConfig(cr=0.5, iters=5))
    assert bool(jnp.all(dec.u >= 0)) and bool(jnp.all(dec.v >= 0))


def test_binary_is_pm1():
    w = _w(3, 64, 128)
    dec = slab.slab_decompose(w, None, SLaBConfig(cr=0.5, iters=3))
    assert set(np.unique(np.asarray(dec.w_b))) <= {-1, 1}


def test_error_decreases_with_iterations():
    """Alternating optimization converges (Table II iterations trend)."""
    w = _w(4, 128, 256)
    an = _an(5, 256)
    errs = []
    for iters in (1, 5, 20):
        dec = slab.slab_decompose(w, an, SLaBConfig(cr=0.5, iters=iters))
        errs.append(float(slab.decomposition_error(w, dec, an)))
    assert errs[2] <= errs[0] + 1e-6
    assert errs[1] <= errs[0] + 1e-6


def test_slab_beats_wanda_same_budget():
    """The paper's core claim at the matrix level: at equal storage
    budget, SLaB reconstructs better than pruning alone."""
    w = _w(6, 128, 256)
    an = _an(7, 256)
    dec = slab.slab_decompose(w, an, SLaBConfig(cr=0.5, iters=10))
    err_slab = float(slab.decomposition_error(w, dec, an))
    wd = baselines.wanda_prune(w, an, 0.5)   # 50% nnz = same CR at b=16
    err_wanda = float(scores.weighted_fro_error(w, wd, an))
    assert err_slab < err_wanda


def test_rank0_equals_wanda():
    """Fig. 3: rank 0 (no W_L/W_B) degenerates to Wanda."""
    w = _w(8, 64, 128)
    an = _an(9, 128)
    cfg = SLaBConfig(cr=0.5, iters=1, include_binary=False,
                     include_lowrank=False)
    dec = slab.slab_decompose(w, an, cfg)
    keep = slab.keep_fraction(0.5, 16, 64, 128, include_binary=False,
                              include_lowrank=False)
    wd = baselines.wanda_prune(w, an, keep)
    np.testing.assert_allclose(np.asarray(dec.w_s), np.asarray(wd),
                               rtol=0, atol=1e-6)


# ------------------------------ sparsity -------------------------------

@settings(max_examples=30, deadline=None)
@given(keep=st.floats(0.1, 0.9),
       g_rows=st.sampled_from([1, 16, 32]),
       seed=st.integers(0, 5))
def test_group_topk_counts(keep, g_rows, seed):
    s = jnp.abs(_w(seed, 64, 128))
    mask = sparsity.group_topk_mask(s, keep, group=(g_rows, 0))
    gsz = g_rows * 128
    want = int(np.floor(keep * gsz))
    got = np.asarray(mask).reshape(64 // g_rows, -1).sum(1)
    assert (got == want).all()


@settings(max_examples=20, deadline=None)
@given(n=st.sampled_from([2, 4]), seed=st.integers(0, 5))
def test_nm_mask_structure(n, seed):
    m = 2 * n                      # 2:4 and 4:8
    s = jnp.abs(_w(seed, 32, 64))
    mask = sparsity.nm_mask(s, n, m)
    per_group = np.asarray(mask).reshape(32, 64 // m, m).sum(-1)
    assert (per_group == n).all()


def test_nm_then_group_respects_both():
    w = _w(10, 64, 128)
    dec = slab.slab_decompose(w, None, SLaBConfig(cr=0.5, iters=2,
                                                  pattern="2:4"))
    nz = np.asarray(dec.w_s != 0)
    assert (nz.reshape(64, 32, 4).sum(-1) <= 2).all()
    keep = slab.keep_fraction(0.5, 16, 64, 128)
    assert (nz.sum(1) == int(np.floor(keep * 128))).all()


def test_infeasible_nm_budget_raises():
    with pytest.raises(ValueError):
        sparsity.prune_mask(jnp.ones((8, 8)), 0.9, pattern="2:4")


# ------------------------------ packing --------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10), d_in=st.sampled_from([32, 64, 128]))
def test_signbit_roundtrip(seed, d_in):
    w = _w(seed, 16, d_in)
    b = jnp.where(w >= 0, 1, -1).astype(jnp.int8)
    packed = packing.pack_sign_bits(b)
    assert packed.shape == (d_in // 32, 16)
    out = packing.unpack_sign_bits(packed, d_in)
    assert bool(jnp.all(out == b))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10), n=st.sampled_from([2, 4]))
def test_nm_pack_roundtrip(seed, n):
    m = 2 * n
    w = _w(seed, 32, 64)
    mask = sparsity.nm_mask(jnp.abs(w), n, m)
    ws = jnp.where(mask, w, 0)
    p = packing.pack_nm(ws, n, m)
    out = packing.unpack_nm(p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ws), atol=0)


def test_ell_pack_roundtrip():
    w = _w(11, 64, 128)
    dec = slab.slab_decompose(w, None, SLaBConfig(cr=0.5, iters=2))
    nnz = sparsity.mask_nnz_per_row_uniform(dec.w_s != 0)
    assert nnz is not None          # (1, D_in) groups -> row-uniform
    p = packing.ell_pack(dec.w_s, nnz)
    np.testing.assert_allclose(np.asarray(packing.ell_unpack(p)),
                               np.asarray(dec.w_s), atol=0)


def test_packed_bits_match_eq9():
    """Packed storage cost stays within the CR budget of Eq. 9."""
    d_out, d_in, cr, b = 128, 256, 0.5, 16
    w = _w(12, d_out, d_in)
    dec = slab.slab_decompose(w, None, SLaBConfig(cr=cr, iters=3))
    bits = slab.compressed_bits(dec, bits=b)
    assert bits <= (1 - cr) * b * d_out * d_in + b  # <= budget


# ------------------------------ forward --------------------------------

def test_forward_equivalence_paths():
    w = _w(13, 96, 160)
    an = _an(14, 160)
    x = jax.random.normal(jax.random.PRNGKey(15), (24, 160), jnp.float32)
    dec = slab.slab_decompose(w, an, SLaBConfig(cr=0.5, iters=4))
    dense = x @ slab.reconstruct(dec).T
    y1 = slab_linear(x, dec)
    y2 = packed_matmul(x, pack_linear(dec, None), interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(dense),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(dense),
                               atol=2e-4)


# ------------------------------ baselines ------------------------------

def test_sparsegpt_better_than_magnitude():
    """Hessian-aware pruning beats magnitude on the layer-output error
    ‖X(W−Ŵ)ᵀ‖_F — with *correlated* activations (the LLM regime; with
    isotropic X the Hessian is ≈ identity and there is nothing for OBS
    to exploit)."""
    w = _w(16, 64, 128)
    z = jax.random.normal(jax.random.PRNGKey(17), (256, 16), jnp.float32)
    a = jax.random.normal(jax.random.PRNGKey(18), (16, 128), jnp.float32)
    sc = jnp.exp(jax.random.normal(jax.random.PRNGKey(19), (128,)))
    x = (z @ a) * sc[None, :] + \
        0.1 * jax.random.normal(jax.random.PRNGKey(20), (256, 128))
    ws = baselines.sparsegpt_prune(w, x.T @ x, 0.5)
    wm = baselines.magnitude_prune(w, 0.5)
    err_s = float(jnp.linalg.norm(x @ (w - ws).T))
    err_m = float(jnp.linalg.norm(x @ (w - wm).T))
    assert err_s < err_m
    assert abs(float(jnp.mean(ws != 0)) - 0.5) < 0.02


def test_sparsegpt_nm_pattern():
    w = _w(18, 32, 64)
    x = jax.random.normal(jax.random.PRNGKey(19), (128, 64), jnp.float32)
    ws = baselines.sparsegpt_prune(w, x.T @ x, 0.5, pattern="2:4")
    nz = np.asarray(ws != 0).reshape(32, 16, 4).sum(-1)
    assert (nz <= 2).all()


def test_streaming_act_norms():
    x = jax.random.normal(jax.random.PRNGKey(20), (96, 32), jnp.float32)
    acc = scores.ActNormAccumulator(32)
    for i in range(0, 96, 32):
        acc.update(x[i:i + 32])
    np.testing.assert_allclose(np.asarray(acc.norms()),
                               np.asarray(scores.act_col_norms(x)),
                               rtol=1e-5)
