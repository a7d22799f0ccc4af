"""Pallas TPU kernels: the fused SLaB-family compressed linears.

    y = x @ W_Sᵀ + Σ_r ((x ⊙ v_r) @ Bᵀ) ⊙ u_r        (binary + rank-r)
    y = x @ W_Sᵀ + (x @ Vᵀ) @ U                      (no binary, rank-r)

One pass over K per output tile: every term shares the streamed x tile,
so x is read once (vs once per term for separate matmuls) and y is
written once. All accumulation is fp32 in VMEM scratch. The low-rank
factors arrive as row-major rank stacks u (R, N) / v (R, K) — R is
static and small (paper default 1; HASSLE-free-style decompositions use
r ≤ 16) — and the binary⊙rank-r identity

    (U Vᵀ ⊙ B) x = Σ_r u_r ⊙ (B (v_r ⊙ x))

lets the kernel accumulate r rank-1 binary terms against ONE streamed B
tile. Four variants:

  slab_matmul      — W_S dense-masked (unstructured sparsity) + binary.
  slab_nm_matmul   — W_S in N:M packed form + binary (the roofline win).
  slab_lr_matmul   — W_S dense-masked + rank-r low-rank, NO binary term
                     (HASSLE-free / SoLA-style decs): the low-rank path
                     accumulates x @ Vᵀ (bm, R) per K step and applies U
                     once on the last step — no B bytes, no ±1 expand.
  slab_nm_lr_matmul— N:M W_S + rank-r low-rank, no binary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (accum_binlr_terms, accum_lowrank_proj,
                                  expand_nm_tile, lowrank_epilogue,
                                  unpack_bits_tile)

Array = jax.Array


# ------------------------- dense-masked W_S -------------------------

def _kernel_dense(x_ref, ws_ref, bp_ref, u_ref, v_ref, o_ref,
                  acc, *, n_k: int, rank: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]
    acc[...] += jax.lax.dot_general(
        x, ws_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    b = unpack_bits_tile(bp_ref[...], x.dtype)
    accum_binlr_terms(acc, x, b, u_ref, v_ref, rank)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def slab_matmul(x: Array, w_s: Array, b_packed: Array, u: Array, v: Array,
                *, bm: int = 256, bn: int = 256, bk: int = 512,
                interpret: bool = False) -> Array:
    """x (M,K); w_s (N,K); b_packed (K/32,N); u (R,N); v (R,K) -> (M,N)."""
    m, k = x.shape
    n = w_s.shape[0]
    rank = u.shape[0]
    assert u.shape == (rank, n) and v.shape == (rank, k), (u.shape, v.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % 32 == 0

    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel_dense, n_k=grid[2], rank=rank)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bk // 32, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((rank, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((rank, bk), lambda i, j, kk: (0, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_s, b_packed, u, v)


# --------------------------- N:M packed W_S --------------------------

def _kernel_nm(x_ref, val_ref, idx_ref, bp_ref, u_ref, v_ref, o_ref,
               acc, *, n_k: int, m_pat: int, rank: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]
    w = expand_nm_tile(val_ref[...], idx_ref[...], m_pat, x.dtype)
    acc[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    b = unpack_bits_tile(bp_ref[...], x.dtype)
    accum_binlr_terms(acc, x, b, u_ref, v_ref, rank)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def slab_nm_matmul(x: Array, vals: Array, idx: Array, m_pat: int,
                   b_packed: Array, u: Array, v: Array,
                   *, bm: int = 256, bn: int = 256, bk: int = 512,
                   interpret: bool = False) -> Array:
    """N:M variant. vals/idx (n, K/m, N); b_packed (K/32, N); u (R, N);
    v (R, K)."""
    m, k = x.shape
    n_keep, n_grp, n = vals.shape
    assert n_grp * m_pat == k
    rank = u.shape[0]
    assert u.shape == (rank, n) and v.shape == (rank, k), (u.shape, v.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert (m % bm == 0 and n % bn == 0 and k % bk == 0
            and bk % 32 == 0 and bk % m_pat == 0)
    bg = bk // m_pat

    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel_nm, n_k=grid[2], m_pat=m_pat,
                               rank=rank)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((bk // 32, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((rank, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((rank, bk), lambda i, j, kk: (0, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, vals, idx, b_packed, u, v)


# ------------------- sparse + low-rank, no binary --------------------
#
# y = x @ W_Sᵀ + (x @ Vᵀ) @ U.  The low-rank term accumulates the
# projection p = x @ Vᵀ (bm, R) across K steps and applies the (R, bn)
# U tile once on the last step — one skinny MXU pass per K step plus
# one tiny (bm,R)@(R,bn) epilogue, no binary bytes at all.

def _kernel_dense_lr(x_ref, ws_ref, u_ref, v_ref, o_ref,
                     acc, acc_p, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        acc_p[...] = jnp.zeros_like(acc_p)

    x = x_ref[...]
    acc[...] += jax.lax.dot_general(
        x, ws_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    accum_lowrank_proj(acc_p, x, v_ref)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = lowrank_epilogue(acc, acc_p, u_ref).astype(o_ref.dtype)


def slab_lr_matmul(x: Array, w_s: Array, u: Array, v: Array,
                   *, bm: int = 256, bn: int = 256, bk: int = 512,
                   interpret: bool = False) -> Array:
    """x (M,K); w_s (N,K); u (R,N); v (R,K) -> (M,N). No binary term."""
    m, k = x.shape
    n = w_s.shape[0]
    rank = u.shape[0]
    assert u.shape == (rank, n) and v.shape == (rank, k), (u.shape, v.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0

    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel_dense_lr, n_k=grid[2])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((rank, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((rank, bk), lambda i, j, kk: (0, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, rank), jnp.float32)],
        interpret=interpret,
    )(x, w_s, u, v)


def _kernel_nm_lr(x_ref, val_ref, idx_ref, u_ref, v_ref, o_ref,
                  acc, acc_p, *, n_k: int, m_pat: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        acc_p[...] = jnp.zeros_like(acc_p)

    x = x_ref[...]
    w = expand_nm_tile(val_ref[...], idx_ref[...], m_pat, x.dtype)
    acc[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    accum_lowrank_proj(acc_p, x, v_ref)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = lowrank_epilogue(acc, acc_p, u_ref).astype(o_ref.dtype)


def slab_nm_lr_matmul(x: Array, vals: Array, idx: Array, m_pat: int,
                      u: Array, v: Array,
                      *, bm: int = 256, bn: int = 256, bk: int = 512,
                      interpret: bool = False) -> Array:
    """N:M sparse + rank-r low-rank, no binary. vals/idx (n, K/m, N)."""
    m, k = x.shape
    n_keep, n_grp, n = vals.shape
    assert n_grp * m_pat == k
    rank = u.shape[0]
    assert u.shape == (rank, n) and v.shape == (rank, k), (u.shape, v.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % m_pat == 0
    bg = bk // m_pat

    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel_nm_lr, n_k=grid[2], m_pat=m_pat)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((rank, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((rank, bk), lambda i, j, kk: (0, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, rank), jnp.float32)],
        interpret=interpret,
    )(x, vals, idx, u, v)
