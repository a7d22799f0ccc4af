"""Pallas TPU kernels: row-padded ELL unstructured-sparse matmuls.

    y = x @ W_Sᵀ,   W_S streamed as (vals (N, K_max), idx (N, K_max))

ELL storage keeps each output row's non-zeros left-justified and padded
to the realized per-row maximum K_max (uint16 column ids, value 0 at a
zero column for pads), so at b=32 and 50% unstructured sparsity the
streamed bytes are (4+2)/2 = 3 per weight vs 4 dense — the format that
lets unstructured SLaB / HASSLE-free / Wanda layers beat dense bytes
without an N:M constraint.

The compute is a **gather-matmul**: for each (bm, bn) output tile the
kernel gathers x columns through the idx tile and contracts against the
value tile,

    y[m, o] = Σ_j x[m, idx[o, j]] · vals[o, j]

accumulated over K_max in chunks of ``jc`` so the gathered intermediate
stays (bm, bn, jc). Work is nnz-proportional (no dense rebuild, no
wasted zero MACs). K is NOT gridded: each grid step owns a full-K x
block, which the low-rank / binary fusions also consume in one pass:

  ell_matmul      — W_S only.
  ell_lr_matmul   — + rank-r low-rank, no binary: projection p = x @ Vᵀ
                    in one MXU pass, U applied as the epilogue.
  slab_ell_matmul — + binary ⊙ rank-r (full SLaB): the ±1 tile is
                    bit-unpacked once per (bn, K) block and consumed by
                    r rank-1 accumulations (kernels.common helpers).

TPU note: the column gather lowers to Mosaic dynamic-gather along
lanes; on CPU the kernels run in interpret mode (numerics-exact) like
the rest of the kernel family.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import accum_binlr_terms, unpack_bits_tile

Array = jax.Array


def _auto_jc(bm: int, bn: int, k_max: int) -> int:
    """Chunk K_max so the gathered (bm, bn, jc) intermediate stays under
    ~1 MB fp32 — bounds VMEM on TPU and peak working set in interpret."""
    return max(1, min(k_max, (1 << 18) // max(1, bm * bn)))


def _gather_chunk(xf, vals_c, idx_c):
    """One (bm, bn, jc) gather + contract -> (bm, bn) fp32 partial."""
    xg = jnp.take(xf, idx_c.astype(jnp.int32), axis=1)    # (bm, bn, jc)
    return jnp.sum(xg * vals_c.astype(jnp.float32)[None], axis=-1)


def _gather_accum(x, vals, idx, jc: int):
    """(bm, K) x, (bn, K_max) vals/idx -> (bm, bn) fp32 gather-matmul.

    Chunks of jc unroll statically when there are few (smoke/decode
    shapes); at realistic K_max the full chunks run under ONE
    fori_loop so the traced body stays O(1) in K_max, with a single
    static tail for the K_max % jc remainder."""
    bm = x.shape[0]
    bn, k_max = vals.shape
    acc = jnp.zeros((bm, bn), jnp.float32)
    xf = x.astype(jnp.float32)
    n_full, tail0 = k_max // jc, 0
    if n_full > 4:
        def chunk(i, acc):
            j0 = i * jc
            return acc + _gather_chunk(
                xf, jax.lax.dynamic_slice_in_dim(vals, j0, jc, 1),
                jax.lax.dynamic_slice_in_dim(idx, j0, jc, 1))
        acc = jax.lax.fori_loop(0, n_full, chunk, acc)
        tail0 = n_full * jc
    for j0 in range(tail0, k_max, jc):
        acc += _gather_chunk(xf, vals[:, j0:j0 + jc], idx[:, j0:j0 + jc])
    return acc


# ------------------------------ sparse only ----------------------------

def _kernel_ell(x_ref, val_ref, idx_ref, o_ref, *, jc: int):
    acc = _gather_accum(x_ref[...], val_ref[...], idx_ref[...], jc)
    o_ref[...] = acc.astype(o_ref.dtype)


def ell_matmul(x: Array, vals: Array, idx: Array,
               *, bm: int = 128, bn: int = 256,
               jc: Optional[int] = None,
               interpret: bool = False) -> Array:
    """x (M, K); vals (N, K_max); idx (N, K_max) uint16 -> (M, N)."""
    m, k = x.shape
    n, k_max = vals.shape
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0, (x.shape, vals.shape, bm, bn)

    grid = (m // bm, n // bn)
    kernel = functools.partial(_kernel_ell,
                               jc=jc or _auto_jc(bm, bn, k_max))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k_max), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, k_max), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, vals, idx)


# -------------------------- + rank-r low-rank --------------------------

def _kernel_ell_lr(x_ref, val_ref, idx_ref, u_ref, v_ref, o_ref,
                   *, jc: int):
    x = x_ref[...]
    acc = _gather_accum(x, val_ref[...], idx_ref[...], jc)
    p = jax.lax.dot_general(                  # (bm, R) = x @ v_blockᵀ
        x.astype(jnp.float32), v_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y = acc + jax.lax.dot_general(
        p, u_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def ell_lr_matmul(x: Array, vals: Array, idx: Array, u: Array, v: Array,
                  *, bm: int = 128, bn: int = 256,
                  jc: Optional[int] = None,
                  interpret: bool = False) -> Array:
    """ELL sparse + rank-r low-rank, no binary. u (R, N); v (R, K)."""
    m, k = x.shape
    n, k_max = vals.shape
    rank = u.shape[0]
    assert u.shape == (rank, n) and v.shape == (rank, k), (u.shape, v.shape)
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0

    grid = (m // bm, n // bn)
    kernel = functools.partial(_kernel_ell_lr,
                               jc=jc or _auto_jc(bm, bn, k_max))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k_max), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, k_max), lambda i, j: (j, 0)),
            pl.BlockSpec((rank, bn), lambda i, j: (0, j)),
            pl.BlockSpec((rank, k), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, vals, idx, u, v)


# ------------------------ + binary ⊙ rank-r ---------------------------

class _Acc:
    """Adapter so accum_binlr_terms's ``acc[...] +=`` protocol works on
    a plain array accumulator (this kernel has no K grid, hence no VMEM
    scratch carry — one body owns the whole reduction)."""

    def __init__(self, a):
        self.a = a

    def __getitem__(self, _):
        return self.a

    def __setitem__(self, _, val):
        self.a = val


def _kernel_slab_ell(x_ref, val_ref, idx_ref, bp_ref, u_ref, v_ref,
                     o_ref, *, jc: int, rank: int):
    x = x_ref[...]
    acc = _Acc(_gather_accum(x, val_ref[...], idx_ref[...], jc))
    b = unpack_bits_tile(bp_ref[...], x.dtype)
    accum_binlr_terms(acc, x, b, u_ref, v_ref, rank)
    o_ref[...] = acc[...].astype(o_ref.dtype)


def slab_ell_matmul(x: Array, vals: Array, idx: Array, b_packed: Array,
                    u: Array, v: Array,
                    *, bm: int = 128, bn: int = 256,
                    jc: Optional[int] = None,
                    interpret: bool = False) -> Array:
    """Full SLaB with ELL sparse part: y = x @ W_Sᵀ + Σ_r ((x⊙v_r) @ Bᵀ)⊙u_r."""
    m, k = x.shape
    n, k_max = vals.shape
    rank = u.shape[0]
    assert u.shape == (rank, n) and v.shape == (rank, k), (u.shape, v.shape)
    assert b_packed.shape == (k // 32, n), (b_packed.shape, n, k)
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0 and k % 32 == 0

    grid = (m // bm, n // bn)
    kernel = functools.partial(_kernel_slab_ell,
                               jc=jc or _auto_jc(bm, bn, k_max),
                               rank=rank)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k_max), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, k_max), lambda i, j: (j, 0)),
            pl.BlockSpec((k // 32, bn), lambda i, j: (0, j)),
            pl.BlockSpec((rank, bn), lambda i, j: (0, j)),
            pl.BlockSpec((rank, k), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, vals, idx, b_packed, u, v)
