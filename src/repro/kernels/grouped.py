"""Pallas TPU kernels: grouped-expert variants of the SLaB fused matmuls.

MoE serving hands each expert its own post-dispatch token block — the
capacity-dispatch einsum produces ``(G, E, C, D)`` buffers, flattened
here to x ``(E, M, K)`` with a matching per-expert weight plane stacked
on a leading E axis. One ``pallas_call`` covers a whole expert bucket:
the grid grows a **leading expert dimension** and every BlockSpec gains
a length-1 expert block, so grid step ``(e, i, j[, k])`` streams expert
``e``'s weight tile against expert ``e``'s x tile. K stays the
innermost grid axis for the scratch-accumulator kernels (sequential TPU
grid order ⇒ the fp32 VMEM accumulator carries across K steps exactly
as in the 2-D kernels, re-initialised at ``k == 0`` per (e, i, j)).

The bodies reuse the 2-D kernels' compute helpers verbatim — the only
deltas are the ``ref[0]`` expert-block squeeze on loads, the ``[None]``
on the output store, and ``pl.program_id(3)`` for K. Experts in one
launch share static shape metadata (same variant / rank / ELL K_max pad
— `packed_model.ExpertPackedStack` groups experts into buckets by
realized K_max so ragged experts never pad to the global max).
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
from repro.kernels.ell import _auto_jc, _gather_accum
from repro.kernels.ell import _Acc

Array = jax.Array


def _espec(block, imap):
    """BlockSpec with a leading length-1 expert block: grid step e owns
    expert plane e; ``imap`` gives the 2-D kernel's index map over the
    remaining grid axes."""
    return pl.BlockSpec((1,) + tuple(block),
                        lambda e, *ij: (e,) + tuple(imap(*ij)))


# --------------------------- ELL family (no K grid) --------------------

def _kernel_ell_g(x_ref, val_ref, idx_ref, o_ref, *, jc: int):
    acc = _gather_accum(x_ref[0], val_ref[0], idx_ref[0], jc)
    o_ref[...] = acc.astype(o_ref.dtype)[None]


def ell_matmul_g(x: Array, vals: Array, idx: Array,
                 *, bm: int = 128, bn: int = 256,
                 jc=None, interpret: bool = False) -> Array:
    """x (E, M, K); vals/idx (E, N, K_max) -> (E, M, N)."""
    e, m, k = x.shape
    _, n, k_max = vals.shape
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0, (x.shape, vals.shape, bm, bn)
    kernel = functools.partial(_kernel_ell_g,
                               jc=jc or _auto_jc(bm, bn, k_max))
    return pl.pallas_call(
        kernel,
        grid=(e, m // bm, n // bn),
        in_specs=[
            _espec((bm, k), lambda i, j: (i, 0)),
            _espec((bn, k_max), lambda i, j: (j, 0)),
            _espec((bn, k_max), lambda i, j: (j, 0)),
        ],
        out_specs=_espec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), x.dtype),
        interpret=interpret,
    )(x, vals, idx)


def _kernel_ell_lr_g(x_ref, val_ref, idx_ref, u_ref, v_ref, o_ref,
                     *, jc: int):
    x = x_ref[0]
    acc = _gather_accum(x, val_ref[0], idx_ref[0], jc)
    p = jax.lax.dot_general(
        x.astype(jnp.float32), v_ref[0].astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y = acc + jax.lax.dot_general(
        p, u_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)[None]


def ell_lr_matmul_g(x: Array, vals: Array, idx: Array, u: Array, v: Array,
                    *, bm: int = 128, bn: int = 256,
                    jc=None, interpret: bool = False) -> Array:
    """ELL + rank-r low-rank per expert. u (E, R, N); v (E, R, K)."""
    e, m, k = x.shape
    _, n, k_max = vals.shape
    rank = u.shape[1]
    assert u.shape == (e, rank, n) and v.shape == (e, rank, k)
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0
    kernel = functools.partial(_kernel_ell_lr_g,
                               jc=jc or _auto_jc(bm, bn, k_max))
    return pl.pallas_call(
        kernel,
        grid=(e, m // bm, n // bn),
        in_specs=[
            _espec((bm, k), lambda i, j: (i, 0)),
            _espec((bn, k_max), lambda i, j: (j, 0)),
            _espec((bn, k_max), lambda i, j: (j, 0)),
            _espec((rank, bn), lambda i, j: (0, j)),
            _espec((rank, k), lambda i, j: (0, 0)),
        ],
        out_specs=_espec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), x.dtype),
        interpret=interpret,
    )(x, vals, idx, u, v)


def _kernel_slab_ell_g(x_ref, val_ref, idx_ref, bp_ref, u_ref, v_ref,
                       o_ref, *, jc: int, rank: int):
    x = x_ref[0]
    acc = _Acc(_gather_accum(x, val_ref[0], idx_ref[0], jc))
    b = unpack_bits_tile(bp_ref[0], x.dtype)
    accum_binlr_terms(acc, x, b, u_ref[0], v_ref[0], rank)
    o_ref[...] = acc[...].astype(o_ref.dtype)[None]


def slab_ell_matmul_g(x: Array, vals: Array, idx: Array, b_packed: Array,
                      u: Array, v: Array,
                      *, bm: int = 128, bn: int = 256,
                      jc=None, interpret: bool = False) -> Array:
    """Full SLaB with ELL sparse part, per expert. b_packed (E, K/32, N)."""
    e, m, k = x.shape
    _, n, k_max = vals.shape
    rank = u.shape[1]
    assert u.shape == (e, rank, n) and v.shape == (e, rank, k)
    assert b_packed.shape == (e, k // 32, n), (b_packed.shape, e, n, k)
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0 and k % 32 == 0
    kernel = functools.partial(_kernel_slab_ell_g,
                               jc=jc or _auto_jc(bm, bn, k_max), rank=rank)
    return pl.pallas_call(
        kernel,
        grid=(e, m // bm, n // bn),
        in_specs=[
            _espec((bm, k), lambda i, j: (i, 0)),
            _espec((bn, k_max), lambda i, j: (j, 0)),
            _espec((bn, k_max), lambda i, j: (j, 0)),
            _espec((k // 32, bn), lambda i, j: (0, j)),
            _espec((rank, bn), lambda i, j: (0, j)),
            _espec((rank, k), lambda i, j: (0, 0)),
        ],
        out_specs=_espec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), x.dtype),
        interpret=interpret,
    )(x, vals, idx, b_packed, u, v)


# ----------------------- K-gridded family (scratch) --------------------
#
# Grid (E, M/bm, N/bn, K/bk): K innermost so the VMEM accumulator
# carries across K steps of one (e, i, j) tile, exactly as at 2-D.

def _kernel_nm_g(x_ref, val_ref, idx_ref, o_ref, acc,
                 *, n_k: int, m_pat: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[0]
    w = expand_nm_tile(val_ref[0], idx_ref[0], m_pat, x.dtype)
    acc[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)[None]


def nm_matmul_g(x: Array, vals: Array, idx: Array, m_pat: int,
                *, bm: int = 256, bn: int = 256, bk: int = 512,
                interpret: bool = False) -> Array:
    """x (E, M, K); vals/idx (E, n, K/m, N) -> (E, M, N)."""
    e, m, k = x.shape
    _, n_keep, n_grp, n = vals.shape
    assert n_grp * m_pat == k
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % m_pat == 0
    bg = bk // m_pat
    grid = (e, m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel_nm_g, n_k=grid[3], m_pat=m_pat)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _espec((bm, bk), lambda i, j, kk: (i, kk)),
            _espec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
            _espec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
        ],
        out_specs=_espec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, vals, idx)


def _kernel_dense_g(x_ref, ws_ref, bp_ref, u_ref, v_ref, o_ref, acc,
                    *, n_k: int, rank: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[0]
    acc[...] += jax.lax.dot_general(
        x, ws_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    b = unpack_bits_tile(bp_ref[0], x.dtype)
    accum_binlr_terms(acc, x, b, u_ref[0], v_ref[0], rank)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)[None]


def slab_matmul_g(x: Array, w_s: Array, b_packed: Array, u: Array, v: Array,
                  *, bm: int = 256, bn: int = 256, bk: int = 512,
                  interpret: bool = False) -> Array:
    """Dense-masked SLaB per expert. w_s (E,N,K); b_packed (E,K/32,N)."""
    e, m, k = x.shape
    n = w_s.shape[1]
    rank = u.shape[1]
    assert u.shape == (e, rank, n) and v.shape == (e, rank, k)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % 32 == 0
    grid = (e, m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel_dense_g, n_k=grid[3], rank=rank)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _espec((bm, bk), lambda i, j, kk: (i, kk)),
            _espec((bn, bk), lambda i, j, kk: (j, kk)),
            _espec((bk // 32, bn), lambda i, j, kk: (kk, j)),
            _espec((rank, bn), lambda i, j, kk: (0, j)),
            _espec((rank, bk), lambda i, j, kk: (0, kk)),
        ],
        out_specs=_espec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_s, b_packed, u, v)


def _kernel_nm_full_g(x_ref, val_ref, idx_ref, bp_ref, u_ref, v_ref,
                      o_ref, acc, *, n_k: int, m_pat: int, rank: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[0]
    w = expand_nm_tile(val_ref[0], idx_ref[0], m_pat, x.dtype)
    acc[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    b = unpack_bits_tile(bp_ref[0], x.dtype)
    accum_binlr_terms(acc, x, b, u_ref[0], v_ref[0], rank)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)[None]


def slab_nm_matmul_g(x: Array, vals: Array, idx: Array, m_pat: int,
                     b_packed: Array, u: Array, v: Array,
                     *, bm: int = 256, bn: int = 256, bk: int = 512,
                     interpret: bool = False) -> Array:
    """N:M SLaB per expert. vals/idx (E, n, K/m, N)."""
    e, m, k = x.shape
    _, n_keep, n_grp, n = vals.shape
    assert n_grp * m_pat == k
    rank = u.shape[1]
    assert u.shape == (e, rank, n) and v.shape == (e, rank, k)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert (m % bm == 0 and n % bn == 0 and k % bk == 0
            and bk % 32 == 0 and bk % m_pat == 0)
    bg = bk // m_pat
    grid = (e, m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel_nm_full_g, n_k=grid[3],
                               m_pat=m_pat, rank=rank)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _espec((bm, bk), lambda i, j, kk: (i, kk)),
            _espec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
            _espec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
            _espec((bk // 32, bn), lambda i, j, kk: (kk, j)),
            _espec((rank, bn), lambda i, j, kk: (0, j)),
            _espec((rank, bk), lambda i, j, kk: (0, kk)),
        ],
        out_specs=_espec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, vals, idx, b_packed, u, v)


def _kernel_dense_lr_g(x_ref, ws_ref, u_ref, v_ref, o_ref, acc, acc_p,
                       *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        acc_p[...] = jnp.zeros_like(acc_p)

    x = x_ref[0]
    acc[...] += jax.lax.dot_general(
        x, ws_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    accum_lowrank_proj(acc_p, x, v_ref[0])

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = lowrank_epilogue(
            acc, acc_p, u_ref[0]).astype(o_ref.dtype)[None]


def slab_lr_matmul_g(x: Array, w_s: Array, u: Array, v: Array,
                     *, bm: int = 256, bn: int = 256, bk: int = 512,
                     interpret: bool = False) -> Array:
    """Dense-masked sparse + rank-r low-rank, no binary, per expert."""
    e, m, k = x.shape
    n = w_s.shape[1]
    rank = u.shape[1]
    assert u.shape == (e, rank, n) and v.shape == (e, rank, k)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    grid = (e, m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel_dense_lr_g, n_k=grid[3])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _espec((bm, bk), lambda i, j, kk: (i, kk)),
            _espec((bn, bk), lambda i, j, kk: (j, kk)),
            _espec((rank, bn), lambda i, j, kk: (0, j)),
            _espec((rank, bk), lambda i, j, kk: (0, kk)),
        ],
        out_specs=_espec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, rank), jnp.float32)],
        interpret=interpret,
    )(x, w_s, u, v)


def _kernel_nm_lr_g(x_ref, val_ref, idx_ref, u_ref, v_ref, o_ref,
                    acc, acc_p, *, n_k: int, m_pat: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        acc_p[...] = jnp.zeros_like(acc_p)

    x = x_ref[0]
    w = expand_nm_tile(val_ref[0], idx_ref[0], m_pat, x.dtype)
    acc[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)
    accum_lowrank_proj(acc_p, x, v_ref[0])

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = lowrank_epilogue(
            acc, acc_p, u_ref[0]).astype(o_ref.dtype)[None]


def slab_nm_lr_matmul_g(x: Array, vals: Array, idx: Array, m_pat: int,
                        u: Array, v: Array,
                        *, bm: int = 256, bn: int = 256, bk: int = 512,
                        interpret: bool = False) -> Array:
    """N:M sparse + rank-r low-rank, no binary, per expert."""
    e, m, k = x.shape
    _, n_keep, n_grp, n = vals.shape
    assert n_grp * m_pat == k
    rank = u.shape[1]
    assert u.shape == (e, rank, n) and v.shape == (e, rank, k)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % m_pat == 0
    bg = bk // m_pat
    grid = (e, m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel_nm_lr_g, n_k=grid[3], m_pat=m_pat)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _espec((bm, bk), lambda i, j, kk: (i, kk)),
            _espec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
            _espec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
            _espec((rank, bn), lambda i, j, kk: (0, j)),
            _espec((rank, bk), lambda i, j, kk: (0, kk)),
        ],
        out_specs=_espec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, rank), jnp.float32)],
        interpret=interpret,
    )(x, vals, idx, u, v)


def _kernel_binlr_g(x_ref, bp_ref, u_ref, v_ref, o_ref, acc,
                    *, n_k: int, rank: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[0]
    b = unpack_bits_tile(bp_ref[0], x.dtype)
    accum_binlr_terms(acc, x, b, u_ref[0], v_ref[0], rank)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc[...].astype(o_ref.dtype)[None]


def binlr_matmul_g(x: Array, b_packed: Array, u: Array, v: Array,
                   *, bm: int = 256, bn: int = 256, bk: int = 512,
                   interpret: bool = False) -> Array:
    """Binary ⊙ rank-r per expert. b_packed (E, K/32, N) uint32."""
    e, m, k = x.shape
    n = b_packed.shape[2]
    assert b_packed.shape[1] * 32 == k
    rank = u.shape[1]
    assert u.shape == (e, rank, n) and v.shape == (e, rank, k)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % 32 == 0
    grid = (e, m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel_binlr_g, n_k=grid[3], rank=rank)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _espec((bm, bk), lambda i, j, kk: (i, kk)),
            _espec((bk // 32, bn), lambda i, j, kk: (kk, j)),
            _espec((rank, bn), lambda i, j, kk: (0, j)),
            _espec((rank, bk), lambda i, j, kk: (0, kk)),
        ],
        out_specs=_espec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, b_packed, u, v)
