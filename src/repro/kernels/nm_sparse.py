"""Pallas TPU kernel: N:M semi-structured sparse matmul.

    y (M, N) = x @ W_Sᵀ,  W_S streamed as (values (n, K/m, N), idx int8)

2:4 at b=16 streams 9/16ths of the dense bytes (values + 2-bit indices,
int8-stored); the dense tile is rebuilt in VMEM by comparison-one-hot
expand (no scatter/gather — VPU compares only), then hits the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import expand_nm_tile

Array = jax.Array


def _kernel(x_ref, val_ref, idx_ref, o_ref, acc_ref, *, n_k: int, m_pat: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                        # (bm, bk)
    w = expand_nm_tile(val_ref[...], idx_ref[...], m_pat, x.dtype)  # (bk, bn)
    acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def nm_matmul(x: Array, vals: Array, idx: Array, m_pat: int,
              *, bm: int = 256, bn: int = 256, bk: int = 512,
              interpret: bool = False) -> Array:
    """x (M, K); vals/idx (n, K/m, N) -> (M, N)."""
    m, k = x.shape
    n_keep, n_grp, n = vals.shape
    assert n_grp * m_pat == k, (vals.shape, m_pat, k)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % m_pat == 0
    bg = bk // m_pat

    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel, n_k=grid[2], m_pat=m_pat)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((n_keep, bg, bn), lambda i, j, kk: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, vals, idx)
