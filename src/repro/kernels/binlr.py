"""Pallas TPU kernel: bit-packed binary ⊙ rank-r matmul.

    y (M, N) = Σ_r ((x ⊙ v_r) @ Bᵀ) ⊙ u_r,   B ∈ {±1} packed 32/uint32

HBM traffic for the B operand is 1/16th of bf16 — this is the term that
makes SLaB pay on a memory-bound TPU decode (DESIGN.md §3). The rank-r
generalization uses (U Vᵀ ⊙ B) x = Σ_r u_r ⊙ (B (v_r ⊙ x)): every rank
term reuses the ONE streamed/expanded B tile, so extra ranks cost MXU
passes but no extra HBM bytes beyond the (R·N + R·K) factor vectors.

Grid is (M/bm, N/bn, K/bk); each step streams a (bk/32, bn) uint32
tile, expands it to the ±1 (bk, bn) tile of Bᵀ in VMEM, and feeds the
MXU. fp32 accumulation in
VMEM scratch; ``u_r`` is folded into each step's rank term (it is
constant along K, so per-step scaling equals the end-scaling of the old
rank-1 kernel).

Block shapes on the chip: bn a multiple of 128 (D_out is the lane axis
of every streamed plane) and bk a multiple of 256, so the (bk/32, bn)
word tile fills whole (8, 128) uint32 tiles — or the full axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import accum_binlr_terms, unpack_bits_tile

Array = jax.Array


def _kernel(x_ref, bp_ref, u_ref, v_ref, o_ref, acc_ref,
            *, n_k: int, rank: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    b = unpack_bits_tile(bp_ref[...], x.dtype)         # (bn, bk) ±1
    accum_binlr_terms(acc_ref, x, b, u_ref, v_ref, rank)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def binlr_matmul(x: Array, b_packed: Array, u: Array, v: Array,
                 *, bm: int = 256, bn: int = 256, bk: int = 512,
                 interpret: bool = False) -> Array:
    """x (M, K); b_packed (K/32, N) uint32; u (R, N); v (R, K) -> (M, N)."""
    m, k = x.shape
    n = b_packed.shape[1]
    assert b_packed.shape[0] * 32 == k, (b_packed.shape, k)
    rank = u.shape[0]
    assert u.shape == (rank, n) and v.shape == (rank, k), (u.shape, v.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % 32 == 0

    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(_kernel, n_k=grid[2], rank=rank)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 32, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((rank, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((rank, bk), lambda i, j, kk: (0, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, b_packed, u, v)
