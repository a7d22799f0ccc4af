"""Shared in-VMEM unpack helpers for the SLaB Pallas kernels.

TPU adaptation (DESIGN.md §3): there is no XNOR-popcount datapath on the
MXU, so the binary matrix is *packed for bandwidth* (1 bit/elt in HBM)
and expanded to ±1 tiles in VMEM by VPU shift/mask ops; the MXU then
consumes dense bf16/f32 tiles. Same pattern for N:M sparse values:
(values, 2-bit indices) stream from HBM, a comparison-one-hot expand
rebuilds the dense tile in VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def unpack_bits_tile(packed: Array, dtype) -> Array:
    """(bk/32, bn) uint32 -> (bk, bn) ±1 in ``dtype`` (the Wᵀ tile).

    Word (g, o) holds the signs of columns g*32 .. g*32+31 of output row
    o, so testing bit j of every word against a per-sublane mask gives a
    (bk/32, 32, bn) block whose row-major merge is the tile in column
    order: D_out stays on lanes, and the merge only folds a leading dim
    into whole sublane tiles. One AND + compare + select per element;
    the select runs in f32 and the cast to ``dtype`` comes last."""
    words, bn = packed.shape
    masks = jnp.uint32(1) << jax.lax.broadcasted_iota(jnp.uint32,
                                                      (1, 32, 1), 1)
    pm = jnp.where((packed[:, None, :] & masks) != 0, 1.0, -1.0)
    return pm.reshape(words * 32, bn).astype(dtype)


def accum_binlr_terms(acc, x, b, u_ref, v_ref, rank: int) -> None:
    """acc += Σ_r ((x ⊙ v_r) @ b) ⊙ u_r for one (bm, bk) x tile and an
    already-expanded ±1 tile b (bk, bn) — Wᵀ orientation, so each term
    is a plain (bm, bk) @ (bk, bn) MXU pass; u_ref/v_ref hold
    (rank, bn) / (rank, bk) blocks. The Python loop over ranks unrolls
    at trace time; every term reuses the one expanded B tile, so extra
    ranks cost MXU passes, not HBM bytes. u_r is constant along K, so
    folding it into each step equals scaling once at the end."""
    for r in range(rank):
        xv = x * v_ref[r:r + 1, :]
        acc[...] += (jax.lax.dot_general(
            xv, b.astype(xv.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
            * u_ref[r:r + 1, :].astype(jnp.float32))


def accum_lowrank_proj(acc_p, x, v_ref) -> None:
    """acc_p (bm, R) += x @ v_blockᵀ for one K step of the no-binary
    low-rank kernels (v_ref holds an (R, bk) block); fp32 MXU pass."""
    acc_p[...] += jax.lax.dot_general(
        x.astype(jnp.float32), v_ref[...].astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def lowrank_epilogue(acc, acc_p, u_ref) -> Array:
    """Final-K-step combine of the no-binary kernels: sparse accumulator
    plus the rank-R projection applied through the (R, bn) U block."""
    return acc[...] + jax.lax.dot_general(
        acc_p[...], u_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def expand_nm_tile(vals: Array, idx: Array, m: int, dtype) -> Array:
    """(n, bg, bn) values + (n, bg, bn) int8 positions -> the dense Wᵀ
    tile (bg*m, bn).

    Comparison one-hot expand: dense[g, p, o] = Σ_s vals[s,g,o]·[idx==p].
    No scatter — pure VPU compares/selects on (bg, m, bn) blocks whose
    row-major merge puts column g*m+p at row g*m+p; D_out stays on
    lanes. Built in f32 and cast to ``dtype`` last."""
    n, bg, bn = vals.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, m, 1), 1)
    dense = None
    for s in range(n):
        hit = idx[s].astype(jnp.int32)[:, None, :] == pos
        term = jnp.where(hit, vals[s].astype(jnp.float32)[:, None, :], 0.0)
        dense = term if dense is None else dense + term
    return dense.reshape(bg * m, bn).astype(dtype)
