"""Storage packing for SLaB components — the formats the Pallas kernels
stream from HBM.

- sign bits:   W_B {±1} -> uint32 words (Di/32, Do), 32 signs/word
               along D_in (16x smaller than bf16; bit j of word (g, o)
               is W_B[o, g*32+j]).
- N:M packed:  W_S (2:4 / 4:8) -> values (n, Di/m, Do) + int8 indices
               (position of each kept element inside its m-group).

Every plane that the matmul kernels stream keeps D_out as its minor
(lane) axis, so a kernel tile is (rows along D_in, bn along D_out) and
the TPU's (8, 128) tiling never pads a short minor axis (a 2-wide slot
axis or a D_in/32-wide word axis would).
- ELL packed:  unstructured W_S -> row-padded values (Do, K_max) +
               uint16 column indices (uint32 when D_in > 65535),
               K_max = realized max per-row nnz (short rows pad with
               value 0 at a zero column).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


# ------------------------------ sign bits ------------------------------

def pack_sign_bits(w_b: Array) -> Array:
    """Pack ±1 (or bool 'is positive') (Do, Di) into uint32 words
    (Di/32, Do): bit j of word (g, o) is the sign of W_B[o, g*32+j].

    D_in must be a multiple of 32 (true for every assigned architecture).
    """
    d_out, d_in = w_b.shape
    if d_in % 32:
        raise ValueError(f"D_in={d_in} not a multiple of 32")
    pos = (w_b > 0).astype(jnp.uint32).T.reshape(d_in // 32, 32, d_out)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(pos << shifts[None, :, None], axis=1).astype(jnp.uint32)


def unpack_sign_bits(packed: Array, d_in: int, dtype=jnp.int8) -> Array:
    """Inverse of pack_sign_bits: uint32 words -> ±1 matrix (Do, d_in)."""
    words, d_out = packed.shape
    if words * 32 != d_in:
        raise ValueError(f"{words} words cannot hold D_in={d_in}")
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (packed[:, None, :] >> shifts[None, :, None]) & jnp.uint32(1)
    pm = bits.astype(jnp.int32) * 2 - 1
    return pm.reshape(d_in, d_out).T.astype(dtype)


# ------------------------------ N:M packing ----------------------------

class NMPacked(NamedTuple):
    values: Array   # (n, Di // m, Do): slot s of group g of row o
    indices: Array  # (n, Di // m, Do) int8, position within the m-group
    n: int
    m: int
    d_in: int


def pack_nm(w_s: Array, n: int, m: int, strict: bool = False) -> NMPacked:
    """Pack an N:M-sparse dense-masked matrix. Rows whose group has fewer
    than n non-zeros are padded with (value 0, index = smallest unused).

    ``strict=True`` raises if any m-group holds MORE than n non-zeros
    (the pack would silently drop values) — the guard the plan-driven
    packer uses against a rule pattern that disagrees with what the
    compressor actually produced.

    Works on one (D_in/m, D_out) plane per position in the group, so
    D_out stays the minor axis throughout (an (..., m) layout would pad
    m to 128 lanes on a TPU). Slot order: a group's non-zeros by
    position, then its zeros by position; slot s takes the position
    whose rank in that order is s."""
    d_out, d_in = w_s.shape
    if d_in % m:
        raise ValueError(f"D_in={d_in} not divisible by m={m}")
    planes = w_s.reshape(d_out, d_in // m, m).transpose(2, 1, 0)
    nz = [planes[p] != 0 for p in range(m)]
    if strict:
        worst = int(jnp.max(sum(z.astype(jnp.int32) for z in nz)))
        if worst > n:
            raise ValueError(
                f"matrix is not {n}:{m} sparse (a group holds {worst} "
                f"non-zeros; packing would drop values)")
    key = [jnp.where(nz[p], p, m + p) for p in range(m)]
    rank = [sum((key[q] < key[p]).astype(jnp.int32)
                for q in range(m) if q != p) for p in range(m)]
    zero = jnp.zeros((), w_s.dtype)
    vals = jnp.stack([sum(jnp.where(rank[p] == s, planes[p], zero)
                          for p in range(m)) for s in range(n)])
    idx = jnp.stack([sum(jnp.where(rank[p] == s, p, 0) for p in range(m))
                     for s in range(n)]).astype(jnp.int8)
    return NMPacked(vals, idx, n, m, d_in)


def unpack_nm(p: NMPacked) -> Array:
    planes = [sum(jnp.where(p.indices[s] == q, p.values[s],
                            jnp.zeros((), p.values.dtype))
                  for s in range(p.n)) for q in range(p.m)]
    d_out = p.values.shape[-1]
    return jnp.stack(planes).transpose(2, 1, 0).reshape(d_out, p.d_in)


def nm_packed_bits(p: NMPacked, bits: int = 16) -> int:
    """Storage cost: values at b bits + ceil(log2(m)) bits per index."""
    import math
    idx_bits = max(1, math.ceil(math.log2(p.m)))
    return p.values.size * bits + p.indices.size * idx_bits


# ------------------------------ ELL packing ----------------------------

class ELLPacked(NamedTuple):
    values: Array   # (Do, K_max)
    indices: Array  # (Do, K_max) column ids: uint16 (2 bytes — the reason
    d_in: int       # ELL beats dense bytes at 50% unstructured sparsity),
                    # widened to uint32 when D_in overflows 16 bits.


def ell_row_nnz_max(w_s: Array) -> int:
    """Realized K_max of a sparse matrix: the largest per-row nnz (the
    ELL pad width). Device sync — pack-time only."""
    return max(1, int(jnp.max(jnp.sum(w_s != 0, axis=1))))


_ELL_MAX_DIN = 2 ** 16   # uint16 column-id ceiling; wider rows use uint32


def ell_idx_itemsize(d_in: int) -> int:
    """Bytes per ELL column index: 2 (uint16) while indices fit 16 bits,
    4 (uint32) for wider linears (e.g. nemotron_4_340b d_ff)."""
    return 2 if d_in <= _ELL_MAX_DIN else 4


def ell_wins_bytes(k_max: int, d_in: int, itemsize: int = 4) -> bool:
    """True when row-padded ELL (values at ``itemsize`` bytes + uint16 or
    uint32 indices, whichever D_in requires) stores strictly fewer bytes
    than the dense matrix."""
    return k_max * (itemsize + ell_idx_itemsize(d_in)) < d_in * itemsize


def ell_pack(w_s: Array, nnz: int | None = None) -> ELLPacked:
    """Row-padded ELL: keep each row's ``nnz`` largest-magnitude entries
    (default: the realized per-row max, so nothing is dropped). Short
    rows pad with (value 0, index of some zero column). Column indices
    are uint16, widened to uint32 when D_in > 65535 (they would wrap)."""
    d_out, d_in = w_s.shape
    idx_dtype = jnp.uint16 if d_in <= _ELL_MAX_DIN else jnp.uint32
    if nnz is None:
        nnz = ell_row_nnz_max(w_s)
    keys = jnp.where(w_s != 0, -jnp.abs(w_s.astype(jnp.float32)), jnp.inf)
    idx = jnp.argsort(keys, axis=1)[:, :nnz].astype(jnp.int32)
    idx = jnp.sort(idx, axis=1)
    vals = jnp.take_along_axis(w_s, idx, axis=1)
    return ELLPacked(vals, idx.astype(idx_dtype), d_in)


def ell_unpack(p: ELLPacked) -> Array:
    d_out, nnz = p.values.shape
    rows = jnp.arange(d_out)[:, None]
    out = jnp.zeros((d_out, p.d_in), p.values.dtype)
    return out.at[rows, p.indices.astype(jnp.int32)].add(p.values)

