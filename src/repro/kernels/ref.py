"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Low-rank factors follow the ops-wrapper convention: ``u`` is (N,) or
(N, R) column factors, ``v`` is (K,) or (K, R).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.packing import (ELLPacked, NMPacked, ell_unpack, unpack_nm,
                                unpack_sign_bits)

Array = jax.Array


def _cols(u: Array) -> Array:
    """(N,) -> (N, 1); (N, R) passes through."""
    return u[:, None] if u.ndim == 1 else u


def binlr_ref(x: Array, b_packed: Array, u: Array, v: Array) -> Array:
    """y = Σ_r ((x ⊙ v_r) @ Bᵀ) ⊙ u_r — binary ⊙ rank-r term."""
    k = x.shape[-1]
    b = unpack_sign_bits(b_packed, k, dtype=jnp.float32)
    uu, vv = _cols(u).astype(jnp.float32), _cols(v).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    out = jnp.zeros((*x.shape[:-1], b.shape[0]), jnp.float32)
    for r in range(uu.shape[1]):
        out = out + ((xf * vv[:, r]) @ b.T) * uu[:, r]
    return out


def lowrank_ref(x: Array, u: Array, v: Array) -> Array:
    """y = (x @ V) @ Uᵀ — rank-r low-rank term, no binary."""
    uu, vv = _cols(u).astype(jnp.float32), _cols(v).astype(jnp.float32)
    return (x.astype(jnp.float32) @ vv) @ uu.T


def nm_matmul_ref(x: Array, vals: Array, idx: Array, m: int) -> Array:
    """y = x @ W_Sᵀ with W_S in N:M packed form (vals/idx (n, K/m, N))."""
    n = vals.shape[0]
    d_in = vals.shape[1] * m
    w = unpack_nm(NMPacked(vals, idx, n, m, d_in))
    return x.astype(jnp.float32) @ w.astype(jnp.float32).T


def ell_matmul_ref(x: Array, vals: Array, idx: Array, d_in: int) -> Array:
    """y = x @ W_Sᵀ with W_S in row-padded ELL form."""
    w = ell_unpack(ELLPacked(vals, idx, d_in))
    return x.astype(jnp.float32) @ w.astype(jnp.float32).T


def ell_lr_matmul_ref(x: Array, vals: Array, idx: Array, d_in: int,
                      u: Array, v: Array) -> Array:
    """ELL sparse + rank-r low-rank, no binary."""
    return ell_matmul_ref(x, vals, idx, d_in) + lowrank_ref(x, u, v)


def slab_ell_matmul_ref(x: Array, vals: Array, idx: Array, d_in: int,
                        b_packed: Array, u: Array, v: Array) -> Array:
    """Fused SLaB linear with ELL sparse part."""
    return ell_matmul_ref(x, vals, idx, d_in) + binlr_ref(x, b_packed, u, v)


def slab_matmul_ref(x: Array, w_s: Array, b_packed: Array,
                    u: Array, v: Array) -> Array:
    """Fused SLaB linear, dense-masked sparse part:
    y = x @ W_Sᵀ + Σ_r ((x ⊙ v_r) @ Bᵀ) ⊙ u_r."""
    y = x.astype(jnp.float32) @ w_s.astype(jnp.float32).T
    return y + binlr_ref(x, b_packed, u, v)


def slab_nm_matmul_ref(x: Array, vals: Array, idx: Array, m: int,
                       b_packed: Array, u: Array, v: Array) -> Array:
    """Fused SLaB linear with N:M packed sparse part."""
    return nm_matmul_ref(x, vals, idx, m) + binlr_ref(x, b_packed, u, v)


def slab_lr_matmul_ref(x: Array, w_s: Array, u: Array, v: Array) -> Array:
    """Sparse + rank-r low-rank, no binary: y = x @ W_Sᵀ + (x @ V) @ Uᵀ."""
    y = x.astype(jnp.float32) @ w_s.astype(jnp.float32).T
    return y + lowrank_ref(x, u, v)


def slab_nm_lr_matmul_ref(x: Array, vals: Array, idx: Array, m: int,
                          u: Array, v: Array) -> Array:
    """N:M sparse + rank-r low-rank, no binary."""
    return nm_matmul_ref(x, vals, idx, m) + lowrank_ref(x, u, v)


def flash_decode_ref(q: Array, k: Array, v: Array, lengths: Array,
                     k_scale: Array | None = None,
                     v_scale: Array | None = None) -> Array:
    """Grouped decode attention oracle. q (B,KV,G,dh) pre-scaled;
    k/v (B,S,KV,dh); lengths (B,). Returns (B,KV,G,dh)."""
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale.astype(jnp.float32)[..., None]
        vf = vf * v_scale.astype(jnp.float32)[..., None]
    logits = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32), kf)
    pos = jnp.arange(k.shape[1])
    mask = pos[None, :] < lengths[:, None]                  # (B, S)
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgs,bskd->bkgd", p, vf).astype(q.dtype)
