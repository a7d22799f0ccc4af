"""Forward ops for SLaB-compressed linear layers (pure-jnp paths).

The rank-1 Hadamard structure gives the cheap serving identity

    x @ (u vᵀ ⊙ B)ᵀ = ((x ⊙ v) @ Bᵀ) ⊙ u

so a compressed linear needs one sparse matmul + one binary matmul + two
vector scalings. The Pallas kernels in ``repro.kernels`` implement the
packed/tiled versions (``core.packed_model``); these jnp forms are the
oracles that path is tested against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.slab import SLaBDecomposition, low_rank_times_binary

Array = jax.Array


def slab_linear(x: Array, dec: SLaBDecomposition) -> Array:
    """y = x @ (W_S + W_L ⊙ W_B)ᵀ for x (..., D_in).

    Uses the rank-1 fast path when possible; general ranks and ablation
    variants fall back to materializing W_L ⊙ W_B.
    """
    dt = x.dtype
    w_s = dec.w_s.astype(dt)
    y = x @ w_s.T
    has_lr = dec.u is not None and dec.u.size
    has_b = dec.w_b is not None and dec.w_b.size
    if has_lr and has_b and dec.u.shape[-1] == 1:
        u = dec.u[:, 0].astype(dt)
        v = dec.v[:, 0].astype(dt)
        y = y + ((x * v) @ dec.w_b.T.astype(dt)) * u
    elif has_lr or has_b:
        y = y + x @ low_rank_times_binary(dec).astype(dt).T
    return y


def to_dense(dec: SLaBDecomposition, dtype=jnp.bfloat16) -> Array:
    """Materialize Ŵ (used to swap compressed weights into existing model
    params for evaluation; memory-equal but numerics-equal to slab_linear)."""
    from repro.core.slab import reconstruct
    return reconstruct(dec).astype(dtype)
