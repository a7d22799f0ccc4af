"""Jit'd public wrappers for the SLaB Pallas kernels.

Handles shape padding to block multiples, dtype plumbing, and the
interpret-mode switch (CPU validation; compiled Mosaic on real TPU).
The packed serving path (``core.packed_model``) calls these per linear,
and under ``jax.vmap`` per expert bucket.

Low-rank factors are accepted in any of the storage conventions —
``u``: (N,) rank-1 vector or (N, R) column factors; ``v``: (K,) or
(K, R) — and canonicalized to the kernels' row-major rank stacks
(R, N) / (R, K).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import binlr as binlr_k
from repro.kernels import ell as ell_k
from repro.kernels import nm_sparse as nm_k
from repro.kernels import slab_matmul as slab_k

Array = jax.Array


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_rows(x: Array, mult: int) -> Array:
    m = x.shape[0]
    pad = (-m) % mult
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x


def _rank_stack(u: Array, v: Array):
    """(N,)/(N,R) u and (K,)/(K,R) v -> kernel-layout (R,N), (R,K)."""
    u2 = u[None, :] if u.ndim == 1 else u.T
    v2 = v[None, :] if v.ndim == 1 else v.T
    return u2, v2


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def binlr(x: Array, b_packed: Array, u: Array, v: Array,
          bm: int = 256, bn: int = 256, bk: int = 512,
          interpret: Optional[bool] = None) -> Array:
    interpret = _on_cpu() if interpret is None else interpret
    u, v = _rank_stack(u, v)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m = x2.shape[0]
    x2 = _pad_rows(x2, min(bm, max(m, 1)))
    y = binlr_k.binlr_matmul(x2, b_packed, u, v, bm=bm, bn=bn, bk=bk,
                             interpret=interpret)
    return y[:m].reshape(*lead, -1)


@functools.partial(jax.jit,
                   static_argnames=("m_pat", "bm", "bn", "bk", "interpret"))
def nm_matmul(x: Array, vals: Array, idx: Array, m_pat: int,
              bm: int = 256, bn: int = 256, bk: int = 512,
              interpret: Optional[bool] = None) -> Array:
    interpret = _on_cpu() if interpret is None else interpret
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m = x2.shape[0]
    x2 = _pad_rows(x2, min(bm, max(m, 1)))
    y = nm_k.nm_matmul(x2, vals, idx, m_pat, bm=bm, bn=bn, bk=bk,
                       interpret=interpret)
    return y[:m].reshape(*lead, -1)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def slab_matmul(x: Array, w_s: Array, b_packed: Array, u: Array, v: Array,
                bm: int = 256, bn: int = 256, bk: int = 512,
                interpret: Optional[bool] = None) -> Array:
    interpret = _on_cpu() if interpret is None else interpret
    u, v = _rank_stack(u, v)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m = x2.shape[0]
    x2 = _pad_rows(x2, min(bm, max(m, 1)))
    y = slab_k.slab_matmul(x2, w_s, b_packed, u, v, bm=bm, bn=bn, bk=bk,
                           interpret=interpret)
    return y[:m].reshape(*lead, -1)


@functools.partial(jax.jit,
                   static_argnames=("m_pat", "bm", "bn", "bk", "interpret"))
def slab_nm_matmul(x: Array, vals: Array, idx: Array, m_pat: int,
                   b_packed: Array, u: Array, v: Array,
                   bm: int = 256, bn: int = 256, bk: int = 512,
                   interpret: Optional[bool] = None) -> Array:
    interpret = _on_cpu() if interpret is None else interpret
    u, v = _rank_stack(u, v)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m = x2.shape[0]
    x2 = _pad_rows(x2, min(bm, max(m, 1)))
    y = slab_k.slab_nm_matmul(x2, vals, idx, m_pat, b_packed, u, v,
                              bm=bm, bn=bn, bk=bk, interpret=interpret)
    return y[:m].reshape(*lead, -1)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def slab_lr_matmul(x: Array, w_s: Array, u: Array, v: Array,
                   bm: int = 256, bn: int = 256, bk: int = 512,
                   interpret: Optional[bool] = None) -> Array:
    """Fused sparse + rank-r low-rank linear with NO binary term
    (HASSLE-free-style decompositions): y = x @ W_Sᵀ + (x @ V) @ Uᵀ."""
    interpret = _on_cpu() if interpret is None else interpret
    u, v = _rank_stack(u, v)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m = x2.shape[0]
    x2 = _pad_rows(x2, min(bm, max(m, 1)))
    y = slab_k.slab_lr_matmul(x2, w_s, u, v, bm=bm, bn=bn, bk=bk,
                              interpret=interpret)
    return y[:m].reshape(*lead, -1)


@functools.partial(jax.jit,
                   static_argnames=("m_pat", "bm", "bn", "bk", "interpret"))
def slab_nm_lr_matmul(x: Array, vals: Array, idx: Array, m_pat: int,
                      u: Array, v: Array,
                      bm: int = 256, bn: int = 256, bk: int = 512,
                      interpret: Optional[bool] = None) -> Array:
    """N:M sparse + rank-r low-rank, no binary term."""
    interpret = _on_cpu() if interpret is None else interpret
    u, v = _rank_stack(u, v)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m = x2.shape[0]
    x2 = _pad_rows(x2, min(bm, max(m, 1)))
    y = slab_k.slab_nm_lr_matmul(x2, vals, idx, m_pat, u, v,
                                 bm=bm, bn=bn, bk=bk, interpret=interpret)
    return y[:m].reshape(*lead, -1)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def ell_matmul(x: Array, vals: Array, idx: Array,
               bm: int = 128, bn: int = 256,
               interpret: Optional[bool] = None) -> Array:
    """Row-padded ELL unstructured-sparse matmul (gather-matmul kernel)."""
    interpret = _on_cpu() if interpret is None else interpret
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m = x2.shape[0]
    x2 = _pad_rows(x2, min(bm, max(m, 1)))
    y = ell_k.ell_matmul(x2, vals, idx, bm=bm, bn=bn, interpret=interpret)
    return y[:m].reshape(*lead, -1)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def ell_lr_matmul(x: Array, vals: Array, idx: Array, u: Array, v: Array,
                  bm: int = 128, bn: int = 256,
                  interpret: Optional[bool] = None) -> Array:
    """ELL sparse + rank-r low-rank, no binary term."""
    interpret = _on_cpu() if interpret is None else interpret
    u, v = _rank_stack(u, v)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m = x2.shape[0]
    x2 = _pad_rows(x2, min(bm, max(m, 1)))
    y = ell_k.ell_lr_matmul(x2, vals, idx, u, v, bm=bm, bn=bn,
                            interpret=interpret)
    return y[:m].reshape(*lead, -1)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def slab_ell_matmul(x: Array, vals: Array, idx: Array, b_packed: Array,
                    u: Array, v: Array,
                    bm: int = 128, bn: int = 256,
                    interpret: Optional[bool] = None) -> Array:
    """Full SLaB linear with ELL sparse part + binary ⊙ rank-r term."""
    interpret = _on_cpu() if interpret is None else interpret
    u, v = _rank_stack(u, v)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m = x2.shape[0]
    x2 = _pad_rows(x2, min(bm, max(m, 1)))
    y = ell_k.slab_ell_matmul(x2, vals, idx, b_packed, u, v, bm=bm, bn=bn,
                              interpret=interpret)
    return y[:m].reshape(*lead, -1)


def flash_decode_paged_attention(q: Array, k_pool: Array, v_pool: Array,
                                 block_tables: Array, lengths: Array, layer,
                                 k_scale: Optional[Array] = None,
                                 v_scale: Optional[Array] = None,
                                 interpret: Optional[bool] = None) -> Array:
    """Paged (block-table) grouped-query decode attention over one layer
    of the stacked pool. q (R, KV, G, dh) pre-scaled; k_pool/v_pool
    (L, n_blocks, KV, bs, W), W >= dh with zeros past dh (the pool's
    lane padding: q is padded to W and the output cut back to dh);
    block_tables (R, n_bt); lengths (R,) — zero-length rows return 0;
    layer an int32 scalar.

    Under a multi-device mesh the kernel runs inside ``shard_map`` (the
    chip's compiler cannot partition a Pallas kernel): each device
    attends with its KV heads when they divide the "model" axis, and
    with all of them otherwise."""
    from repro.kernels.flash_decode import flash_decode_paged
    from repro.runtime.meshctx import current_mesh
    interpret = _on_cpu() if interpret is None else interpret
    fn = functools.partial(flash_decode_paged, interpret=interpret)
    dh, pad = q.shape[-1], k_pool.shape[-1] - q.shape[-1]
    if pad:
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, pad),))
    args = (q, k_pool, v_pool, block_tables, lengths, layer, k_scale,
            v_scale)
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        out = fn(*args)
    else:
        from jax.sharding import PartitionSpec as P
        n_model = dict(mesh.shape).get("model", 1)
        ax = ("model" if n_model > 1 and q.shape[1] % n_model == 0
              else None)
        heads, pool = P(None, ax), P(None, None, ax)
        scale = pool if k_scale is not None else None
        out = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(heads, pool, pool, P(), P(), P(), scale, scale),
            out_specs=heads, check_vma=False)(*args)
    return out[..., :dh] if pad else out

