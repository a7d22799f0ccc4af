"""GQA attention: query-chunked (flash-style) prefill/train path and a
single-token decode path against a preallocated KV cache.

Memory discipline: the (S, S) score matrix is never materialized — the
train/prefill path lax.scan's over query chunks of ``cfg.q_chunk`` rows,
so live attention memory is O(q_chunk * S) per (batch, head) instead of
O(S^2). This is the XLA-level equivalent of flash attention's tiling.

Sharding discipline (the 96-head nemotron lesson): the full-sequence
path expands K/V to the full head count (`jnp.repeat` over the group
dim) and keeps every tensor in plain (B, S, H, dh) layout so the TP
sharding of H propagates through reshapes cleanly; `meshctx.hint` pins
the expanded K/V and the per-chunk scores to the "model" axis. The
decode path keeps K/V grouped (cache stays at n_kv heads — 12x smaller
for 96/8 GQA) and shards the cache over sequence ("kv_seq" -> model):
each model shard scores its sequence slice and GSPMD turns the softmax
normalization into the flash-decode all-reduce.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.packed_model import linear
from repro.models.common import (ArchConfig, dense_init, layer_norm,
                                 rotate, scope)
from repro.runtime.meshctx import hint

Array = jax.Array

NEG_INF = -1e30


def attention_axes(cfg: ArchConfig) -> dict:
    a = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv"),
        "wv": ("embed", "kv"),
        "wo": ("heads", "embed"),
    }
    if cfg.qk_norm:
        a["q_norm"] = (None, None)
        a["k_norm"] = (None, None)
    return a


def init_attention(cfg: ArchConfig, key: Array):
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "wq": dense_init(ks[0], (d, cfg.d_q), d, cfg.dtype),
        "wk": dense_init(ks[1], (d, cfg.d_kv), d, cfg.dtype),
        "wv": dense_init(ks[2], (d, cfg.d_kv), d, cfg.dtype),
        "wo": dense_init(ks[3], (cfg.d_q, d), cfg.d_q, cfg.dtype),
    }
    if cfg.qk_norm:
        # one LayerNorm scale per head: (H, dh) and (KV, dh)
        p["q_norm"] = jnp.ones((cfg.n_heads, cfg.d_head), jnp.float32)
        p["k_norm"] = jnp.ones((cfg.n_kv, cfg.d_head), jnp.float32)
    return p, attention_axes(cfg)


def project_qkv(cfg: ArchConfig, p: dict, x: Array, positions: Array
                ) -> Tuple[Array, Array, Array]:
    """x (B, S, D) -> q (B, S, H, dh), k and v (B, S, KV, dh): the three
    projections, then (``cfg.qk_norm``) a LayerNorm of each q and k head
    with that head's own scale, then rotary on q and k."""
    b, s, _ = x.shape
    dh = cfg.d_head
    q = linear(x, p["wq"], tap="wq").reshape(b, s, cfg.n_heads, dh)
    k = linear(x, p["wk"], tap="wk").reshape(b, s, cfg.n_kv, dh)
    v = linear(x, p["wv"], tap="wv").reshape(b, s, cfg.n_kv, dh)
    if cfg.qk_norm:
        with scope("qk_norm"):
            q = layer_norm(q, p["q_norm"], eps=cfg.norm_eps)
            k = layer_norm(k, p["k_norm"], eps=cfg.norm_eps)
    with scope("rope"):
        q = rotate(cfg, q, positions)
        k = rotate(cfg, k, positions)
    return q, k, v


def multihead_attention(
    cfg: ArchConfig,
    p: dict,
    x: Array,
    positions: Array,
) -> Array:
    """Full-sequence attention (train / prefill), query-chunked.
    x (B, S, D) -> (B, S, D). Causality from cfg.causal."""
    from repro.runtime.meshctx import current_mesh
    b, s, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv, cfg.d_head
    g = h // kv
    q, k, v = project_qkv(cfg, p, x, positions)
    if g > 1:                       # expand KV to full heads: clean TP on H
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)

    # TP strategy: shard heads over "model" when they divide; otherwise
    # fall back to sequence parallelism — shard the *query chunk* over
    # "model" so the S^2 score work splits even with indivisible head
    # counts (llama3.2's 24H / qwen2-vl's 12H on a 16-way axis). K/V stay
    # replicated either way (they already are when heads can't shard).
    mesh = current_mesh()
    sp_mode = bool(mesh is not None and "model" in mesh.axis_names
                   and h % mesh.shape["model"] != 0)

    q = q * (dh ** -0.5)
    if not sp_mode:
        q = hint(q, None, None, "model", None)
        k = hint(k, None, None, "model", None)
        v = hint(v, None, None, "model", None)

    cq = min(cfg.q_chunk, s)
    n_chunks = max(s // cq, 1)
    if s % cq:
        cq, n_chunks = s, 1
    kv_pos = jnp.arange(s, dtype=jnp.int32)

    def chunk(carry, inp):
        qc, qpos = inp                                    # (B,cq,H,dh), (cq,)
        if sp_mode:
            qc = hint(qc, None, "model", None, None)
        logits = jnp.einsum("bqhd,bshd->bhqs", qc, k,
                            preferred_element_type=jnp.float32)
        if sp_mode:
            logits = hint(logits, None, None, "model", None)
        else:
            logits = hint(logits, None, "model", None, None)
        if cfg.causal:
            mask = qpos[:, None] >= kv_pos[None, :]
            logits = jnp.where(mask[None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhqs,bshd->bqhd", probs, v)
        if sp_mode:
            out = hint(out, None, "model", None, None)
        return carry, out

    q_cs = q.reshape(b, n_chunks, cq, h, dh).swapaxes(0, 1)
    qpos_rows = positions[..., 0] if positions.ndim == 3 else positions
    qpos_cs = qpos_rows[0].reshape(n_chunks, cq)
    _, out = jax.lax.scan(chunk, None, (q_cs, qpos_cs))
    out = out.swapaxes(0, 1).reshape(b, s, cfg.d_q)
    return linear(out, p["wo"], tap="wo")


# ------------------------------------------------------------------
# Decode path
# ------------------------------------------------------------------

class KVCache(NamedTuple):
    k: Array        # (B, S_max, Kv, dh) — cfg.dtype, or int8 when quantized
    v: Array        # (B, S_max, Kv, dh)
    length: Array   # scalar int32 — tokens currently valid
    k_scale: Optional[Array] = None   # (B, S_max, Kv) f32, int8 mode only
    v_scale: Optional[Array] = None


def init_kv_cache(cfg: ArchConfig, batch: int, s_max: int,
                  length: int = 0) -> KVCache:
    shp = (batch, s_max, cfg.n_kv, cfg.d_head)
    if cfg.kv_quant:
        sshp = (batch, s_max, cfg.n_kv)
        return KVCache(jnp.zeros(shp, jnp.int8), jnp.zeros(shp, jnp.int8),
                       jnp.asarray(length, jnp.int32),
                       jnp.zeros(sshp, jnp.float32),
                       jnp.zeros(sshp, jnp.float32))
    return KVCache(jnp.zeros(shp, cfg.dtype), jnp.zeros(shp, cfg.dtype),
                   jnp.asarray(length, jnp.int32))


def kv_cache_axes(cfg: ArchConfig) -> KVCache:
    """Batch over data, cached sequence over model (SP/flash-decode
    sharding: each model shard owns a KV slice; softmax normalization
    crosses shards as an all-reduce)."""
    scale_ax = ("batch", "kv_seq", None) if cfg.kv_quant else None
    return KVCache(("batch", "kv_seq", None, None),
                   ("batch", "kv_seq", None, None), (),
                   scale_ax, scale_ax)


def _quantize_token(t: Array):
    """(B, 1, Kv, dh) -> int8 payload + (B, 1, Kv) scale."""
    t32 = t.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(t32), axis=-1) / 127.0, 1e-8)
    q = jnp.clip(jnp.round(t32 / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def decode_attention(
    cfg: ArchConfig, p: dict, x: Array, cache: KVCache, positions: Array,
) -> Tuple[Array, KVCache]:
    """One-token step. x (B, 1, D); positions (B, 1[, 3]).

    int8 mode: the cache is stored and *read* as int8; per-(token, head)
    scales are folded into the score/probability tensors, so no
    dequantized copy of the cache is ever materialized (on TPU the
    convert fuses into the dot's operand pipeline)."""
    b, s, d = x.shape
    kv, g, dh = cfg.n_kv, cfg.n_heads // cfg.n_kv, cfg.d_head
    q, k_new, v_new = project_qkv(cfg, p, x, positions)

    idx = cache.length
    if cfg.kv_quant:
        k_q, k_s = _quantize_token(k_new)
        v_q, v_s = _quantize_token(v_new)
        k = jax.lax.dynamic_update_slice(cache.k, k_q, (0, idx, 0, 0))
        v = jax.lax.dynamic_update_slice(cache.v, v_q, (0, idx, 0, 0))
        k_scale = jax.lax.dynamic_update_slice(cache.k_scale, k_s,
                                               (0, idx, 0))
        v_scale = jax.lax.dynamic_update_slice(cache.v_scale, v_s,
                                               (0, idx, 0))
        new_cache = KVCache(k, v, idx + s, k_scale, v_scale)
    else:
        k = jax.lax.dynamic_update_slice(
            cache.k, k_new.astype(cache.k.dtype), (0, idx, 0, 0))
        v = jax.lax.dynamic_update_slice(
            cache.v, v_new.astype(cache.v.dtype), (0, idx, 0, 0))
        new_cache = KVCache(k, v, idx + s, cache.k_scale, cache.v_scale)
        k_scale = v_scale = None

    # grouped form: cache stays at kv heads; q (B, 1, Kv, G, dh)
    q = q.reshape(b, s, kv, g, dh) * (dh ** -0.5)
    kk = k.astype(cfg.dtype) if cfg.kv_quant else k
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, kk,
                        preferred_element_type=jnp.float32)
    if cfg.kv_quant:
        logits = logits * k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    logits = hint(logits, None, None, None, None, "model")  # S over model
    valid = jnp.arange(k.shape[1], dtype=jnp.int32) <= idx
    logits = jnp.where(valid[None, None, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if cfg.kv_quant:
        probs = probs * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    probs = probs.astype(cfg.dtype)
    vv = v.astype(cfg.dtype) if cfg.kv_quant else v
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, vv)
    out = out.reshape(b, s, cfg.d_q)
    return linear(out, p["wo"], tap="wo"), new_cache


def paged_decode_attention(cfg: ArchConfig, p: dict, x: Array, pool,
                           layer, block_tables: Array, lengths: Array,
                           positions: Array, active: Array):
    """One-token decode of layer ``layer`` against the paged KV cache.

    x (R, 1, D); pool the stacked ``serving.paged_cache.PagedKVCache``
    (k/v (L, n_blocks, KV, bs, W), W the head dim rounded up to whole
    lanes); layer an int32 scalar;
    block_tables (R, n_bt) int32; lengths (R,) tokens already cached per
    row (also the write position); active (R,) bool — inactive rows
    write nothing and return zeros. Returns (out (R, 1, D), updated
    pool).

    The new token's K/V scatter into ``[layer, block_tables[r, len//bs],
    :, len % bs]`` of the stacked pool; attention then reads the whole
    stream through the block table via the ``flash_decode_paged`` kernel
    (scalar-prefetched indices), int8 path included."""
    from repro.kernels import ops
    from repro.serving.paged_cache import paged_write
    b, s, d = x.shape
    kv, g, dh = cfg.n_kv, cfg.n_heads // cfg.n_kv, cfg.d_head
    q, k_new, v_new = project_qkv(cfg, p, x, positions)

    bs_blk = pool.block_size
    n_bt = block_tables.shape[1]

    with scope("kv_write"):
        # physical write slot; clamp shields idle rows with stale
        # lengths (their write is dropped by `active` anyway)
        blk = jnp.take_along_axis(
            block_tables, jnp.clip(lengths // bs_blk, 0, n_bt - 1)[:, None],
            axis=1)[:, 0]
        off = lengths % bs_blk
        if cfg.kv_quant:
            k_q, k_s = _quantize_token(k_new)
            v_q, v_s = _quantize_token(v_new)
            news = (k_q[:, 0], v_q[:, 0], k_s[:, 0], v_s[:, 0])
        else:
            news = (k_new[:, 0], v_new[:, 0])
        pool = pool._replace(**{
            f: paged_write(getattr(pool, f), new, layer, blk, off, active)
            for f, new in zip(pool._fields, news)})

    with scope("paged_attn"):
        qg = q[:, 0].reshape(b, kv, g, dh) * (dh ** -0.5)
        att_len = jnp.where(active, lengths + 1, 0).astype(jnp.int32)
        out = ops.flash_decode_paged_attention(
            qg, pool.k, pool.v, block_tables, att_len, layer,
            pool.k_scale, pool.v_scale)
        out = out.reshape(b, 1, cfg.d_q).astype(x.dtype)
    return linear(out, p["wo"], tap="wo"), pool
