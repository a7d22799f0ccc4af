"""Pallas TPU kernel: flash-decode — grouped-query single-token
attention against a paged (possibly int8-quantized) KV cache.

    out (R, KV, G, dh) = softmax(q · Kᵀ / √dh) · V     per (row, kv head)

K/V live in one block pool stacked over layers (L, n_blocks, KV, bs,
dh) — head-major, so one (bs, dh) chunk of one head is a whole tile —
and each request row owns a row of *logical→physical* block indices
(`repro.serving.paged_cache`). Grid (R, KV, n_bt): step (r, h, j)
streams block ``block_tables[r, j]`` of head h in layer ``layer``
HBM→VMEM through a scalar-prefetched BlockSpec index map (the pool is
read where it lies; no layer's pool is sliced out), updates an
online-softmax accumulator in VMEM
scratch (running max m, normalizer l, weighted sum acc), and writes the
normalized output on the last chunk. The (S,) score row is never
materialized in HBM. Chunks past the row's valid length are skipped
(`pl.when`), so decode work is proportional to each request's actual
cache length, and a zero-length row returns exact zeros.

int8 mode: K/V chunks arrive as int8 + per-(token, head) scales; the
scales fold into the scores (K) and the probabilities (V), so the HBM
stream is the 1-byte payload and no dequantized chunk is built.

``flash_decode`` serves a contiguous (B, S, KV, dh) cache through the
same kernel by viewing each row as S/bs consecutive blocks of a
one-layer pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30


def _paged_kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
                  bs: int, n_s: int, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s * bs < len_ref[b])
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)             # (G, dh)
        k = k_ref[0, 0, 0].astype(jnp.float32)          # (bs, dh)
        v = v_ref[0, 0, 0].astype(jnp.float32)          # (bs, dh)
        scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        if quant:
            scores = scores * ks_ref[0, 0, 0]           # (1, bs) scales
        pos = s * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        scores = jnp.where(pos < len_ref[b], scores, NEG_INF)  # (G, bs)

        m_prev = m_ref[...]                             # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                     # (G, bs)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = p * vs_ref[0, 0, 0] if quant else p
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            pv, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(s == n_s - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_paged(q: Array, k_pool: Array, v_pool: Array,
                       block_tables: Array, lengths: Array, layer,
                       k_scale: Array | None = None,
                       v_scale: Array | None = None,
                       *, interpret: bool = False) -> Array:
    """Flash-decode against one layer of a stacked paged KV cache.

    q (R, KV, G, dh) pre-scaled by 1/sqrt(dh); k_pool/v_pool
    (L, n_blocks, KV, bs, dh) [int8 when scales given, with
    k_scale/v_scale (L, n_blocks, KV, bs)]; block_tables (R, n_bt) int32
    physical block ids per logical chunk (entries past a request's
    length may hold anything in range — they are never read); lengths
    (R,) int32 valid tokens per request; layer an int32 scalar, the
    layer whose blocks are read. Returns (R, KV, G, dh); zero-length
    rows return zeros."""
    r, kv, g, dh = q.shape
    n_layers, n_blocks, _, bs, _ = k_pool.shape
    n_bt = block_tables.shape[1]
    quant = k_scale is not None
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def chunk(bb, kk, ss, bt, lens, lay):   # chunk ss of row bb, head kk
        return (lay[0], bt[bb, ss], kk, 0, 0)

    def row(bb, kk, ss, bt, lens, lay):
        return (bb, kk, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, g, dh), row),
        pl.BlockSpec((1, 1, 1, bs, dh), chunk),
        pl.BlockSpec((1, 1, 1, bs, dh), chunk),
    ]
    operands = [q, k_pool, v_pool]
    if quant:
        # (1, bs) scale rows: the token axis rides the lanes
        in_specs += [pl.BlockSpec((1, 1, 1, 1, bs), chunk)] * 2
        operands += [k_scale.reshape(n_layers, n_blocks, kv, 1, bs),
                     v_scale.reshape(n_layers, n_blocks, kv, 1, bs)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,        # block_tables, lengths, layer
        grid=(r, kv, n_bt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, dh), row),
        scratch_shapes=[pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, dh), jnp.float32)],
    )
    kernel = functools.partial(_paged_kernel, bs=bs, n_s=n_bt, quant=quant)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, kv, g, dh), q.dtype),
        interpret=interpret,
        name="flash_decode_paged",
    )(block_tables, lengths, layer, *operands)


def flash_decode(q: Array, k: Array, v: Array, lengths: Array,
                 k_scale: Array | None = None,
                 v_scale: Array | None = None,
                 *, bs: int = 512, interpret: bool = False) -> Array:
    """q (B, KV, G, dh) pre-scaled by 1/sqrt(dh); k/v (B, S, KV, dh)
    [int8 when scales given, with k_scale/v_scale (B, S, KV)];
    lengths (B,) int32. Returns (B, KV, G, dh).

    The contiguous cache is re-laid out as a one-layer pool of bs-token
    blocks (row b owns blocks b*S/bs .. (b+1)*S/bs - 1) and served by
    the paged kernel at layer 0; a trailing partial chunk is zero-padded
    (padded slots sit at positions >= S >= lengths, so the length mask
    excludes them)."""
    b, s, kv, dh = k.shape
    bs = min(bs, s)
    pad = (-s) % bs
    n_c = (s + pad) // bs

    def to_pool(t):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape((b, n_c, bs) + t.shape[2:])
        t = jnp.moveaxis(t, 3, 2)                     # head ahead of bs
        return t.reshape((1, b * n_c) + t.shape[2:])

    tables = jnp.arange(b * n_c, dtype=jnp.int32).reshape(b, n_c)
    scales = ((to_pool(k_scale), to_pool(v_scale))
              if k_scale is not None else (None, None))
    return flash_decode_paged(q, to_pool(k), to_pool(v), tables, lengths, 0,
                              *scales, interpret=interpret)
