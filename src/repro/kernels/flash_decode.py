"""Pallas TPU kernel: flash-decode — grouped-query single-token
attention against a paged (possibly int8-quantized) KV cache.

    out (R, KV, G, W) = softmax(q · Kᵀ) · V     per (row, kv head)

K/V live in one block pool stacked over layers (L, n_blocks, KV, bs,
W) — one block of one layer, ``[layer, blk]``, is a contiguous (KV, bs,
W) slab holding every head — and each request row owns a row of
*logical→physical* block indices (`repro.serving.paged_cache`).

Grid (R,): step r walks row r's valid blocks, ``ceil(lengths[r] /
bs)`` of them, in groups of P (``blocks_per_step``, from the shapes:
about 256 tokens a group within a fixed VMEM budget; P = 16 at bs 16,
P = 1 for ``flash_decode``'s 512-token blocks). The pools stay in HBM
where they lie (no layer's pool is sliced out); for each block of a
group the kernel starts one DMA of ``[layer, block_tables[r, j]]``
(all KV heads) for K and one for V, int8 scales alike, into one of two
VMEM slots, and fetches group g+1 while group g computes. Each group
updates an online-softmax accumulator (running max, normalizer,
weighted sum; f32) for all (KV, G) query rows, one KV head at a time.
A call is R grid steps and at most R * ceil(n_bt / P) groups; no block
at or past a row's length is copied, so the work follows each row's
cache length, and a zero-length row copies nothing and returns exact
zeros. The (S,) score row is never materialized in HBM.

int8 mode: K/V blocks arrive as int8 + per-(token, head) scales and are
dequantized in VMEM, so the HBM stream is the 1-byte payload.

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
TOKENS_PER_STEP = 256         # tokens a group of blocks aims to hold
STAGING_BYTES = 4 << 20       # VMEM for K/V staging: 2 slots x (K, V)


def blocks_per_step(kv: int, bs: int, w: int, itemsize: int,
                    n_bt: int) -> int:
    """P, the blocks a group copies: about TOKENS_PER_STEP tokens, two
    slots of K and V within STAGING_BYTES, at least 1, at most n_bt."""
    fit = STAGING_BYTES // (4 * kv * bs * w * itemsize)
    return max(1, min(TOKENS_PER_STEP // bs, fit, n_bt))


def _paged_kernel(bt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, *rest,
                  bs: int, n_p: int, quant: bool):
    if quant:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem,
         m_ref, l_ref, acc_ref) = rest
        pairs = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                 (vs_hbm, vs_buf))
    else:
        o_ref, k_buf, v_buf, sem, m_ref, l_ref, acc_ref = rest
        pairs = ((k_hbm, k_buf), (v_hbm, v_buf))
    r = pl.program_id(0)
    length = len_ref[r]
    n_valid = jnp.minimum(pl.cdiv(length, bs), bt_ref.shape[1])
    n_grp = pl.cdiv(n_valid, n_p)
    layer = layer_ref[0]
    kv = q_ref.shape[1]
    span = n_p * bs
    # q and k meet in their common dtype (bf16 products are exact in the
    # f32 sums); int8 blocks are scaled in f32
    qk_dt = (jnp.float32 if quant
             else jnp.promote_types(q_ref.dtype, k_buf.dtype))

    def copies(g, slot, i):    # block i of group g, every pool, in `slot`
        blk = bt_ref[r, g * n_p + i]
        return [pltpu.make_async_copy(src.at[layer, blk], dst.at[slot, i],
                                      sem.at[slot]) for src, dst in pairs]

    def each_valid(g, slot, act):
        for i in range(n_p):
            @pl.when(g * n_p + i < n_valid)
            def _():
                for c in copies(g, slot, i):
                    act(c)

    each_valid(0, 0, lambda c: c.start())
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def group(g, carry):
        slot = g % 2

        @pl.when(g + 1 < n_grp)
        def _prefetch():
            each_valid(g + 1, 1 - slot, lambda c: c.start())

        each_valid(g, slot, lambda c: c.wait())
        pos = g * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        row = g * span + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)
        for h in range(kv):
            q = q_ref[0, h].astype(qk_dt)                    # (G, W)
            k = k_buf[slot, :, h].astype(qk_dt)              # (P, bs, W)
            v = v_buf[slot, :, h].astype(jnp.float32)
            if quant:       # (P, bs) scales of head h, tokens to rows
                k = k * ks_buf[slot, :, 0, pl.ds(h * bs, bs)][:, :, None]
                v = v * vs_buf[slot, :, 0, pl.ds(h * bs, bs)][:, :, None]
            k = k.reshape(span, k.shape[-1])
            # stale or never-written rows past the length: select, so
            # nothing they hold reaches the sums
            v = jnp.where(row < length, v.reshape(span, v.shape[-1]), 0.0)
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # (G, P*bs)
            scores = jnp.where(pos < length, scores, NEG_INF)
            m_prev = m_ref[h]                                # (G, 1)
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(scores - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_grp, group, 0)
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_paged(q: Array, k_pool: Array, v_pool: Array,
                       block_tables: Array, lengths: Array, layer,
                       k_scale: Array | None = None,
                       v_scale: Array | None = None,
                       *, interpret: bool = False) -> Array:
    """Flash-decode against one layer of a stacked paged KV cache.

    q (R, KV, G, W) pre-scaled by 1/sqrt(dh); k_pool/v_pool
    (L, n_blocks, KV, bs, W) [int8 when scales given, with
    k_scale/v_scale (L, n_blocks, KV, bs)]; block_tables (R, n_bt) int32
    physical block ids per logical chunk (entries at or past
    ceil(length / bs) may hold anything — they are never read); lengths
    (R,) int32 valid tokens per request; layer an int32 scalar, the
    layer whose blocks are read. Returns (R, KV, G, W); zero-length
    rows return zeros. Grid (R,); each step walks its row's valid
    blocks P at a time (``blocks_per_step``)."""
    r, kv, g, w = q.shape
    n_bt = block_tables.shape[1]
    bs = k_pool.shape[3]
    quant = k_scale is not None
    n_p = blocks_per_step(kv, bs, w, k_pool.dtype.itemsize, n_bt)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def row(rr, bt, lens, lay):
        return (rr, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [q, k_pool, v_pool]
    scratch = [pltpu.VMEM((2, n_p, kv, bs, w), k_pool.dtype)] * 2
    if quant:
        # each block's (KV, bs) scales as one row of whole lanes: the
        # chip's DMA copies no slice narrower than its 128-lane tiles
        lanes = kv * bs + (-kv * bs) % 128

        def rows(s):
            s = s.reshape(s.shape[:2] + (1, kv * bs))
            return jnp.pad(s, ((0, 0),) * 3 + ((0, lanes - kv * bs),))

        operands += [rows(k_scale), rows(v_scale)]
        scratch += [pltpu.VMEM((2, n_p, 1, lanes), jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((kv, g, 1), jnp.float32),
                pltpu.VMEM((kv, g, 1), jnp.float32),
                pltpu.VMEM((kv, g, w), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,        # block_tables, lengths, layer
        grid=(r,),
        in_specs=[pl.BlockSpec((1, kv, g, w), row)]
                 + [hbm] * (len(operands) - 1),
        out_specs=pl.BlockSpec((1, kv, g, w), row),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(_paged_kernel, bs=bs, n_p=n_p, quant=quant)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, kv, g, w), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
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
    excludes them), and the head dim is zero-padded to whole lanes, as
    the paged cache's pool is."""
    b, s, kv, dh = k.shape
    bs = min(bs, s + (-s) % 32)          # whole sublane tiles at any dtype
    pad = (-s) % bs
    n_c = (s + pad) // bs
    lane_pad = (-dh) % 128

    def to_pool(t, lanes=0):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 3)
                    + ((0, lanes),))
        t = t.reshape((b, n_c, bs) + t.shape[2:])
        t = jnp.moveaxis(t, 3, 2)                     # head ahead of bs
        return t.reshape((1, b * n_c) + t.shape[2:])

    tables = jnp.arange(b * n_c, dtype=jnp.int32).reshape(b, n_c)
    scales = ((to_pool(k_scale), to_pool(v_scale))
              if k_scale is not None else (None, None))
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, lane_pad),))
    out = flash_decode_paged(q, to_pool(k, lane_pad), to_pool(v, lane_pad),
                             tables, lengths, 0, *scales, interpret=interpret)
    return out[..., :dh]
