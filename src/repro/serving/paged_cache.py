"""Paged KV cache: fixed-size blocks, per-request block tables, and a
host-side free-list allocator.

Layout. One stacked pool holds every layer's and every request's K/V
in fixed-size blocks:

    k, v     (L, n_blocks, KV, block_size, W)       cfg.dtype | int8
    k_scale  (L, n_blocks, KV, block_size) f32      int8 mode only

Head-major blocks: one block of one layer, ``[layer, block]``, is a
contiguous (KV, block_size, W) slab of whole tiles, which the
``flash_decode_paged`` kernel copies by one DMA. W is the head dim
rounded up to the TPU's 128 lanes (``pool_width``): the chip's DMA
copies whole lane tiles, and
a pool whose last dim is not a multiple of 128 is given a different
memory layout by the TPU compiler (blocks minor, to save the padding),
which it then copies to the kernel's layout and back at every step.
The lanes past the head hold zeros (``paged_write`` pads each token;
``ops.flash_decode_paged_attention`` pads q and drops them from its
output). The pool stays one buffer for the whole step: the layer scan
carries it, each layer writes its token in place and the kernel
reads it where it lies, and the engine donates it to the step, so the
pool that comes out reuses the buffer that went in.

A request's cache is the *logical* concatenation of the blocks its
block-table row names: ``block_tables[r, j]`` is the physical block
holding tokens ``[j*block_size, (j+1)*block_size)`` of request ``r``.
Blocks are allocated on demand as a stream grows and returned to the
free list when it retires (or is evicted) — fragmentation-free KV
memory at block granularity, the vLLM paging idea.

Device state is only the pools. Block tables and lengths are small
host-side numpy arrays owned by the scheduler and shipped as ordinary
jit arguments each step, so allocation/eviction never touches device
state and the step functions stay pure.

Writes go through ``paged_write``: a scatter at ``(layer, block_id, :,
offset)`` with ``mode="drop"`` so inactive rows (idle slots, exhausted
prefill rows) write nowhere. Reads go
through the ``flash_decode_paged`` kernel, which takes the block table
as a scalar-prefetch operand and copies each row's valid blocks.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig

Array = jax.Array


class PagedKVCache(NamedTuple):
    """The block pools of every attention layer, stacked on a leading
    layer axis (families without KV attention don't page). One buffer,
    donated to each engine step and updated in place."""
    k: Array                        # (L, n_blocks, KV, bs, W)
    v: Array                        # (L, n_blocks, KV, bs, W)
    k_scale: Optional[Array] = None   # (L, n_blocks, KV, bs) f32, int8 only
    v_scale: Optional[Array] = None

    @property
    def n_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[3]


LANES = 128


def pool_width(d_head: int) -> int:
    """The pool's last dim: the head dim rounded up to whole lanes."""
    return -(-d_head // LANES) * LANES


def init_paged_cache(cfg: ArchConfig, n_blocks: int,
                     block_size: int) -> PagedKVCache:
    if cfg.family in ("ssm", "hybrid", "audio"):
        raise ValueError(
            f"paged KV serving needs a KV-attention family, not "
            f"{cfg.family!r} (SSM state is O(1) — it doesn't page)")
    shp = (cfg.n_layers, n_blocks, cfg.n_kv, block_size,
           pool_width(cfg.d_head))
    if cfg.kv_quant:
        sshp = shp[:-1]
        return PagedKVCache(jnp.zeros(shp, jnp.int8),
                            jnp.zeros(shp, jnp.int8),
                            jnp.zeros(sshp, jnp.float32),
                            jnp.zeros(sshp, jnp.float32))
    return PagedKVCache(jnp.zeros(shp, cfg.dtype), jnp.zeros(shp, cfg.dtype))


def paged_cache_axes(cfg: ArchConfig) -> PagedKVCache:
    """Logical axes for planner placement (runtime.sharding rules):
    blocks are never sharded — any request may own any block, so a
    block dim split would scatter one stream across shards — while the
    KV-head dim TP-shards over "model" when it divides (each shard
    serves its heads' pool; the flash-decode kernel copies a block's
heads together, whatever their number)."""
    scale_ax = (("layers", "kv_blocks", "kv_heads", None)
                if cfg.kv_quant else None)
    ax = ("layers", "kv_blocks", "kv_heads", None, None)
    return PagedKVCache(ax, ax, scale_ax, scale_ax)


def paged_write(pool: Array, new: Array, layer, block_ids: Array,
                offsets: Array, active: Array) -> Array:
    """Scatter one token per request row into layer ``layer`` of the
    stacked pool, in place when the pool's buffer is free to reuse.

    pool (L, n_blocks, KV, bs, W) | (L, n_blocks, KV, bs); new (R, KV,
    dh) | (R, KV), padded with zeros to W; layer an int32 scalar;
    block_ids/offsets (R,) int32; active (R,) bool. Inactive rows are
    routed out of bounds and dropped by the scatter."""
    blk = jnp.where(active, block_ids, pool.shape[1])[:, None]
    heads = jnp.arange(pool.shape[2])[None, :]
    new = new.astype(pool.dtype)
    if pool.ndim == 4:
        # scales: rewrite each (head, block) row of bs scales whole, so
        # the scatter writes along the minor axis as the K/V one does
        old = pool[layer, blk, heads]                    # (R, KV, bs)
        hit = jnp.arange(pool.shape[3]) == offsets[:, None, None]
        return pool.at[layer, blk, heads].set(
            jnp.where(hit, new[..., None], old), mode="drop")
    pad = pool.shape[-1] - new.shape[-1]
    if pad:
        new = jnp.pad(new, ((0, 0), (0, 0), (0, pad)))
    return pool.at[layer, blk, heads, offsets[:, None]].set(new,
                                                            mode="drop")


class BlockAllocator:
    """Host-side free list over the pool's physical block ids.

    LIFO reuse keeps recently-freed blocks hot. The allocator is
    all-or-nothing: ``alloc(n)`` either returns n block ids or None
    (caller decides to evict/queue) — no partial grants to unwind.

    ``reserve(n)``/``release()`` take free blocks out of circulation
    and put them back — the fault-injection surface for allocator
    pressure (``serving/faults.py`` pool-shrink events). Reserved
    blocks are neither free nor allocated; ``release()`` must be
    called before the end-of-trace leak check ``n_free == n_blocks``
    holds."""

    def __init__(self, n_blocks: int):
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(n_blocks - 1, -1, -1))
        self._reserved: List[int] = []

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_reserved(self) -> int:
        return len(self._reserved)

    def reserve(self, n: int) -> int:
        """Pull up to ``n`` free blocks out of circulation (pool-shrink
        fault). Returns how many were actually reserved — never more
        than are free, so live streams keep their blocks."""
        if n < 0:
            raise ValueError(f"reserve({n})")
        take = min(n, len(self._free))
        self._reserved.extend(self._free[len(self._free) - take:])
        del self._free[len(self._free) - take:]
        return take

    def release(self, n: Optional[int] = None) -> int:
        """Return ``n`` (default: all) reserved blocks to the free
        list. Returns how many came back."""
        give = len(self._reserved) if n is None else min(
            n, len(self._reserved))
        self._free.extend(self._reserved[len(self._reserved) - give:])
        del self._reserved[len(self._reserved) - give:]
        return give

    def alloc(self, n: int) -> Optional[List[int]]:
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        got = self._free[-n:][::-1] if n else []
        del self._free[len(self._free) - n:]
        return got

    def free(self, ids: List[int]) -> None:
        for b in ids:
            if not (0 <= b < self.n_blocks):
                raise ValueError(f"free of out-of-range block {b}")
        if set(ids) & set(self._free):
            raise ValueError(f"double free: {set(ids) & set(self._free)}")
        self._free.extend(ids)


def blocks_needed(n_tokens: int, block_size: int) -> int:
    return -(-n_tokens // block_size)


def table_width(max_len: int, block_size: int) -> int:
    """Block-table columns needed to address ``max_len`` tokens."""
    return max(blocks_needed(max_len, block_size), 1)
