"""Model zoo assembly: one parameterized LM covering the six assigned
families (dense / moe / ssm / hybrid / audio / vlm).

Layer stacks are `lax.scan`'d over stacked parameters (leading dim L) so
compile time and HLO size stay O(1) in depth — at nemotron-340B scale
(96 layers) this is mandatory. The scan body is wrapped in
``jax.checkpoint`` with a configurable remat policy by the runtime step
builders (not here) so inference paths stay remat-free.

Hybrid (zamba2) layout: every layer is a Mamba-2 block; layers with
``idx % attn_every == attn_every - 1`` additionally run one *shared*
transformer block (attention + MLP) whose parameters are common to all
invocations — Zamba2's weight-sharing design. The shared block params
live outside the scanned stack.

Family quirks:
  audio — encoder-only (non-causal), input is precomputed frame
          embeddings (stub frontend per the assignment), no decode path.
  vlm   — M-RoPE positions (B, S, 3); prefill consumes precomputed patch
          embeddings, decode consumes text token ids.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.packed_model import (has_hetero, layer_slice_range,
                                     segment_runs)
from repro.models import attention as attn_lib
from repro.models import mamba2 as mamba_lib
from repro.models import mlp as mlp_lib
from repro.models import moe as moe_lib
from repro.models.common import (ArchConfig, embed_init, dense_init,
                                 is_axes_leaf, layer_norm, positions_for,
                                 rms_norm, scope, softmax_xent, tap_scope)

Array = jax.Array
AUX_LOSS_WEIGHT = 0.01


# ------------------------------------------------------------------
# Norms
# ------------------------------------------------------------------

def _norm_names(cfg: ArchConfig, name: str) -> Tuple[str, ...]:
    """The leaves of norm ``name``: its scale, and a bias ("<name>_bias")
    for LayerNorm."""
    return (name, name + "_bias") if cfg.norm == "layer" else (name,)


def _block_norms(cfg: ArchConfig) -> Tuple[str, ...]:
    """Norms of one attention layer: one before attention, and one
    before the MLP unless the two read the same input."""
    names = ("attn_norm",) if cfg.parallel_residual else ("attn_norm",
                                                           "mlp_norm")
    return tuple(n for name in names for n in _norm_names(cfg, name))


def _init_norms(cfg: ArchConfig, names: Tuple[str, ...]) -> dict:
    return {n: (jnp.zeros if n.endswith("_bias") else jnp.ones)(
        (cfg.d_model,), jnp.float32) for n in names}


def _norm(cfg: ArchConfig, p: dict, name: str, x: Array) -> Array:
    """Norm ``name`` of ``p`` applied to x: RMSNorm, or LayerNorm with
    its bias."""
    if cfg.norm == "layer":
        return layer_norm(x, p[name], p[name + "_bias"], cfg.norm_eps)
    return rms_norm(x, p[name], cfg.norm_eps)


# ------------------------------------------------------------------
# Init
# ------------------------------------------------------------------

def _init_layer(cfg: ArchConfig, key: Array):
    """One layer of the stack (params, axes) — family dependent."""
    ks = jax.random.split(key, 4)
    if cfg.family in ("ssm", "hybrid"):
        mp, ma = mamba_lib.init_mamba(cfg, ks[0])
        return ({"norm": jnp.ones((cfg.d_model,), jnp.float32), "mamba": mp},
                {"norm": ("embed",), "mamba": ma})
    p: dict = _init_norms(cfg, _block_norms(cfg))
    a: dict = {n: ("embed",) for n in p}
    p["attn"], a["attn"] = attn_lib.init_attention(cfg, ks[1])
    if cfg.family == "moe":
        p["moe"], a["moe"] = moe_lib.init_moe(cfg, ks[2])
    else:
        p["mlp"], a["mlp"] = mlp_lib.init_mlp(cfg, ks[2])
    return p, a


def init(cfg: ArchConfig, key: Array):
    """Returns (params, axes). Stacked layers carry a leading "layers" dim."""
    kl, ke, kh, ks = jax.random.split(key, 4)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: _init_layer(cfg, k)[0])(layer_keys)

    params: dict = {"layers": layers,
                    **_init_norms(cfg, _norm_names(cfg, "final_norm"))}
    if cfg.input_mode == "tokens" or cfg.family == "vlm":
        params["embed"] = embed_init(ke, (cfg.vocab, cfg.d_model), cfg.dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kh, (cfg.d_model, cfg.vocab),
                                       cfg.d_model, cfg.dtype)
    if cfg.family == "hybrid":
        sp: dict = _init_norms(cfg, _block_norms(cfg))
        k1, k2 = jax.random.split(ks)
        sp["attn"], _ = attn_lib.init_attention(cfg, k1)
        sp["mlp"], _ = mlp_lib.init_mlp(cfg, k2)
        params["shared_attn"] = sp
    return params, param_axes(cfg)


def abstract_params(cfg: ArchConfig):
    """(ShapeDtypeStruct pytree, axes) without allocating — dry-run path."""
    shapes = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))[0])
    return shapes, param_axes(cfg)


def _layer_axes(cfg: ArchConfig) -> dict:
    """Static logical axes of one layer — no array allocation."""
    if cfg.family in ("ssm", "hybrid"):
        return {"norm": ("embed",), "mamba": mamba_lib.mamba_axes()}
    a: dict = {n: ("embed",) for n in _block_norms(cfg)}
    a["attn"] = attn_lib.attention_axes(cfg)
    if cfg.family == "moe":
        a["moe"] = moe_lib.moe_axes(cfg)
    else:
        a["mlp"] = mlp_lib.mlp_axes(cfg)
    return a


def param_axes(cfg: ArchConfig):
    """Static logical-axes pytree (no array work)."""
    layer_axes = jax.tree.map(lambda ax: ("layers",) + tuple(ax),
                              _layer_axes(cfg),
                              is_leaf=is_axes_leaf)
    axes: dict = {"layers": layer_axes,
                  **{n: ("embed",) for n in _norm_names(cfg, "final_norm")}}
    if cfg.input_mode == "tokens" or cfg.family == "vlm":
        axes["embed"] = ("vocab", "embed")
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if cfg.family == "hybrid":
        axes["shared_attn"] = {
            **{n: ("embed",) for n in _block_norms(cfg)},
            "attn": attn_lib.attention_axes(cfg),
            "mlp": mlp_lib.mlp_axes(cfg)}
    return axes


def param_count(cfg: ArchConfig) -> int:
    import math
    shapes = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0))[0])
    return sum(math.prod(l.shape) if l.shape else 1
               for l in jax.tree.leaves(shapes))


def active_param_count(cfg: ArchConfig) -> int:
    """MoE: params touched per token (top_k of n_experts) — for the
    6·N_active·D model-FLOPs roofline term."""
    total = param_count(cfg)
    if cfg.family != "moe":
        return total
    per_expert = 3 * cfg.d_model * cfg.d_ff
    inactive = cfg.n_layers * per_expert * (cfg.n_experts - cfg.top_k)
    return total - inactive


# ------------------------------------------------------------------
# Forward (train / prefill)
# ------------------------------------------------------------------

def _block(cfg: ArchConfig, lp: dict, h: Array, attend):
    """One attention layer on the residual stream h: the attention norm,
    ``attend(x)`` -> (out, cache) on the normed x, then the MLP (or
    MoE) on the stream after attention through its own norm, or
    (``cfg.parallel_residual``) on the same x, added beside attention.
    Returns (h, MoE aux loss or None, cache)."""
    with tap_scope("attn"):
        x = _norm(cfg, lp, "attn_norm", h)
        a, cache = attend(x)
    if cfg.parallel_residual:
        hin = x
    else:
        h = h + a
        hin = _norm(cfg, lp, "mlp_norm", h)
    aux = None
    if cfg.family == "moe":
        with tap_scope("moe"):
            y, aux = moe_lib.moe_ffn(cfg, lp["moe"], hin)
    else:
        with tap_scope("mlp"):
            y = mlp_lib.mlp(cfg, lp["mlp"], hin)
    if cfg.parallel_residual:
        return h + a + y, aux, cache
    return h + y, aux, cache


def _shared_block(cfg: ArchConfig, sp: dict, h: Array, positions: Array
                  ) -> Array:
    with tap_scope("shared"):
        return _block(cfg, sp, h, lambda x: (attn_lib.multihead_attention(
            cfg, sp["attn"], x, positions), None))[0]


def _layer_fwd(cfg: ArchConfig, params: dict, lp: dict, idx: Array,
               h: Array, positions: Array) -> Tuple[Array, Array]:
    """Returns (h, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.family in ("ssm", "hybrid"):
        if cfg.family == "hybrid" and cfg.attn_every:
            apply_attn = (idx % cfg.attn_every) == (cfg.attn_every - 1)
            if isinstance(apply_attn, jax.core.Tracer):
                h = jax.lax.cond(
                    apply_attn,
                    lambda hh: _shared_block(cfg, params["shared_attn"], hh,
                                             positions),
                    lambda hh: hh, h)
            elif bool(apply_attn):
                # concrete layer index (eager calibration path): run the
                # shared block un-traced so activation taps see values
                h = _shared_block(cfg, params["shared_attn"], h, positions)
        with tap_scope("mamba"):
            h = h + mamba_lib.mamba_block(
                cfg, lp["mamba"], rms_norm(h, lp["norm"], cfg.norm_eps))
        return h, aux
    h, moe_aux, _ = _block(cfg, lp, h, lambda x: (
        attn_lib.multihead_attention(cfg, lp["attn"], x, positions), None))
    return h, aux if moe_aux is None else moe_aux


def embed_inputs(cfg: ArchConfig, params: dict, inputs: Array) -> Array:
    """Token ids (int) -> table lookup; float inputs pass through (stub
    modality frontends provide embeddings directly)."""
    if jnp.issubdtype(inputs.dtype, jnp.integer):
        return params["embed"][inputs]
    return inputs.astype(cfg.dtype)


def unembed(cfg: ArchConfig, params: dict, h: Array) -> Array:
    if cfg.tie_embeddings:
        return h @ params["embed"].T
    return h @ params["lm_head"]


def forward(cfg: ArchConfig, params: dict, inputs: Array,
            positions: Optional[Array] = None,
            remat_policy: Optional[Any] = None,
            remat_block: int = 1,
            segments: Optional[Tuple[Tuple[int, int], ...]] = None
            ) -> Tuple[Array, Array]:
    """Full-sequence forward. Returns (logits (B,S,V), aux_loss).

    ``remat_block`` > 1 enables sqrt-L block checkpointing: layers are
    scanned in groups of ``remat_block``; only group-boundary carries are
    saved for the backward pass (G + K live carries instead of L — the
    change that fits nemotron-340B's activations into v5e HBM).

    ``segments`` overrides the layer-axis partition of the segmented
    path (benchmarking the unrolled equivalent = per-layer segments);
    heterogeneous packed stacks compute it via ``segment_runs``."""
    from repro.runtime.meshctx import DP, hint
    b, s = inputs.shape[0], inputs.shape[1]
    if positions is None:
        positions = positions_for(cfg, b, s)
    h = embed_inputs(cfg, params, inputs)
    h = hint(h, DP, None, None)

    stacked = params["layers"]

    def body(carry, xs):
        h, aux = carry
        lp, idx = xs
        h = hint(h, DP, None, None)   # re-pin batch sharding per layer
        h, a = _layer_fwd(cfg, params, lp, idx, h, positions)
        return (h, aux + a), None

    if has_hetero(stacked) or segments is not None:
        # Heterogeneous packed stacks (PackedStack leaves) change leaf
        # shapes across layers, so ONE lax.scan can't span the model —
        # but the layer axis partitions into maximal contiguous runs
        # with identical packed signatures, and each run scans: one
        # traced layer body per segment (O(#segments) compile, not
        # O(L)). Serving-only path (packed weights never train), so
        # remat is irrelevant.
        if segments is None:
            segments = segment_runs(stacked, cfg.n_layers)
        carry = (h, jnp.zeros((), jnp.float32))
        for lo, hi in segments:
            carry, _ = _seg_scan(
                body, carry,
                (layer_slice_range(stacked, lo, hi), jnp.arange(lo, hi)),
                hi - lo)
        h, aux = carry
        h = _norm(cfg, params, "final_norm", h)
        return unembed(cfg, params, h), aux

    init = (h, jnp.zeros((), jnp.float32))
    k = remat_block
    if k > 1 and cfg.n_layers % k == 0:
        g = cfg.n_layers // k

        def block(carry, xs_blk):
            return jax.lax.scan(body, carry, xs_blk)

        if remat_policy is not None:
            block = jax.checkpoint(block, policy=remat_policy)
        stacked_g = jax.tree.map(
            lambda x: x.reshape(g, k, *x.shape[1:]), stacked)
        idx_g = jnp.arange(cfg.n_layers).reshape(g, k)
        (h, aux), _ = jax.lax.scan(block, init, (stacked_g, idx_g))
    else:
        if remat_policy is not None:
            body = jax.checkpoint(body, policy=remat_policy)
        (h, aux), _ = jax.lax.scan(
            body, init, (stacked, jnp.arange(cfg.n_layers)))
    h = _norm(cfg, params, "final_norm", h)
    return unembed(cfg, params, h), aux


def loss_fn(cfg: ArchConfig, params: dict, batch: dict,
            remat_policy: Optional[Any] = None,
            remat_block: int = 1) -> Tuple[Array, dict]:
    logits, aux = forward(cfg, params, batch["inputs"],
                          batch.get("positions"), remat_policy,
                          remat_block)
    ce = softmax_xent(logits, batch["labels"], batch.get("mask"))
    loss = ce + AUX_LOSS_WEIGHT * aux
    return loss, {"ce": ce, "aux": aux}


# ------------------------------------------------------------------
# Serving: prefill + decode
# ------------------------------------------------------------------

class LayerCache(NamedTuple):
    """Union cache — exactly one member populated per family."""
    kv: Any
    mamba: Any
    shared_kv: Any   # hybrid: KV caches of shared-attn invocations


def n_shared_invocations(cfg: ArchConfig) -> int:
    if cfg.family != "hybrid" or not cfg.attn_every:
        return 0
    return cfg.n_layers // cfg.attn_every


def init_cache(cfg: ArchConfig, batch: int, s_max: int,
               length: int = 0) -> LayerCache:
    if cfg.family in ("ssm", "hybrid"):
        mc = jax.vmap(lambda _: mamba_lib.init_mamba_cache(cfg, batch))(
            jnp.arange(cfg.n_layers))
        skv = None
        if cfg.family == "hybrid":
            ninv = n_shared_invocations(cfg)
            skv = jax.vmap(
                lambda _: attn_lib.init_kv_cache(cfg, batch, s_max, length))(
                jnp.arange(ninv))
        return LayerCache(None, mc, skv)
    kv = jax.vmap(lambda _: attn_lib.init_kv_cache(cfg, batch, s_max, length))(
        jnp.arange(cfg.n_layers))
    return LayerCache(kv, None, None)


def cache_axes(cfg: ArchConfig) -> LayerCache:
    if cfg.family in ("ssm", "hybrid"):
        ma = jax.tree.map(lambda ax: ("layers",) + tuple(ax),
                          mamba_lib.mamba_cache_axes(),
                          is_leaf=is_axes_leaf)
        sa = None
        if cfg.family == "hybrid":
            sa = jax.tree.map(lambda ax: ("layers",) + tuple(ax),
                              attn_lib.kv_cache_axes(cfg),
                              is_leaf=is_axes_leaf)
        return LayerCache(None, ma, sa)
    ka = jax.tree.map(lambda ax: ("layers",) + tuple(ax),
                      attn_lib.kv_cache_axes(cfg),
                      is_leaf=is_axes_leaf)
    return LayerCache(ka, None, None)


def _layer_decode(cfg: ArchConfig, params: dict, lp: dict, idx: Array,
                  h: Array, kv_l, positions: Array):
    if cfg.family in ("ssm", "hybrid"):
        with tap_scope("mamba"):
            y, mc = mamba_lib.mamba_decode_step(
                cfg, lp["mamba"], rms_norm(h, lp["norm"], cfg.norm_eps), kv_l)
        return h + y, mc
    h, _, kc = _block(cfg, lp, h, lambda x: attn_lib.decode_attention(
        cfg, lp["attn"], x, kv_l, positions))
    return h, kc


def _shared_block_decode(cfg: ArchConfig, sp: dict, h: Array,
                         kv: attn_lib.KVCache, positions: Array):
    with tap_scope("shared"):
        h, _, kv = _block(cfg, sp, h, lambda x: attn_lib.decode_attention(
            cfg, sp["attn"], x, kv, positions))
    return h, kv


def _cat_parts(parts):
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *parts)


def _seg_scan(body, carry, xs, length: int):
    """Drive one layer segment: a real ``lax.scan`` for multi-layer
    runs, a direct body call for length-1 runs — no scan machinery and
    no layer-axis ``dynamic_slice`` for trivial depth (this is where
    the old ~15% segmented-vs-unrolled overhead lived; per-layer
    segmentation now IS the unrolled path). The direct call also passes
    a concrete layer index, so trace counts stay one body per segment
    either way."""
    if length > 1:
        return jax.lax.scan(body, carry, xs)
    xs0 = jax.tree.map(lambda a: a[0], xs)
    carry, y = body(carry, xs0)
    return carry, jax.tree.map(lambda a: a[None], y)


def _slice_layers(tree, lo: int, hi: int, n_layers: int):
    """Layer-axis slice of stacked per-layer state; the full range is
    the identity (the homogeneous one-segment path copies nothing)."""
    if lo == 0 and hi == n_layers:
        return tree
    return jax.tree.map(lambda x: x[lo:hi], tree)


def decode_step(cfg: ArchConfig, params: dict, cache: LayerCache,
                token: Array, positions: Array,
                segments: Optional[Tuple[Tuple[int, int], ...]] = None
                ) -> Tuple[Array, LayerCache]:
    """One decode step. token (B, 1) int32 (or (B,1,D) embeds);
    positions (B,1[,3]). Returns (logits (B,1,V), new cache).

    The layer loop is one ``lax.scan`` per contiguous same-signature
    segment (``segment_runs``): a homogeneous stack is the single
    segment (0, L) — the classic one-scan decode — while heterogeneous
    packed stacks trace O(#segments) layer bodies instead of O(L).
    Per-segment caches are sliced from / concatenated back into the
    same stacked buffers, so segmentations are interchangeable step to
    step; ``segments`` overrides the partition (per-layer segments =
    the old unrolled path, kept reachable for benchmarks/tests)."""
    from repro.runtime.meshctx import DP, hint
    h = embed_inputs(cfg, params, token)
    h = hint(h, DP, None, None)

    stacked = params["layers"]
    if segments is None:
        segments = segment_runs(stacked, cfg.n_layers)

    if cfg.family in ("ssm", "hybrid"):
        if cfg.family == "hybrid":
            per = cfg.attn_every

            def body(carry, xs):
                h, skv = carry                  # skv: stacked (ninv, …) caches
                lp, mc_l, idx = xs
                h = hint(h, DP, None, None)

                def with_attn(args):
                    h, skv = args
                    inv = idx // per
                    skv_l = jax.tree.map(lambda x: x[inv], skv)
                    h2, skv_new = _shared_block_decode(
                        cfg, params["shared_attn"], h,
                        attn_lib.KVCache(*skv_l), positions)
                    skv2 = jax.tree.map(
                        lambda buf, new: jax.lax.dynamic_update_index_in_dim(
                            buf, new, inv, 0), skv, skv_new)
                    return h2, skv2

                h, skv = jax.lax.cond((idx % per) == (per - 1),
                                      with_attn, lambda a: a, (h, skv))
                h, mc_new = _layer_decode(cfg, params, lp, idx, h, mc_l,
                                          positions)
                return (h, skv), mc_new

            carry, mc_parts = (h, cache.shared_kv), []
            for lo, hi in segments:
                carry, mc_new = _seg_scan(
                    body, carry,
                    (layer_slice_range(stacked, lo, hi),
                     _slice_layers(cache.mamba, lo, hi, cfg.n_layers),
                     jnp.arange(lo, hi)), hi - lo)
                mc_parts.append(mc_new)
            (h, skv) = carry
            new_cache = LayerCache(None, _cat_parts(mc_parts), skv)
        else:
            def body(h, xs):
                lp, mc_l, idx = xs
                h = hint(h, DP, None, None)
                h, mc_new = _layer_decode(cfg, params, lp, idx, h,
                                          mc_l, positions)
                return h, mc_new

            mc_parts = []
            for lo, hi in segments:
                h, mc_new = _seg_scan(
                    body, h,
                    (layer_slice_range(stacked, lo, hi),
                     _slice_layers(cache.mamba, lo, hi, cfg.n_layers),
                     jnp.arange(lo, hi)), hi - lo)
                mc_parts.append(mc_new)
            new_cache = LayerCache(None, _cat_parts(mc_parts), None)
    else:
        def body(h, xs):
            lp, kv_l, idx = xs
            h = hint(h, DP, None, None)   # re-pin batch sharding per layer
            h, kv_new = _layer_decode(cfg, params, lp, idx, h,
                                      attn_lib.KVCache(*kv_l), positions)
            return h, kv_new

        kv_parts = []
        for lo, hi in segments:
            h, kv_new = _seg_scan(
                body, h,
                (layer_slice_range(stacked, lo, hi),
                 _slice_layers(cache.kv, lo, hi, cfg.n_layers),
                 jnp.arange(lo, hi)), hi - lo)
            kv_parts.append(kv_new)
        new_cache = LayerCache(_cat_parts(kv_parts), None, None)

    h = _norm(cfg, params, "final_norm", h)
    return unembed(cfg, params, h), new_cache


def _layer_decode_paged(cfg: ArchConfig, params: dict, lp: dict, idx: Array,
                        h: Array, pool, block_tables: Array,
                        lengths: Array, positions: Array, active: Array):
    h, _, pool = _block(
        cfg, lp, h, lambda x: attn_lib.paged_decode_attention(
            cfg, lp["attn"], x, pool, idx, block_tables, lengths, positions,
            active))
    return h, pool


def paged_decode_step(cfg: ArchConfig, params: dict, paged,
                      block_tables: Array, lengths: Array, token: Array,
                      active: Array,
                      segments: Optional[Tuple[Tuple[int, int], ...]] = None
                      ):
    """One decode step against the paged KV cache (serving engine path).

    token (R, 1) int32 over the engine's fixed request slots; paged a
    ``serving.paged_cache.PagedKVCache``; block_tables (R, n_bt) int32;
    lengths (R,) tokens already cached per row; active (R,) bool.
    Returns (logits (R, 1, V), new paged cache). Inactive rows write
    nothing into the pool and their logits are garbage-but-finite.

    The layer loop reuses the segmented-scan machinery of
    ``decode_step`` — heterogeneous packed stacks trace O(#segments)
    bodies. The whole stacked pool rides the scan carry, so each layer
    writes its token in place at its layer index and the kernel reads
    it there: no layer's pool is sliced out or stacked back.
    KV-attention families only (the engine gates SSM/hybrid out at
    construction)."""
    from repro.runtime.meshctx import DP, hint
    if cfg.family in ("ssm", "hybrid", "audio"):
        raise ValueError(f"paged decode: unsupported family {cfg.family!r}")
    r = token.shape[0]
    positions = positions_for(cfg, r, 1, offset=lengths[:, None])
    with scope("embed"):
        h = embed_inputs(cfg, params, token)
    h = hint(h, DP, None, None)

    stacked = params["layers"]
    if segments is None:
        segments = segment_runs(stacked, cfg.n_layers)

    def body(carry, xs):
        h, pool = carry
        lp, idx = xs
        h = hint(h, DP, None, None)
        return _layer_decode_paged(cfg, params, lp, idx, h, pool,
                                   block_tables, lengths, positions,
                                   active), None

    carry = (h, paged)
    for lo, hi in segments:
        with scope("layer_scan"):
            carry, _ = _seg_scan(
                body, carry,
                (layer_slice_range(stacked, lo, hi), jnp.arange(lo, hi)),
                hi - lo)
    h, paged = carry

    with scope("head"):
        h = _norm(cfg, params, "final_norm", h)
        return unembed(cfg, params, h), paged


def prefill(cfg: ArchConfig, params: dict, inputs: Array,
            positions: Optional[Array] = None) -> Tuple[Array, Array]:
    """Prefill = full forward returning logits (cache fill is modeled as
    the forward pass; the dry-run prefill cell lowers this fn). Encoder
    (audio) prefill is just the forward."""
    logits, _ = forward(cfg, params, inputs, positions)
    return logits
