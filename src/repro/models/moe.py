"""Mixture-of-Experts layer: sort-free capacity-based top-k dispatch.

GSPMD-native design (Mesh-TF / GShard lineage): tokens are reshaped into
dispatch *groups* of ``cfg.moe_group`` tokens; gates + a within-group
running count produce a one-hot dispatch tensor (G, T, E, C) that einsums
tokens into per-expert buffers (G*? -> E, C, D). When experts are sharded
over "model" and tokens over "data", XLA lowers the two einsums to the
canonical all-to-all pair. No sorting, no dynamic shapes — TPU-friendly.

Supports shared experts (DeepSeek-MoE): a dense always-on gated MLP with
total hidden width ``cfg.shared_ff`` added to the routed output.

Auxiliary load-balancing loss (Switch-style) is returned so train steps
can weight it in.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.packed_model import ExpertPackedStack, expert_matmul
from repro.models.common import (ArchConfig, dense_init, tap_record,
                                 tap_record_stacked, tap_scope)
from repro.models import mlp as mlp_lib

Array = jax.Array


def _expert_apply(x4: Array, w) -> Array:
    """Batched per-expert linear on the post-dispatch buffer: x4
    (G, E, C, D_in) -> (G, E, C, D_out). ``w`` is either the dense
    (E, D_in, D_out) expert leaf (einsum — XLA batched matmul) or an
    ``ExpertPackedStack``, served by the per-linear Pallas kernels under
    ``jax.vmap`` (one launch per expert bucket, expert index in the
    grid)."""
    if isinstance(w, ExpertPackedStack):
        g, e, c, d = x4.shape
        xe = x4.transpose(1, 0, 2, 3).reshape(e, g * c, d)
        y = expert_matmul(xe, w)
        return y.reshape(e, g, c, -1).transpose(1, 0, 2, 3)
    return jnp.einsum("gecd,edf->gecf", x4, w)


def moe_axes(cfg: ArchConfig) -> dict:
    axes = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "ffn"),
        "w_up": ("experts", "embed", "ffn"),
        "w_down": ("experts", "ffn", "embed"),
    }
    if cfg.shared_ff:
        axes["shared"] = mlp_lib.mlp_axes(cfg.with_(act="swiglu"))
    return axes


def init_moe(cfg: ArchConfig, key: Array):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), d, jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), d, cfg.dtype),
        "w_up": dense_init(ks[2], (e, d, f), d, cfg.dtype),
        "w_down": dense_init(ks[3], (e, f, d), f, cfg.dtype),
    }
    if cfg.shared_ff:
        p["shared"], _ = mlp_lib.init_mlp(cfg.with_(act="swiglu"), ks[4],
                                          d_ff=cfg.shared_ff)
    return p, moe_axes(cfg)


def capacity(cfg: ArchConfig, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(c, cfg.top_k)


def moe_ffn(cfg: ArchConfig, p: dict, x: Array) -> Tuple[Array, Array]:
    """x (B, S, D) -> (y (B, S, D), aux_loss scalar)."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n_tok = b * s
    tpg = min(cfg.moe_group, n_tok)
    if n_tok % tpg:
        tpg = n_tok            # degenerate smoke shapes: one group
    g = n_tok // tpg
    c = capacity(cfg, tpg)

    xt = x.reshape(g, tpg, d)
    tap_record("router", xt)
    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                      # (G,T,E)

    # top-k gating: iteratively peel off the argmax k times (k is small).
    gates = jnp.zeros_like(probs)
    remaining = probs
    sel_onehot = jnp.zeros_like(probs)
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        oh = jax.nn.one_hot(idx, e, dtype=probs.dtype)
        gates = gates + remaining * oh
        sel_onehot = sel_onehot + oh
        remaining = remaining * (1.0 - oh)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # position of each token within its expert's buffer (running count)
    pos_in_expert = jnp.cumsum(sel_onehot, axis=1) - sel_onehot   # (G,T,E)
    keep = sel_onehot * (pos_in_expert < c)                       # drop overflow
    gates = gates * (jnp.sum(keep, -1, keepdims=True) > 0)

    slot = jax.nn.one_hot(pos_in_expert, c, dtype=xt.dtype)       # (G,T,E,C)
    dispatch = slot * keep[..., None].astype(xt.dtype)            # (G,T,E,C)
    combine = dispatch * gates[..., None].astype(xt.dtype)

    expert_in = jnp.einsum("gtec,gtd->gecd", dispatch, xt)        # (G,E,C,D)
    # per-expert taps at the per-expert matmul site: each expert's stats
    # see exactly the dispatched-token subset it serves (capacity drops
    # included), with unused capacity slots contributing zero rows.
    tap_record_stacked("w_gate", expert_in, stack_axis=1)
    tap_record_stacked("w_up", expert_in, stack_axis=1)
    h = _expert_apply(expert_in, p["w_gate"])
    h = jax.nn.silu(h) * _expert_apply(expert_in, p["w_up"])
    tap_record_stacked("w_down", h, stack_axis=1)
    expert_out = _expert_apply(h, p["w_down"])                    # (G,E,C,D)
    y = jnp.einsum("gtec,gecd->gtd", combine, expert_out)
    y = y.reshape(b, s, d)

    # Switch load-balancing aux: E * sum_e f_e * P_e
    frac_tokens = jnp.mean(sel_onehot, axis=(0, 1)) / k           # (E,)
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs)

    if cfg.shared_ff:
        with tap_scope("shared"):
            y = y + mlp_lib.mlp(cfg.with_(act="swiglu"), p["shared"], x)
    return y, aux
