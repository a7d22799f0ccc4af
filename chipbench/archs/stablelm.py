"""StableLM-2's block (``model_type: stablelm``, HF
``StableLmDecoderLayer``) as StableLM-2-12B's ``config.json`` sets it:

    x  = LayerNorm(h)                       weight and bias; the only
                                            norm of the layer
    h' = h + attn(x) + mlp(x)               use_parallel_residual: both
                                            read the same x
    attn: q, k, v = x Wq, x Wk, x Wv        no bias (use_qkv_bias false)
          q, k <- LayerNorm of each head    qk_layernorm: one weight of
                                            head_dim per head, no bias
          rotary on the first partial_rotary_factor * head_dim dims of
          each head, split-half within them (inv_freq = 1 / theta **
          (2i / n_rot)); the other dims pass unchanged
          softmax(q k^T / sqrt(head_dim)), causal, grouped-query
    mlp:  (silu(x W_gate) * x W_up) W_down
    out:  LayerNorm (weight and bias), untied head

head_dim is hidden_size / num_attention_heads (the config has no
``head_dim`` key). The reference below is written from that
description in plain ``jax.numpy`` at float32 with every product at
``HIGHEST`` precision; it shares no code with the program. Norm weights
are drawn from the seed (``norms``), never left at one and zero.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights, work
from chipbench.archs.decoder import _linear

HIGHEST = jax.lax.Precision.HIGHEST
# What this block is; a configuration that states otherwise cannot run.
BLOCK = {"hidden_act": "silu", "tie_word_embeddings": False,
         "qk_layernorm": True, "use_parallel_residual": True,
         "use_qkv_bias": False}
# Sub-keys of the norms' weights, beside those ``weights`` uses.
TAG_LN_SCALE, TAG_LN_BIAS, TAG_QK_SCALE = 11, 12, 13


def check(cfg: dict) -> None:
    for k, want in BLOCK.items():
        if cfg.get(k) != want:
            raise ValueError(f"{cfg['name']}: {k}={cfg.get(k)!r}; the "
                             f"stablelm block runs {k}={want!r}")


@functools.partial(jax.jit, static_argnames=("n_layers", "d", "n_heads",
                                             "n_kv", "dh"))
def norms(key, n_layers: int, d: int, n_heads: int, n_kv: int, dh: int):
    """Float32 norm weights: LayerNorm scales (L + 1, d) uniform in
    [0.75, 1.25] and biases (L + 1, d) uniform in [-0.1, 0.1], row L
    the final norm's; q and k norm scales (L, H, dh) and (L, KV, dh)
    uniform in [0.75, 1.25]."""
    f32 = jnp.float32
    s = weights.uniform(weights.sub_key(key, TAG_LN_SCALE),
                        (n_layers + 1, d), 0.75, 1.25, f32)
    b = weights.uniform(weights.sub_key(key, TAG_LN_BIAS),
                        (n_layers + 1, d), -0.1, 0.1, f32)
    qk = weights.uniform(weights.sub_key(key, TAG_QK_SCALE),
                         (n_layers, n_heads + n_kv, dh), 0.75, 1.25, f32)
    return s, b, qk[:, :n_heads], qk[:, n_heads:]


def _norms(cfg: dict, key):
    return norms(key, cfg["num_hidden_layers"], cfg["hidden_size"],
                 cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 work.head_dim(cfg))


def program_config(cfg: dict):
    """The program's ``ArchConfig`` for this configuration file."""
    from repro.models.common import ArchConfig
    check(cfg)
    return ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], d_head=work.head_dim(cfg),
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        act="swiglu", rope="rope", rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["layer_norm_eps"]), norm="layer",
        rotary_pct=float(cfg["partial_rotary_factor"]), qk_norm=True,
        parallel_residual=True, dtype=jnp.bfloat16)


def program_params(cfg: dict, key) -> dict:
    """The program's parameter tree, every weight made on the device from
    ``key``; linears in the configuration's format."""
    fmt = cfg["format"]
    mod = weights.format_module(fmt["kind"])
    shapes = work.linear_shapes(cfg)
    n_layers, d, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                          cfg["vocab_size"])
    lin = mod.program_linears(
        lambda l: mod.make_layer(weights.layer_key(key, l), shapes, fmt),
        n_layers, fmt)
    s, b, qn, kn = _norms(cfg, key)
    layers: dict = {"attn_norm": s[:n_layers], "attn_norm_bias": b[:n_layers]}
    for path, leaf in lin.items():
        grp, name = path.split(".")
        layers.setdefault(grp, {})[name] = leaf
    layers["attn"].update(q_norm=qn, k_norm=kn)
    return {"layers": layers, "final_norm": s[n_layers],
            "final_norm_bias": b[n_layers],
            "embed": weights.embed(key, vocab, d),
            "lm_head": weights.head(key, vocab, d)}


# ----------------------------------------------------------------------
# Plain reference
# ----------------------------------------------------------------------

def _ln(x, scale, bias, eps):
    xc = x - jnp.mean(x, -1, keepdims=True)
    y = xc * jax.lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + eps) \
        * scale
    return y if bias is None else y + bias


def _rope(x, theta, n_rot):
    """x (B, S, H, dh), positions 0..S-1: rotate the (x1, x2) halves of
    the first n_rot dims of each head."""
    s = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, n_rot, 2, dtype=jnp.float32)
                          / n_rot)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :n_rot // 2], x[..., n_rot // 2:n_rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., n_rot:]], -1)


@functools.partial(jax.jit, static_argnames=("dims", "kind"))
def _layer(w, ln_s, ln_b, q_norm, k_norm, h, dims, kind):
    """One StableLM layer over h (B, S, d) float32, causal."""
    n_heads, n_kv, dh, n_rot, theta, eps = dims
    b, s, _ = h.shape
    x = _ln(h, ln_s, ln_b, eps)
    q = _linear(x, w["attn.wq"], kind).reshape(b, s, n_heads, dh)
    k = _linear(x, w["attn.wk"], kind).reshape(b, s, n_kv, dh)
    v = _linear(x, w["attn.wv"], kind).reshape(b, s, n_kv, dh)
    q, k = _ln(q, q_norm, None, eps), _ln(k, k_norm, None, eps)
    q, k = _rope(q, theta, n_rot), _rope(k, theta, n_rot)
    g = n_heads // n_kv
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / dh ** 0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    a = _linear(o.reshape(b, s, n_heads * dh), w["attn.wo"], kind)
    gate = _linear(x, w["mlp.w_gate"], kind)
    up = _linear(x, w["mlp.w_up"], kind)
    return h + a + _linear(jax.nn.silu(gate) * up, w["mlp.w_down"], kind)


@functools.partial(jax.jit, static_argnames=("eps", "kind"))
def _logits(h, ln_s, ln_b, head, eps, kind):
    return _linear(_ln(h, ln_s, ln_b, eps), head.astype(jnp.float32),
                   kind)


def logit_gaps(cfg: dict, key, seqs: Sequence[np.ndarray],
               positions: Sequence[Sequence[int]],
               served: Sequence[Sequence[int]],
               control: Optional[str] = None,
               shape: Tuple[int, int, int] = (16, 512, 1024)
               ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """The reference's verdict on served tokens, with the contract of
    ``decoder.logit_gaps``: per served token, the reference's best logit
    minus its logit of the served token, and with ``control`` the same
    gap for the token the reference computed at that lower precision
    puts first. Weights are made again one layer at a time from
    ``key``; each layer runs one request at a time; inputs are padded
    to ``shape`` (rows, positions, served tokens)."""
    fmt = cfg["format"]
    mod = weights.format_module(fmt["kind"])
    shapes = work.linear_shapes(cfg)
    n_layers, d, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                          cfg["vocab_size"])
    eps = float(cfg["layer_norm_eps"])
    dh = work.head_dim(cfg)
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"], dh,
            int(dh * cfg["partial_rotary_factor"]), float(cfg["rope_theta"]),
            eps)
    rows, s_max, n_max = shape
    n_tok = sum(len(p) for p in positions)
    if len(seqs) > rows or max(len(s) for s in seqs) > s_max \
            or n_tok > n_max:
        raise ValueError(f"check sample exceeds its shape {shape}")
    tok = np.zeros((len(seqs), s_max), np.int32)   # right-padded: causal
    for i, s in enumerate(seqs):                   # rows ignore the pad
        tok[i, :len(s)] = s
    emb = weights.embed(key, vocab, d)
    h = [jnp.take(emb, jnp.asarray(tok[i:i + 1]), axis=0).astype(
        jnp.float32) for i in range(len(seqs))]
    del emb
    streams = {None: h, control: h} if control else {None: h}
    ln_s, ln_b, qn, kn = _norms(cfg, key)
    for l in range(n_layers):
        parts = mod.make_layer(weights.layer_key(key, l), shapes, fmt)
        w = {p: mod.dense_equivalent(parts[p]) for p in parts}
        del parts
        for kind in streams:
            streams[kind] = [_layer(w, ln_s[l], ln_b[l], qn[l], kn[l], x,
                                    dims, kind) for x in streams[kind]]
        del w
    pad = n_max - n_tok
    r_idx = np.concatenate([np.full(len(p), i) for i, p in
                            enumerate(positions)] + [np.zeros(pad, int)])
    c_idx = np.concatenate([np.asarray(p, np.int64) for p in positions]
                           + [np.zeros(pad, int)])
    want = jnp.asarray(np.concatenate([np.asarray(s, np.int64)
                                       for s in served]
                                      + [np.zeros(pad, int)]))
    head = weights.head(key, vocab, d)
    out = {}
    for kind, hs in streams.items():
        hs = jnp.concatenate(hs + [jnp.zeros((rows - len(hs), s_max, d))])
        out[kind] = _logits(hs[r_idx, c_idx], ln_s[n_layers],
                            ln_b[n_layers], head, eps, kind)
    ref = out[None]
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, want[:, None], -1)[:, 0]
    gap_c = None
    if control:
        top = jnp.argmax(out[control], axis=-1)
        gap_c = np.asarray(best - jnp.take_along_axis(ref, top[:, None],
                                                      -1)[:, 0])[:n_tok]
    return np.asarray(gap)[:n_tok], gap_c
