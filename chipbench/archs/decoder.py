"""Pre-norm decoder: RMSNorm, grouped-query attention with rotary
position embedding on every dimension of a head (split-half
convention), a SiLU-gated MLP, untied embedding and output head. This is
Mistral's block as its ``config.json`` defines it, and the block the
program runs for every dense configuration.

The reference below is written from that description in plain
``jax.numpy`` at float32 with every product at ``HIGHEST`` precision; it
shares no code with the program.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights, work

HIGHEST = jax.lax.Precision.HIGHEST
# What this block is; a configuration that states otherwise cannot run.
BLOCK = {"hidden_act": "silu", "tie_word_embeddings": False,
         "partial_rotary_factor": 1.0, "qk_layernorm": False,
         "use_parallel_residual": False}


def _eps(cfg: dict) -> float:
    return float(cfg.get("rms_norm_eps", cfg.get("layer_norm_eps")))


def check(cfg: dict) -> None:
    for k, want in BLOCK.items():
        if cfg.get(k, want) != want:
            raise ValueError(f"{cfg['name']}: {k}={cfg[k]!r}; the decoder "
                             f"block runs {k}={want!r}")


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
        norm_eps=_eps(cfg), dtype=jnp.bfloat16)


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
    attn_n, mlp_n, final_n = weights.norms(key, n_layers, d)
    layers: dict = {"attn_norm": attn_n, "mlp_norm": mlp_n}
    for path, leaf in lin.items():
        grp, name = path.split(".")
        layers.setdefault(grp, {})[name] = leaf
    return {"layers": layers, "final_norm": final_n,
            "embed": weights.embed(key, vocab, d),
            "lm_head": weights.head(key, vocab, d)}


# ----------------------------------------------------------------------
# Plain reference
# ----------------------------------------------------------------------

def quantize(x: jax.Array, axis: int, kind: Optional[str]) -> jax.Array:
    """Round ``x`` to ``kind`` ('fp8' = float8_e4m3fn, 'int8') with one
    scale per slice along ``axis`` (the largest magnitude maps to the
    format's largest value), returned in float32; None leaves it."""
    if kind is None:
        return x
    top = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if kind == "fp8":
        s = jnp.maximum(top, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if kind == "int8":
        s = jnp.maximum(top, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    raise ValueError(f"unknown precision {kind!r}")


def _linear(x, w, kind):
    """x (..., d_in) @ w (d_in, d_out); under ``kind`` both operands are
    rounded first, per token and per output column."""
    return jnp.matmul(quantize(x, -1, kind), quantize(w, 0, kind),
                      precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x (B, S, H, dh), positions 0..S-1: rotate (x1, x2) halves."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("dims", "kind"))
def _layer(w: Dict[str, jax.Array], attn_norm, mlp_norm, h, dims, kind):
    """One decoder layer over h (B, S, d) float32, causal."""
    n_heads, n_kv, dh, theta, eps = dims
    b, s, _ = h.shape
    x = _rms(h, attn_norm, eps)
    q = _linear(x, w["attn.wq"], kind).reshape(b, s, n_heads, dh)
    k = _linear(x, w["attn.wk"], kind).reshape(b, s, n_kv, dh)
    v = _linear(x, w["attn.wv"], kind).reshape(b, s, n_kv, dh)
    q, k = _rope(q, theta), _rope(k, theta)
    g = n_heads // n_kv
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / dh ** 0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    h = h + _linear(o.reshape(b, s, n_heads * dh), w["attn.wo"], kind)
    x = _rms(h, mlp_norm, eps)
    gate = _linear(x, w["mlp.w_gate"], kind)
    up = _linear(x, w["mlp.w_up"], kind)
    return h + _linear(jax.nn.silu(gate) * up, w["mlp.w_down"], kind)


@functools.partial(jax.jit, static_argnames=("eps", "kind"))
def _logits(h, final_norm, head, eps, kind):
    return _linear(_rms(h, final_norm, eps), head.astype(jnp.float32),
                   kind)


def logit_gaps(cfg: dict, key, seqs: Sequence[np.ndarray],
               positions: Sequence[Sequence[int]],
               served: Sequence[Sequence[int]],
               control: Optional[str] = None,
               shape: Tuple[int, int, int] = (16, 512, 1024)
               ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """The reference's verdict on served tokens.

    ``seqs[i]`` is request i's prompt followed by its served tokens (the
    last one left off: it was never fed); ``served[i][j]`` is the token
    served at ``positions[i][j]``. Returns, per served token, the
    reference's best logit minus its logit of the served token, and
    with ``control`` the same gap for the token the reference computed
    at that lower precision puts first. Weights are made again one
    layer at a time from ``key``, so the reference fits beside nothing
    else on the device. Each layer runs one request at a time, so the
    scores of a whole row are the largest thing held. Inputs are padded
    to ``shape`` (rows, positions, served tokens) so that every run
    compiles the same programs."""
    fmt = cfg["format"]
    mod = weights.format_module(fmt["kind"])
    shapes = work.linear_shapes(cfg)
    n_layers, d, vocab = (cfg["num_hidden_layers"], cfg["hidden_size"],
                          cfg["vocab_size"])
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            work.head_dim(cfg), float(cfg["rope_theta"]), _eps(cfg))
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
    attn_n, mlp_n, final_n = weights.norms(key, n_layers, d)
    for l in range(n_layers):
        parts = mod.make_layer(weights.layer_key(key, l), shapes, fmt)
        w = {p: mod.dense_equivalent(parts[p]) for p in parts}
        del parts
        for kind in streams:
            streams[kind] = [_layer(w, attn_n[l], mlp_n[l], x, dims, kind)
                             for x in streams[kind]]
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
        out[kind] = _logits(hs[r_idx, c_idx], final_n, head, _eps(cfg),
                            kind)
    ref = out[None]
    best = jnp.max(ref, axis=-1)
    gap = best - jnp.take_along_axis(ref, want[:, None], -1)[:, 0]
    gap_c = None
    if control:
        top = jnp.argmax(out[control], axis=-1)
        gap_c = np.asarray(best - jnp.take_along_axis(ref, top[:, None],
                                                      -1)[:, 0])[:n_tok]
    return np.asarray(gap)[:n_tok], gap_c
