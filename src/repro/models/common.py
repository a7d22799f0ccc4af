"""Shared model building blocks: norms, activations, rotary embeddings,
initializers, and the logical-axis annotation convention.

Every ``init_*`` helper returns ``(params, axes)`` where ``axes`` is a
pytree of the same structure whose leaves are tuples of *logical axis
names* (one per tensor dim). The sharding planner (``repro.runtime.
sharding``) maps logical names -> mesh axes with divisibility checks.

Logical axis vocabulary:
  "layers"   stacked-layer leading dim (scan axis, never sharded)
  "vocab"    vocabulary dim            -> "model"
  "embed"    d_model dim               -> fsdp axes ("data" [, "pod"])
  "heads"    flattened q-head dim      -> "model" (if divisible)
  "kv"       flattened kv-head dim     -> "model" (if divisible)
  "ffn"      feed-forward hidden dim   -> "model"
  "experts"  MoE expert dim            -> "model"
  "ssm"      mamba inner dim           -> "model"
  null (None) unsharded dim
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


# ------------------------------------------------------------------
# Activation taps
# ------------------------------------------------------------------
#
# The calibration statistics that drive compression (‖X‖₂ column norms,
# X^T X Hessians) are captured from the *real* model forward instead of
# re-deriving layer wiring elsewhere. The mechanism:
#
#   * ``core.packed_model.linear(x, w, tap="wq")`` — the single matmul
#     dispatch chokepoint — reports its input ``x`` here when a capture
#     is active;
#   * modules that own several linears run them under ``tap_scope``
#     prefixes ("attn", "mlp", "moe", "shared", "mamba"), so full tap
#     names ("attn.wq", "moe.shared.w_gate", "mamba.out") match the
#     compression pipeline's ``linear_paths`` exactly;
#   * ``with tap_capture(hessian=...) as tap:`` activates recording for
#     the enclosed (eager) forward and accumulates streaming fp32
#     reductions per tap name.
#
# Captures are thread-local and nestable; recording is a no-op (one
# list check) when no capture is active, so instrumented forwards cost
# nothing in production, and the scope/record calls inside scanned layer
# bodies only ever execute at trace time.
#
# The same names label the device work: ``tap_scope`` and a tapped
# ``linear`` also enter ``jax.named_scope`` (through ``scope``), so the
# compiled ops of ``attn.wq`` carry ``attn/wq`` in their op names;
# parts of a step that own no linear (the layer scan, the KV write,
# the head) enter ``scope`` directly.

#: every name ``scope`` has entered: how the serving engine tells the
#: program's scopes from the components JAX adds to an op's name
#: (``while``, ``body``, ``closed_call``, ``jit(f)``).
SCOPE_NAMES: set = set()


@contextlib.contextmanager
def scope(name: str):
    """``jax.named_scope(name)``, recorded in ``SCOPE_NAMES``."""
    SCOPE_NAMES.add(name)
    with jax.named_scope(name):
        yield

_tap_state = threading.local()


def _tap_captures() -> List["TapCapture"]:
    if not hasattr(_tap_state, "captures"):
        _tap_state.captures = []
    return _tap_state.captures


def _tap_prefix() -> List[str]:
    if not hasattr(_tap_state, "prefix"):
        _tap_state.prefix = []
    return _tap_state.prefix


class TapCapture:
    """Streaming per-linear activation statistics for one capture scope.

    Per tap name, accumulates (fp32) the column sum-of-squares of every
    recorded input — ``norms(name)`` is then ``diag(sqrt(X^T X))`` — and,
    with ``hessian=True``, the full Gram matrix ``X^T X``. Stacked
    (per-expert) records keep a leading expert dim: norms (E, D_in),
    Hessians (E, D_in, D_in), holding exactly the dispatched-token
    subset each expert served.
    """

    def __init__(self, hessian: bool = False,
                 hessian_names: Optional[set] = None):
        self.want_hessian = hessian
        # restrict the O(T·D²) Gram accumulation to these tap names
        # (None = all); norms are cheap and always recorded
        self._hess_names = (None if hessian_names is None
                            else set(hessian_names))
        self._sumsq: Dict[str, Array] = {}
        self._hess: Dict[str, Array] = {}
        self._count: Dict[str, Any] = {}   # int, or (E,) for stacked taps
        # taps fed by the same array in one forward (wq/wk/wv share hn,
        # moe w_gate/w_up share expert_in) share one Gram compute. The
        # cache is bounded: entries hold a strong ref to the recorded
        # activation (keeps the id valid), and same-input taps fire back
        # to back, so a few slots give full dedup without pinning every
        # batch's activations in a streaming multi-batch capture
        self._gram_cache: Dict[Tuple[int, str], Tuple[Array, Array]] = {}
        self._gram_cache_slots = 4

    # -- recording ---------------------------------------------------

    @staticmethod
    def _check_concrete(name: str, x):
        if isinstance(x, jax.core.Tracer):
            raise RuntimeError(
                f"activation tap {name!r} hit a traced value: run the "
                "calibration forward eagerly (outside jit/scan) under "
                "tap_capture")

    def _want_hess(self, name: str) -> bool:
        return self.want_hessian and (self._hess_names is None
                                      or name in self._hess_names)

    def _gram(self, x: Array, kind: str, compute) -> Array:
        key = (id(x), kind)
        hit = self._gram_cache.get(key)
        if hit is not None and hit[0] is x:
            return hit[1]
        g = compute()
        while len(self._gram_cache) >= self._gram_cache_slots:
            self._gram_cache.pop(next(iter(self._gram_cache)))  # FIFO
        self._gram_cache[key] = (x, g)
        return g

    def record(self, name: str, x: Array) -> None:
        """x (..., D_in): all leading dims are token dims."""
        self._check_concrete(name, x)
        f = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        ss = jnp.sum(f * f, axis=0)
        self._sumsq[name] = self._sumsq.get(name, 0.0) + ss
        self._count[name] = self._count.get(name, 0) + f.shape[0]
        if self._want_hess(name):
            g = self._gram(x, "flat", lambda: f.T @ f)
            self._hess[name] = self._hess.get(name, 0.0) + g

    def record_stacked(self, name: str, x: Array, stack_axis: int) -> None:
        """x with one stacked dim (experts) at ``stack_axis``; remaining
        leading dims are token dims, last dim is D_in."""
        self._check_concrete(name, x)
        xe = jnp.moveaxis(x, stack_axis, 0)
        e = xe.shape[0]
        f = xe.reshape(e, -1, xe.shape[-1]).astype(jnp.float32)
        ss = jnp.sum(f * f, axis=1)                      # (E, D)
        self._sumsq[name] = self._sumsq.get(name, 0.0) + ss
        # per-expert token counts: only rows actually dispatched (unused
        # capacity slots are zero rows and must not inflate the count)
        nz = jnp.sum(jnp.any(f != 0, axis=-1), axis=1)   # (E,)
        self._count[name] = self._count.get(name, 0) + nz
        if self._want_hess(name):
            g = self._gram(x, f"stk{stack_axis}",
                           lambda: jnp.einsum("eti,etj->eij", f, f))
            self._hess[name] = self._hess.get(name, 0.0) + g

    # -- queries -----------------------------------------------------

    def names(self) -> List[str]:
        return sorted(self._sumsq)

    def has(self, name: str) -> bool:
        return name in self._sumsq

    def norms(self, name: str) -> Array:
        return jnp.sqrt(self._sumsq[name])

    def hessian(self, name: str) -> Optional[Array]:
        return self._hess.get(name)

    def token_count(self, name: str):
        """Recorded token rows: an int for flat taps, an (E,) array of
        per-expert dispatched counts for stacked taps."""
        return self._count.get(name, 0)


@contextlib.contextmanager
def tap_capture(hessian: bool = False,
                hessian_names: Optional[set] = None):
    """Activate activation recording for the enclosed eager forward."""
    cap = TapCapture(hessian=hessian, hessian_names=hessian_names)
    _tap_captures().append(cap)
    try:
        yield cap
    finally:
        _tap_captures().remove(cap)


@contextlib.contextmanager
def tap_scope(prefix: str):
    """Push a name component: taps inside record as '<prefix>.<leaf>',
    and the ops traced inside carry the named scope ``prefix``."""
    stack = _tap_prefix()
    stack.append(prefix)
    try:
        with scope(prefix):
            yield
    finally:
        stack.pop()


def tap_active() -> bool:
    return bool(_tap_captures())


def _full_tap_name(leaf: str) -> str:
    pre = _tap_prefix()
    return ".".join(pre + [leaf]) if pre else leaf


def tap_record(leaf: str, x: Array) -> None:
    """Report a linear's input under the current scope. No-op unless a
    capture is active (the check is one empty-list test)."""
    caps = _tap_captures()
    if not caps:
        return
    name = _full_tap_name(leaf)
    for cap in caps:
        cap.record(name, x)


def tap_record_stacked(leaf: str, x: Array, stack_axis: int) -> None:
    """Per-expert variant: ``stack_axis`` indexes the expert dim."""
    caps = _tap_captures()
    if not caps:
        return
    name = _full_tap_name(leaf)
    for cap in caps:
        cap.record_stacked(name, x, stack_axis)


def is_axes_leaf(x) -> bool:
    """A logical-axes annotation: plain tuple of str/None. Excludes
    namedtuples (KVCache, MambaCache, …) which are pytree containers."""
    return (isinstance(x, tuple) and not hasattr(x, "_fields")
            and all(a is None or isinstance(a, str) for a in x))


# ------------------------------------------------------------------
# Config
# ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One architecture from the assignment (full or reduced)."""

    name: str
    family: str                  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_head: int
    d_ff: int
    vocab: int
    act: str = "swiglu"          # swiglu | relu2 | gelu
    rope: str = "rope"           # rope | mrope | none
    rope_theta: float = 10_000.0
    mrope_sections: Tuple[int, int, int] = (0, 0, 0)
    # MoE
    n_experts: int = 0
    top_k: int = 0
    shared_ff: int = 0           # total d_ff of the shared-expert branch
    capacity_factor: float = 1.25
    moe_group: int = 1024        # tokens per dispatch group (sort-free MoE)
    # SSM (mamba2)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # hybrid (zamba2): one *shared* attention block applied every k layers
    attn_every: int = 0
    # misc
    causal: bool = True
    input_mode: str = "tokens"   # tokens | embeds (audio/vlm stub frontends)
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    # the block: "rms" norms, or "layer" (LayerNorm with a bias); the
    # share of each head's leading dims that rotary rotates; a per-head
    # LayerNorm (scale only) on q and k before rotary; attention and MLP
    # reading one normalized input beside the residual (one norm per
    # layer) instead of one after the other
    norm: str = "rms"
    rotary_pct: float = 1.0
    qk_norm: bool = False
    parallel_residual: bool = False
    q_chunk: int = 512           # query-chunked attention block size
    kv_quant: bool = False       # int8 KV cache (beyond-paper serve opt)
    dtype: Any = jnp.bfloat16

    @property
    def d_q(self) -> int:
        return self.n_heads * self.d_head

    @property
    def d_kv(self) -> int:
        return self.n_kv * self.d_head

    @property
    def rotary_dims(self) -> int:
        return int(self.d_head * self.rotary_pct)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def conv_dim(self) -> int:
        # x-branch + B + C streams go through the depthwise conv (n_groups=1)
        return self.d_inner + 2 * self.ssm_state

    def with_(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


# ------------------------------------------------------------------
# Initializers
# ------------------------------------------------------------------

def dense_init(key: Array, shape: Tuple[int, ...], in_dim: int, dtype) -> Array:
    """Truncated-normal fan-in init (LLM-standard)."""
    scale = in_dim ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def embed_init(key: Array, shape: Tuple[int, ...], dtype) -> Array:
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ------------------------------------------------------------------
# Norms / activations
# ------------------------------------------------------------------

def rms_norm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x: Array, scale: Array, bias: Optional[Array] = None,
               eps: float = 1e-5) -> Array:
    """LayerNorm over the last axis in float32; ``scale`` (and ``bias``)
    broadcast against x's trailing axes, so an (H, dh) scale normalizes
    each head of an (..., H, dh) input with its own weight."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    xc = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def activation(x: Array, kind: str) -> Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu2":            # nemotron-4 squared ReLU
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(f"unknown activation {kind}")


# ------------------------------------------------------------------
# Rotary embeddings (RoPE and Qwen2-VL M-RoPE)
# ------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x (B, S, H, dh); positions (B, S) int32. Split-half convention."""
    b, s, h, dh = x.shape
    freqs = rope_freqs(dh, theta)                       # (dh/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (B, S, dh/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: Array, positions: Array, theta: float,
                sections: Tuple[int, int, int]) -> Array:
    """Qwen2-VL multimodal RoPE. positions (B, S, 3) = (t, h, w) ids;
    rotary frequency groups are split across the three streams
    (sections sum to dh/2). For text tokens all three ids coincide and
    M-RoPE reduces exactly to 1-D RoPE."""
    b, s, h, dh = x.shape
    freqs = rope_freqs(dh, theta)                        # (dh/2,)
    ang3 = positions.astype(jnp.float32)[:, :, None, :] * freqs[None, None, :, None]
    # select which stream drives each frequency                        (B,S,dh/2,3)
    sec = jnp.concatenate([
        jnp.full((n,), i, jnp.int32) for i, n in enumerate(sections)])
    ang = jnp.take_along_axis(ang3, sec[None, None, :, None].astype(jnp.int32),
                              axis=-1)[..., 0]           # (B, S, dh/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def positions_for(cfg: ArchConfig, batch: int, seq: int,
                  offset: int | Array = 0) -> Array:
    """Default position ids (text stream). M-RoPE gets (B,S,3)."""
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (batch, seq))
    if cfg.rope == "mrope":
        return jnp.broadcast_to(pos[..., None], (batch, seq, 3))
    return pos


def _rotate_all(cfg: ArchConfig, x: Array, positions: Array) -> Array:
    if cfg.rope == "rope":
        return apply_rope(x, positions, cfg.rope_theta)
    if cfg.rope == "mrope":
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return x


def rotate(cfg: ArchConfig, x: Array, positions: Array) -> Array:
    """Rotary on the leading ``cfg.rotary_dims`` of each head, with the
    frequencies and split-half pairs of that slice; the other dims pass
    unchanged."""
    n = cfg.rotary_dims
    if n < x.shape[-1]:
        return jnp.concatenate(
            [_rotate_all(cfg, x[..., :n], positions), x[..., n:]], axis=-1)
    return _rotate_all(cfg, x, positions)


# ------------------------------------------------------------------
# Cross-entropy (vocab-sharding friendly: logits stay (…, V))
# ------------------------------------------------------------------

def softmax_xent(logits: Array, labels: Array, mask: Optional[Array] = None
                 ) -> Array:
    """Mean next-token CE. logits (B,S,V) any float dtype, labels (B,S).

    One-hot (multiply+reduce) label pick instead of take_along_axis so a
    vocab-sharded logits tensor never gets gathered: both the logsumexp
    and the label-select lower to sharded reductions + tiny all-reduces
    under GSPMD."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    vocab_ids = jnp.arange(logits.shape[-1], dtype=jnp.int32)
    onehot = (labels[..., None].astype(jnp.int32) == vocab_ids)
    ll = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    nll = lse - ll
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
