"""StableLM-2-12B. [hf:stabilityai/stablelm-2-12b config.json; hf]
40L d_model=5120 32H (GQA kv=8) head_dim 160 d_ff=13824 vocab=100352.
The block: LayerNorm with bias, one per layer; attention and the
SiLU-gated MLP read the same normed input beside the residual
(use_parallel_residual); per-head LayerNorm on q and k (qk_layernorm);
rotary on the first 25% of each head (partial_rotary_factor 0.25)."""
from repro.models.common import ArchConfig

FULL = ArchConfig(
    name="stablelm-12b", family="dense",
    n_layers=40, d_model=5120, n_heads=32, n_kv=8, d_head=160,
    d_ff=13824, vocab=100352, act="swiglu", rope="rope",
    rope_theta=10_000.0, norm_eps=1e-5, norm="layer", rotary_pct=0.25,
    qk_norm=True, parallel_residual=True,
)

SMOKE = FULL.with_(
    name="stablelm-12b-smoke",
    n_layers=2, d_model=128, n_heads=4, n_kv=2, d_head=32,
    d_ff=256, vocab=512, q_chunk=64,
)
