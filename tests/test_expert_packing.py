"""Expert-axis packed serving: 3-D MoE leaves pack into K_max-bucketed
ExpertPackedStacks served by the per-linear fused kernels under
``jax.vmap`` (interpret mode on CPU), the hybrid shared block packs into plain PackedLinears,
and end-to-end MoE traces — including PR-7 engine eviction replay —
stay token-exact against the dense model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.apply import slab_linear
from repro.core.packed_model import (ExpertPackedStack, PackedLinear,
                                     PackedStack, expert_matmul,
                                     pack_expert_stack, pack_plan_decs)
from repro.core.pipeline import (collect_model_stats, compress_model,
                                 linear_paths)
from repro.core.plan import CompressionPlan
from repro.core.slab import SLaBConfig, SLaBDecomposition, reconstruct
from repro.core.sparsity import prune_mask
from repro.data import calibration_batch
from repro.launch.serve import greedy_decode
from repro.models import lm
from repro.models.common import positions_for
from repro.serving import Engine, EngineConfig, Request

EXPERT_PATHS = ("moe.w_gate", "moe.w_up", "moe.w_down")


def _cfg(arch="phi3_5_moe", **kw):
    return configs.get(arch, smoke=True).with_(dtype=jnp.float32, **kw)


def _compress_packed(cfg, plan_spec, seed=0, iters=2):
    params, _ = lm.init(cfg, jax.random.PRNGKey(seed))
    cal = calibration_batch(cfg.vocab, n_seq=2, seq_len=16)
    plan = CompressionPlan.parse(plan_spec,
                                 base=SLaBConfig(cr=0.5, iters=iters))
    dense_c, stats, decs = compress_model(cfg, params, cal, plan=plan,
                                          keep_decompositions=True)
    # the serve.py flow: hand the pipeline's classification through so
    # expert tuples short-circuit past the per-linear variants map
    packed, rep = pack_plan_decs(
        dense_c, decs, cfg.n_layers, plan,
        variants={(s.layer, s.name): s.variant for s in stats})
    return dense_c, packed, rep, stats, decs, plan


def _max_rel(a, b):
    return (float(jnp.max(jnp.abs(a - b)))
            / max(float(jnp.max(jnp.abs(a))), 1e-12))


@pytest.fixture(scope="module")
def moe_setup():
    cfg = _cfg()
    return (cfg,) + _compress_packed(cfg, "*=slab")


# ------------------------------------------------------------------
# pack_expert_stack units: bucketing, dense members, permutations
# ------------------------------------------------------------------

def _edec(seed, n=64, k=128, *, keep=0.4, rank=2, binary=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    w = jax.random.normal(ks[0], (n, k), jnp.float32) * 0.1
    w_s = jnp.where(prune_mask(jnp.abs(w), keep), w, 0.0)
    if rank:
        u = jax.random.normal(ks[1], (n, rank), jnp.float32) * 0.2
        v = jax.random.normal(ks[2], (k, rank), jnp.float32) * 0.2
    else:
        u = jnp.zeros((n, 0), jnp.float32)
        v = jnp.zeros((k, 0), jnp.float32)
    if binary:
        w_b = jnp.where(jax.random.bernoulli(ks[3], 0.5, (n, k)),
                        1, -1).astype(jnp.int8)
    else:
        w_b = jnp.zeros((0, 0), jnp.int8)
    return SLaBDecomposition(w_s, u, v, w_b)


def _unservable_dec(n=64, k=128):
    # no sparse plane at all: variant_of -> None (an all-ZERO w_s would
    # instead pack as a servable width-1 ELL serving zeros)
    return SLaBDecomposition(None,
                             jnp.zeros((n, 0), jnp.float32),
                             jnp.zeros((k, 0), jnp.float32),
                             jnp.zeros((0, 0), jnp.int8))


def test_mixed_kmax_buckets_pad_to_bucket_max():
    """Experts with very different realized row-nnz land in different
    K_max buckets: each bucket pads to ITS realized max, never the
    global one."""
    decs = tuple(_edec(s, keep=kp)
                 for s, kp in enumerate((0.05, 0.08, 0.4, 0.45)))
    old = jax.random.normal(jax.random.PRNGKey(9), (4, 128, 64))
    eps = pack_expert_stack(old, decs, None)
    assert isinstance(eps, ExpertPackedStack)
    assert eps.dense_members == () and eps.dense is None
    flat = sorted(e for mem in eps.members for e in mem)
    assert flat == [0, 1, 2, 3]
    assert len(eps.groups) >= 2             # sparse vs dense-ish buckets
    kmaxes = [int(jnp.max(jnp.sum(d.w_s != 0, -1))) for d in decs]
    for grp, mem in zip(eps.groups, eps.members):
        pad = grp.sparse_idx.shape[-1]
        assert pad == max(kmaxes[e] for e in mem)   # bucket-realized max
    x = jax.random.normal(jax.random.PRNGKey(10), (4, 8, 128))
    got = expert_matmul(x, eps, interpret=True)
    for e, d in enumerate(decs):
        np.testing.assert_allclose(np.asarray(got[e]),
                                   np.asarray(slab_linear(x[e], d)),
                                   rtol=1e-4, atol=1e-4)


def test_expert_stack_dense_member_and_permutation():
    """An unservable expert (no packable terms) rides the dense slice of
    ``old``; the bucket gather/scatter restores expert order even when
    groups interleave member ids."""
    decs = (_edec(0, keep=0.45), _unservable_dec(), _edec(2, keep=0.05),
            _edec(3, keep=0.45))
    old = jax.random.normal(jax.random.PRNGKey(11), (4, 128, 64)) * 0.1
    eps = pack_expert_stack(old, decs, None)
    assert eps.dense_members == (1,)
    assert eps.dense.shape == (1, 128, 64)
    assert 1 not in {e for mem in eps.members for e in mem}
    x = jax.random.normal(jax.random.PRNGKey(12), (4, 8, 128))
    got = expert_matmul(x, eps, interpret=True)
    for e in (0, 2, 3):
        np.testing.assert_allclose(np.asarray(got[e]),
                                   np.asarray(slab_linear(x[e], decs[e])),
                                   rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got[1]),
                               np.asarray(x[1] @ old[1]),
                               rtol=1e-4, atol=1e-4)


def test_single_bucket_full_coverage_fast_path():
    """Same-signature experts collapse to one group covering every
    expert id — the no-gather launch path."""
    decs = tuple(_edec(s, keep=0.4) for s in range(4))
    old = jax.random.normal(jax.random.PRNGKey(13), (4, 128, 64))
    eps = pack_expert_stack(old, decs, None)
    assert len(eps.groups) == 1 and eps.members == ((0, 1, 2, 3),)
    x = jax.random.normal(jax.random.PRNGKey(14), (4, 8, 128))
    got = expert_matmul(x, eps, interpret=True)
    for e, d in enumerate(decs):
        np.testing.assert_allclose(np.asarray(got[e]),
                                   np.asarray(slab_linear(x[e], d)),
                                   rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------
# Full-model MoE packing + parity
# ------------------------------------------------------------------

def test_moe_packs_every_expert_zero_fallback(moe_setup):
    """The acceptance property: a full-coverage slab plan on an MoE
    model leaves NO dense-fallback linears — every expert of every 3-D
    leaf serves on a packed kernel."""
    cfg, _, packed, rep, stats, _, _ = moe_setup
    assert rep.fallback == []
    n_expert = len(EXPERT_PATHS) * cfg.n_layers * cfg.n_experts
    n_2d = cfg.n_layers * (len(linear_paths(cfg)) - len(EXPERT_PATHS))
    assert rep.n_packed == n_2d + n_expert
    assert sum(rep.by_variant.values()) == rep.n_packed
    assert "dense-fallback" not in rep.bytes_by_variant
    for var, (pb, db) in rep.bytes_by_variant.items():
        assert pb < db, (var, pb, db)       # expert-packed bytes win too
    for p in EXPERT_PATHS:
        k = p.split(".")[1]
        assert isinstance(packed["layers"]["moe"][k],
                          (ExpertPackedStack, PackedStack))
        assert p in rep.paths
    # every 3-D leaf's stats row carries the expert classification
    assert all(s.variant == "expert" for s in stats
               if s.name in EXPERT_PATHS)


def test_moe_forward_matches_dense(moe_setup):
    cfg, dense_c, packed, _, _, _, _ = moe_setup
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    f_d, _ = lm.forward(cfg, dense_c, toks)
    f_p, _ = lm.forward(cfg, packed, toks)
    assert _max_rel(f_d, f_p) < 1e-4


def test_moe_greedy_decode_token_exact(moe_setup):
    """Greedy decode through the expert-packed kernels emits the SAME
    tokens as the dense-applied model at f32."""
    cfg, dense_c, packed, _, _, _, _ = moe_setup
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                 cfg.vocab)
    want = np.asarray(greedy_decode(cfg, dense_c, prompts, 6))
    got = np.asarray(greedy_decode(cfg, packed, prompts, 6))
    assert np.array_equal(got, want)


def test_moe_decode_step_matches_dense(moe_setup):
    cfg, dense_c, packed, _, _, _, _ = moe_setup
    b, s = 2, 3
    toks = jax.random.randint(jax.random.PRNGKey(3), (b, s), 0, cfg.vocab)
    cd = lm.init_cache(cfg, b, s)
    cp = lm.init_cache(cfg, b, s)
    for t in range(s):
        pos = positions_for(cfg, b, 1, offset=t)
        ld, cd = lm.decode_step(cfg, dense_c, cd, toks[:, t:t + 1], pos)
        lp, cp = lm.decode_step(cfg, packed, cp, toks[:, t:t + 1], pos)
    assert _max_rel(ld, lp) < 1e-4


def test_mixed_buckets_full_model_parity(moe_setup):
    """Force one expert into a lower K_max bucket than its peers (the
    ragged case) and check the whole forward still matches the
    dense-applied decompositions."""
    cfg, dense_c, _, _, _, decs, plan = moe_setup
    decs2 = dict(decs)
    tup = list(decs2[(0, "moe.w_up")])
    d0 = tup[0]
    w_s = jnp.where(prune_mask(jnp.abs(d0.w_s), 0.08), d0.w_s, 0.0)
    tup[0] = SLaBDecomposition(w_s, d0.u, d0.v, d0.w_b)
    decs2[(0, "moe.w_up")] = tuple(tup)
    dense2 = jax.tree.map(lambda a: a, dense_c)
    old = dense2["layers"]["moe"]["w_up"]
    w0 = reconstruct(tup[0]).T.astype(old.dtype)
    dense2["layers"]["moe"]["w_up"] = old.at[0, 0].set(w0)
    packed2, rep2 = pack_plan_decs(dense2, decs2, cfg.n_layers, plan)
    assert rep2.fallback == []
    leaf = packed2["layers"]["moe"]["w_up"]
    eps0 = leaf.at_layer(0) if isinstance(leaf, PackedStack) else leaf
    assert isinstance(eps0, ExpertPackedStack)
    assert len(eps0.groups) >= 2            # the re-pruned expert split off
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, cfg.vocab)
    f_d, _ = lm.forward(cfg, dense2, toks)
    f_p, _ = lm.forward(cfg, packed2, toks)
    assert _max_rel(f_d, f_p) < 1e-4


def test_unservable_expert_reports_dense_fallback(moe_setup):
    """One expert with no packable terms: it serves from the dense
    slice, is named in the fallback list, its bytes land under the
    "dense-fallback" pseudo-variant, and parity still holds."""
    cfg, dense_c, _, _, _, decs, plan = moe_setup
    decs2 = dict(decs)
    tup = list(decs2[(0, "moe.w_down")])
    n, k = tup[1].w_s.shape
    tup[1] = _unservable_dec(n, k)
    decs2[(0, "moe.w_down")] = tuple(tup)
    packed2, rep2 = pack_plan_decs(dense_c, decs2, cfg.n_layers, plan)
    assert (0, "moe.w_down[expert 1]") in rep2.fallback
    pb, db = rep2.bytes_by_variant["dense-fallback"]
    assert pb == db > 0                     # still-dense bytes, reported
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0, cfg.vocab)
    f_d, _ = lm.forward(cfg, dense_c, toks)
    f_p, _ = lm.forward(cfg, packed2, toks)
    assert _max_rel(f_d, f_p) < 1e-4


def test_zero_gram_expert_still_packs_and_matches():
    """An expert no calibration tokens route to (all-zero Gram) takes
    the identity-Hessian fallback: it must still produce a servable dec
    and match the dense-applied model."""
    cfg = _cfg()
    params, _ = lm.init(cfg, jax.random.PRNGKey(7))
    cal = calibration_batch(cfg.vocab, n_seq=2, seq_len=16)
    plan = CompressionPlan.parse("moe.*=sparsegpt; *=slab",
                                 base=SLaBConfig(cr=0.5, iters=2))
    stats = collect_model_stats(cfg, params, cal, plan=plan)
    for l in range(cfg.n_layers):
        for p in EXPERT_PATHS:             # starve expert 2 everywhere
            if (l, p) in stats.hessians:
                stats.hessians[(l, p)] = \
                    stats.hessians[(l, p)].at[2].set(0.0)
            stats.norms[(l, p)] = stats.norms[(l, p)].at[2].set(0.0)
    dense_c, cstats, decs = compress_model(cfg, params, None, plan=plan,
                                           stats=stats,
                                           keep_decompositions=True)
    packed, rep = pack_plan_decs(dense_c, decs, cfg.n_layers, plan)
    assert rep.fallback == []              # identity fallback is servable
    toks = jax.random.randint(jax.random.PRNGKey(8), (2, 16), 0, cfg.vocab)
    f_d, _ = lm.forward(cfg, dense_c, toks)
    f_p, _ = lm.forward(cfg, packed, toks)
    assert _max_rel(f_d, f_p) < 1e-4


@pytest.mark.slow
def test_deepseek_shared_experts_pack_and_match():
    """DeepSeek-MoE geometry: routed experts pack on the expert axis
    while the always-on shared MLP packs as plain 2-D linears — zero
    fallback, forward parity."""
    cfg = _cfg("deepseek_moe_16b")
    dense_c, packed, rep, _, _, _ = _compress_packed(cfg, "*=slab")
    assert rep.fallback == []
    assert isinstance(packed["layers"]["moe"]["w_gate"],
                      (ExpertPackedStack, PackedStack))
    assert "moe.shared.w_gate" in rep.paths
    toks = jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0, cfg.vocab)
    f_d, _ = lm.forward(cfg, dense_c, toks)
    f_p, _ = lm.forward(cfg, packed, toks)
    assert _max_rel(f_d, f_p) < 1e-4


# ------------------------------------------------------------------
# Hybrid shared block (zamba2): packed once, outside the layer stack
# ------------------------------------------------------------------

@pytest.fixture(scope="module")
def zamba_setup():
    cfg = _cfg("zamba2_7b", n_layers=3)     # shared block fires at L2
    return (cfg,) + _compress_packed(cfg, "*=slab")


def test_shared_block_packs_and_matches(zamba_setup):
    """Every shared.* linear becomes a PackedLinear inside
    params["shared_attn"] and the hybrid forward matches dense."""
    cfg, dense_c, packed, rep, _, _, _ = zamba_setup
    assert rep.fallback == []
    shared = [p for p in rep.paths if p.startswith("shared.")]
    assert len(shared) == 7                 # wq wk wv wo + swiglu mlp
    for p in shared:
        node = packed["shared_attn"]
        for part in p.split(".")[1:]:
            node = node[part]
        assert isinstance(node, PackedLinear), p
    toks = jax.random.randint(jax.random.PRNGKey(15), (2, 8), 0,
                              cfg.vocab)
    f_d, _ = lm.forward(cfg, dense_c, toks)
    f_p, _ = lm.forward(cfg, packed, toks)
    assert _max_rel(f_d, f_p) < 1e-4


@pytest.mark.slow
def test_shared_block_decode_matches_dense(zamba_setup):
    cfg, dense_c, packed, _, _, _, _ = zamba_setup
    b, s = 2, 3
    toks = jax.random.randint(jax.random.PRNGKey(16), (b, s), 0,
                              cfg.vocab)
    cd = lm.init_cache(cfg, b, s)
    cp = lm.init_cache(cfg, b, s)
    for t in range(s):
        pos = positions_for(cfg, b, 1, offset=t)
        ld, cd = lm.decode_step(cfg, dense_c, cd, toks[:, t:t + 1], pos)
        lp, cp = lm.decode_step(cfg, packed, cp, toks[:, t:t + 1], pos)
    assert _max_rel(ld, lp) < 1e-4


# ------------------------------------------------------------------
# PR-7 engine: eviction replay through the expert-packed kernels
# ------------------------------------------------------------------

@pytest.mark.slow
def test_engine_eviction_replay_packed_moe(moe_setup):
    """A pool too small for all streams forces evict -> requeue ->
    recompute through the expert-packed model; greedy determinism makes
    the replay token-exact vs per-request greedy_decode."""
    cfg, _, packed, _, _, _, _ = moe_setup
    rng = np.random.default_rng(17)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, size=p,
                                        dtype=np.int64).astype(np.int32),
                    max_new=n, arrival=a)
            for i, (p, n, a) in enumerate([(10, 6, 0.0), (12, 6, 0.0),
                                           (8, 6, 0.0)])]
    eng = Engine(cfg, packed,
                 EngineConfig(n_slots=3, n_blocks=8, block_size=4,
                              max_len=32, prefill_chunk=4))
    done = eng.run(reqs, clock="steps", max_steps=2000)
    assert eng.sched.n_evictions > 0        # the point of this pool size
    for r in done:
        want = np.asarray(greedy_decode(
            cfg, packed, jnp.asarray(r.prompt)[None, :], r.max_new))[0]
        assert np.array_equal(np.asarray(r.out, np.int32), want), r.rid
