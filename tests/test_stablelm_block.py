"""StableLM-2's block in the program against the plain float32 reference
(``chipbench/archs/stablelm.py``), on seeded random weights with norm
scales and biases away from one and zero: the full forward, prefill then
decode on the contiguous cache, and on the paged cache through the
engine's step and through ``Engine.run``. Smoke widths with 40-wide heads,
so rotary turns 10 dims, not a multiple of 8. A program with one part of
the block left out fails the same tolerance. Then the compress -> pack ->
serve path on the registry's smoke config."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.archs import stablelm as ref
from repro import configs
from repro.core.pipeline import layer_tap_stats
from repro.core.packed_model import PackedLinear
from repro.models import lm
from repro.models.common import positions_for
from repro.serving import Engine, EngineConfig, Request, init_paged_cache

CFG = configs.get("stablelm_12b", smoke=True).with_(d_head=40,
                                                    dtype=jnp.float32)
B, S, BLOCK = 2, 12, 4
# Worst |program - reference| over all logits, relative to the largest
# reference logit. Both sides compute in float32; they differ only in
# the order of sums (the program's attention is chunked and its kernels
# tile): 4e-7 here. A part of the block left out moves them by 0.15 and
# more.
TOL = 1e-4


def _randomize_norms(params, key):
    """Norm scales uniform in [0.75, 1.25], biases in [-0.1, 0.1]."""
    ks = iter(jax.random.split(key, 6))

    def u(a, lo, hi):
        return jax.random.uniform(next(ks), a.shape, jnp.float32, lo, hi)

    lay = params["layers"]
    attn = dict(lay["attn"], q_norm=u(lay["attn"]["q_norm"], 0.75, 1.25),
                k_norm=u(lay["attn"]["k_norm"], 0.75, 1.25))
    lay = dict(lay, attn=attn,
               attn_norm=u(lay["attn_norm"], 0.75, 1.25),
               attn_norm_bias=u(lay["attn_norm_bias"], -0.1, 0.1))
    return dict(params, layers=lay,
                final_norm=u(params["final_norm"], 0.75, 1.25),
                final_norm_bias=u(params["final_norm_bias"], -0.1, 0.1))


@pytest.fixture(scope="module")
def params():
    p, _ = lm.init(CFG, jax.random.PRNGKey(0))
    return _randomize_norms(p, jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(2)
    return jnp.asarray(rng.integers(0, CFG.vocab, size=(B, S)), jnp.int32)


def reference_logits(cfg, params, tokens):
    """The plain reference's full forward over the program's weights."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)          # noqa: E731
    dims = (cfg.n_heads, cfg.n_kv, cfg.d_head, cfg.rotary_dims,
            cfg.rope_theta, cfg.norm_eps)
    h = f32(params["embed"][tokens])
    for l in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: f32(a[l]), params["layers"])
        w = {f"{g}.{n}": lp[g][n] for g in ("attn", "mlp") for n in lp[g]
             if n.startswith("w")}
        h = ref._layer(w, lp["attn_norm"], lp["attn_norm_bias"],
                       lp["attn"]["q_norm"], lp["attn"]["k_norm"], h, dims,
                       None)
    return ref._logits(h, f32(params["final_norm"]),
                       f32(params["final_norm_bias"]),
                       params["lm_head"], cfg.norm_eps, None)


def _err(got, want):
    want = np.asarray(want)
    return float(np.max(np.abs(np.asarray(got) - want))
                 / np.max(np.abs(want)))


def paged_logits(params, tokens, prompt):
    """Logits of every position through ``lm.paged_decode_step``, fed
    one token at a time as the engine's step feeds them, over a paged
    cache whose blocks are scattered in the pool: the first ``prompt``
    tokens are given (prefill), each later one is the program's greedy
    pick (decode). Returns (tokens fed, logits)."""
    b, s = tokens.shape
    n_bt = -(-s // BLOCK)
    rng = np.random.default_rng(3)
    tables = jnp.asarray(rng.permutation(4 * b * n_bt)[:b * n_bt]
                         .reshape(b, n_bt), jnp.int32)
    pool = init_paged_cache(CFG, 4 * b * n_bt, BLOCK)
    step = jax.jit(functools.partial(lm.paged_decode_step, CFG))
    fed, out = np.array(tokens), []
    for t in range(s):
        if t >= prompt:
            fed[:, t] = np.asarray(jnp.argmax(out[-1], -1))
        lg, pool = step(params, pool, tables, jnp.full((b,), t, jnp.int32),
                        jnp.asarray(fed[:, t:t + 1]), jnp.ones((b,), bool))
        out.append(lg[:, 0])
    return jnp.asarray(fed), jnp.stack(out, 1)


def test_forward_matches_the_reference(params, tokens):
    got, _ = lm.forward(CFG, params, tokens)
    assert _err(got, reference_logits(CFG, params, tokens)) < TOL


def test_contiguous_decode_matches_the_reference(params, tokens):
    cache = lm.init_cache(CFG, B, S)
    out = []
    for t in range(S):
        lg, cache = lm.decode_step(CFG, params, cache, tokens[:, t:t + 1],
                                   positions_for(CFG, B, 1, offset=t))
        out.append(lg[:, -1])
    got = jnp.stack(out, 1)
    assert _err(got, reference_logits(CFG, params, tokens)) < TOL


def test_paged_prefill_then_decode_matches_the_reference(params, tokens):
    seq, got = paged_logits(params, tokens, prompt=8)
    assert _err(got, reference_logits(CFG, params, seq)) < TOL


def test_engine_serves_the_references_tokens(params):
    """Requests through ``Engine.run`` (chunked prefill, decode, scattered
    blocks): each served token's reference logit lies within 2 * TOL of
    the reference's best (the program's argmax, off by at most TOL on
    each side)."""
    rng = np.random.default_rng(4)
    reqs = [Request(rid=i, prompt=rng.integers(0, CFG.vocab, size=p)
                    .astype(np.int32), max_new=n, arrival=a)
            for i, (p, n, a) in enumerate([(9, 6, 0.0), (5, 8, 1.0),
                                           (14, 4, 2.0)])]
    eng = Engine(CFG, params, EngineConfig(n_slots=2, n_blocks=24,
                                           block_size=BLOCK, max_len=32,
                                           prefill_chunk=4))
    done = eng.run(reqs, clock="steps", max_steps=500)
    for r in done:
        assert r.status == "finished" and len(r.out) == r.max_new
        seq = jnp.asarray(np.concatenate([r.prompt, r.out[:-1]])[None])
        want = np.asarray(reference_logits(CFG, params, seq))[0]
        rows = want[len(r.prompt) - 1:]
        gap = rows.max(-1) - rows[np.arange(len(r.out)), r.out]
        assert np.max(gap) <= 2 * TOL * np.max(np.abs(want)), (r.rid, gap)


def _without(part, params):
    """The program with one part of the block left out."""
    if part == "qk_norm":
        return CFG.with_(qk_norm=False), params
    if part == "norm_bias":
        zero = lambda t, k: dict(t, **{k: jnp.zeros_like(t[k])})  # noqa
        return CFG, dict(zero(params, "final_norm_bias"),
                         layers=zero(params["layers"], "attn_norm_bias"))
    if part == "parallel_residual":       # sequential, same norm weights
        lay = params["layers"]
        return CFG.with_(parallel_residual=False), dict(
            params, layers=dict(lay, mlp_norm=lay["attn_norm"],
                                mlp_norm_bias=lay["attn_norm_bias"]))
    if part == "partial_rotary":
        return CFG.with_(rotary_pct=1.0), params
    raise ValueError(part)


@pytest.mark.parametrize("part", ["qk_norm", "norm_bias",
                                  "parallel_residual", "partial_rotary"])
def test_a_part_left_out_fails_the_tolerance(params, tokens, part):
    cfg, p = _without(part, params)
    got, _ = lm.forward(cfg, p, tokens)
    assert _err(got, reference_logits(CFG, params, tokens)) > TOL


# ----------------------------------------------------------------------
# compress -> pack -> serve on the registry's smoke config
# ----------------------------------------------------------------------

def test_calibration_taps_attention_and_mlp_on_one_input():
    """Under the parallel residual the MLP reads the attention's normed
    input: their tapped statistics are the same."""
    cfg = configs.get("stablelm_12b", smoke=True).with_(dtype=jnp.float32)
    p, _ = lm.init(cfg, jax.random.PRNGKey(0))
    p = _randomize_norms(p, jax.random.PRNGKey(1))
    lp = jax.tree.map(lambda a: a[0], p["layers"])
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)), jnp.float32)
    acts, hess = layer_tap_stats(cfg, p, lp, 0, h,
                                 positions_for(cfg, 2, 16), hessian=True)
    assert set(acts) == {"attn.wq", "attn.wk", "attn.wv", "attn.wo",
                         "mlp.w_gate", "mlp.w_up", "mlp.w_down"}
    for name in ("mlp.w_gate", "mlp.w_up"):
        np.testing.assert_array_equal(acts[name], acts["attn.wq"])
        np.testing.assert_array_equal(hess[name], hess["attn.wq"])


def test_serve_packs_every_linear_and_no_norm():
    from repro.launch import serve
    cfg = configs.get("stablelm_12b", smoke=True)
    args = serve.build_parser().parse_args(
        ["--engine", "--requests", "2", "--batch", "2", "--block-size", "4",
         "--prompt-len", "8", "--gen-len", "3", "--compress", "slab",
         "--pattern", "2:4", "--cr", "0.5", "--iters", "1",
         "--calib-seqs", "1", "--calib-len", "16", "--packed"])
    res = serve.serve(args, cfg=cfg)
    assert dict(res.report.by_variant) == {"slab-nm": 7 * cfg.n_layers}
    assert not res.report.fallback
    assert all(r.status == "finished" for r in res.requests)
    lay, ref_lay = res.params["layers"], res.reference["layers"]
    for grp, name in [(None, "attn_norm"), (None, "attn_norm_bias"),
                      ("attn", "q_norm"), ("attn", "k_norm")]:
        got = lay[grp][name] if grp else lay[name]
        want = ref_lay[grp][name] if grp else ref_lay[name]
        assert not isinstance(got, PackedLinear)
        np.testing.assert_array_equal(got, want)
    for name in ("wq", "wk", "wv", "wo"):
        assert isinstance(lay["attn"][name], PackedLinear)
