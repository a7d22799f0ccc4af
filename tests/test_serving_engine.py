"""Continuous-batching serving engine: scheduler policy units, paged
decode-step parity, and end-to-end open-loop traces (dense and
SLaB-packed) checked token-exact against per-request greedy_decode."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.pipeline import compress_model
from repro.core.slab import SLaBConfig
from repro.core.packed_model import pack_model
from repro.data import calibration_batch
from repro.launch.serve import greedy_decode
from repro.models import lm
from repro.serving import (BlockAllocator, Engine, EngineConfig, Request,
                           Scheduler, init_paged_cache)
from repro.serving.paged_cache import blocks_needed, paged_write


# ----------------------------------------------------------------------
# Block allocator / paged-cache units
# ----------------------------------------------------------------------

def test_allocator_all_or_nothing():
    a = BlockAllocator(4)
    got = a.alloc(3)
    assert got is not None and len(got) == 3 and a.n_free == 1
    assert a.alloc(2) is None            # insufficient: nothing taken
    assert a.n_free == 1
    a.free(got)
    assert a.n_free == 4


def test_allocator_rejects_double_free():
    a = BlockAllocator(2)
    ids = a.alloc(1)
    a.free(ids)
    with pytest.raises(ValueError):
        a.free(ids)


def test_blocks_needed():
    assert blocks_needed(1, 16) == 1
    assert blocks_needed(16, 16) == 1
    assert blocks_needed(17, 16) == 2


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_write_masks_inactive_rows(layer):
    pool = jax.random.normal(jax.random.PRNGKey(0),
                             (3, 4, 3, 2, 8))   # (L, n_blocks, KV, bs, dh)
    pool = pool.at[layer].set(0.0)
    new = jnp.ones((3, 8))               # one token's (KV, dh) per row
    out = paged_write(pool, jnp.stack([new, new * 5]), layer,
                      block_ids=jnp.array([1, 2]),
                      offsets=jnp.array([0, 1]),
                      active=jnp.array([True, False]))
    mine = out[layer]
    assert float(jnp.sum(jnp.abs(mine[2]))) == 0.0   # masked row dropped
    np.testing.assert_allclose(np.asarray(mine[1, :, 0]), np.asarray(new))
    assert float(jnp.sum(jnp.abs(mine[1, :, 1]))) == 0.0  # offset 0 only
    others = [i for i in range(pool.shape[0]) if i != layer]
    assert np.array_equal(np.asarray(out[others, ...]),
                          np.asarray(pool[others, ...]))   # bit-identical


def test_init_paged_cache_rejects_cacheless_families():
    cfg = configs.get("mamba2_1_3b", smoke=True)
    with pytest.raises(ValueError):
        init_paged_cache(cfg, 8, 16)


# ----------------------------------------------------------------------
# Scheduler policy units (no model involved)
# ----------------------------------------------------------------------

def _req(rid, p_len, max_new=4, arrival=0.0):
    return Request(rid=rid, prompt=np.full(p_len, rid + 1, np.int32),
                   max_new=max_new, arrival=arrival)


def test_scheduler_admits_in_arrival_order():
    s = Scheduler(n_slots=2, n_blocks=16, block_size=4, max_len=32)
    s.submit(_req(0, 4, arrival=5.0))
    s.submit(_req(1, 4, arrival=1.0))
    s.submit(_req(2, 4, arrival=3.0))
    assert s.admit(now=0.0) == []        # nothing has arrived
    s.admit(now=10.0)
    admitted = sorted(sl.req.rid for sl in s.slots.values())
    assert admitted == [1, 2]            # earliest arrivals fill slots
    assert [r.rid for r in s.waiting] == [0]


def test_scheduler_rejects_oversized_request():
    """Unservable requests reject-with-status instead of raising: one
    bad request must not kill the trace."""
    s = Scheduler(n_slots=1, n_blocks=4, block_size=4, max_len=16)
    r = _req(0, 14, max_new=8)               # 21 cached > max_len
    assert s.submit(r) is False
    assert r.status == "rejected" and "max_len" in r.error
    assert not s.pending and not s.waiting
    s2 = Scheduler(n_slots=1, n_blocks=2, block_size=4, max_len=32)
    r2 = _req(1, 12, max_new=8)
    assert s2.submit(r2) is False
    assert r2.status == "rejected" and "cannot ever run" in r2.error


def test_scheduler_retire_frees_blocks_and_slot():
    s = Scheduler(n_slots=1, n_blocks=8, block_size=4, max_len=32,
                  prefill_chunk=8)
    s.submit(_req(0, 6, max_new=1))
    s.admit(0.0)
    plan = s.plan_step()
    assert plan is not None
    tokens, n_valid, any_prefill = plan
    assert any_prefill and n_valid[0] == 6
    assert s.alloc.n_free < 8
    retired = s.commit_step(n_valid, np.array([42]), now=1.0)
    assert [r.rid for r in retired] == [0]   # max_new=1: done after prefill
    assert retired[0].out == [42] and retired[0].ttft == 1.0
    assert s.alloc.n_free == 8 and not s.slots


def test_scheduler_evicts_lifo_and_requeues():
    # pool of 4 blocks x 4 tokens; two 8-token prompts fit exactly,
    # first decode-growth OOMs and must evict the LATEST admit
    s = Scheduler(n_slots=2, n_blocks=4, block_size=4, max_len=16,
                  prefill_chunk=8)
    s.submit(_req(0, 8, max_new=4, arrival=0.0))
    s.submit(_req(1, 8, max_new=4, arrival=1.0))
    s.admit(2.0)
    tokens, n_valid, _ = s.plan_step()
    s.commit_step(n_valid, np.array([7, 9]), now=3.0)
    assert all(sl.phase == "decode" for sl in s.slots.values())
    plan = s.plan_step()                 # both rows want block 5 -> OOM
    assert plan is not None
    tokens, n_valid, any_prefill = plan
    assert s.n_evictions == 1
    victims = [r.rid for r in s.waiting]
    assert victims == [1]                # LIFO: later arrival evicted
    # the victim's already-emitted token is folded into its replay prompt
    assert list(s.waiting[0].serve_prompt()[-1:]) == [9]
    survivors = [sl.req.rid for sl in s.slots.values()]
    assert survivors == [0] and n_valid[list(s.slots)[0]] == 1


def test_scheduler_admission_watermark_blocks_thrash():
    """A waiting request whose prompt exceeds free blocks must NOT be
    admitted (it would instantly evict itself back)."""
    s = Scheduler(n_slots=2, n_blocks=4, block_size=4, max_len=16,
                  prefill_chunk=16)
    s.submit(_req(0, 12, max_new=2))
    s.submit(_req(1, 12, max_new=2))
    s.admit(0.0)
    tokens, n_valid, _ = s.plan_step()
    s.commit_step(n_valid, np.array([3, 3]), now=1.0)
    running = [sl.req.rid for sl in s.slots.values()]
    assert running == [0]                # second stayed in the queue
    assert [r.rid for r in s.waiting] == [1]


# ----------------------------------------------------------------------
# End-to-end: engine output == per-request greedy_decode
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def dense_setup():
    cfg = configs.get("llama2_7b", smoke=True).with_(dtype=jnp.float32)
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def packed_setup():
    cfg = configs.get("stablelm_12b", smoke=True).with_(dtype=jnp.float32)
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    cal = calibration_batch(cfg.vocab, n_seq=4, seq_len=32)
    dense_c, stats, decs = compress_model(
        cfg, params, cal, method="slab",
        scfg=SLaBConfig(cr=0.5, iters=3, pattern="2:4"),
        keep_decompositions=True)
    packed = pack_model(dense_c, decs, cfg.n_layers, pattern="2:4")
    return cfg, packed


def _trace(cfg, specs, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, size=p, dtype=np.int64
                                        ).astype(np.int32),
                    max_new=n, arrival=a)
            for i, (p, n, a) in enumerate(specs)]


def _check_against_greedy(cfg, params, reqs):
    for r in reqs:
        want = np.asarray(greedy_decode(
            cfg, params, jnp.asarray(r.prompt)[None, :], r.max_new))[0]
        got = np.asarray(r.out, np.int32)
        assert np.array_equal(got, want), (
            f"rid={r.rid}: engine {got} != greedy {want}")


def test_engine_mixed_arrival_trace_matches_greedy(dense_setup):
    """≥3 requests, different prompt/output lengths, admitted at
    different steps, more requests than slots — token-exact vs the
    per-request static path."""
    cfg, params = dense_setup
    reqs = _trace(cfg, [(9, 6, 0.0), (17, 9, 2.0), (5, 12, 5.0),
                        (23, 4, 5.0)])
    eng = Engine(cfg, params,
                 EngineConfig(n_slots=3, n_blocks=32, block_size=4,
                              max_len=64, prefill_chunk=4))
    done = eng.run(reqs, clock="steps", max_steps=500)
    assert all(r.status == "finished" for r in done)
    assert all(r.n_generated == r.max_new for r in done)
    assert all(r.ttft is not None and r.finish is not None for r in done)
    # staggered arrivals really were admitted at different times
    assert len({r.ttft + r.arrival for r in done}) > 1
    _check_against_greedy(cfg, params, done)


def test_engine_eviction_replay_is_exact(dense_setup):
    """A pool too small for all streams forces evict -> requeue ->
    recompute; greedy determinism makes the replay token-exact."""
    cfg, params = dense_setup
    reqs = _trace(cfg, [(10, 8, 0.0), (12, 8, 0.0), (8, 8, 0.0)], seed=1)
    eng = Engine(cfg, params,
                 EngineConfig(n_slots=3, n_blocks=8, block_size=4,
                              max_len=32, prefill_chunk=4))
    done = eng.run(reqs, clock="steps", max_steps=2000)
    assert eng.sched.n_evictions > 0     # the point of this pool size
    _check_against_greedy(cfg, params, done)


def test_engine_packed_slab_trace_matches_greedy(packed_setup):
    """The acceptance trace: mixed arrivals through a SLaB-packed
    (fused-kernel) model — engine tokens == per-request greedy_decode
    with the same packed params."""
    cfg, packed = packed_setup
    reqs = _trace(cfg, [(7, 5, 0.0), (13, 7, 3.0), (4, 9, 6.0)], seed=2)
    eng = Engine(cfg, packed,
                 EngineConfig(n_slots=2, n_blocks=24, block_size=4,
                              max_len=48, prefill_chunk=4))
    done = eng.run(reqs, clock="steps", max_steps=1000)
    _check_against_greedy(cfg, packed, done)


def test_engine_int8_kv_trace(dense_setup):
    """kv_quant engine run: parity vs greedy_decode under the SAME
    quantized cache config."""
    cfg, params = dense_setup
    cfg8 = cfg.with_(kv_quant="int8")
    reqs = _trace(cfg8, [(8, 5, 0.0), (14, 6, 1.0), (6, 7, 2.0)], seed=3)
    eng = Engine(cfg8, params,
                 EngineConfig(n_slots=3, n_blocks=32, block_size=4,
                              max_len=64, prefill_chunk=4))
    done = eng.run(reqs, clock="steps", max_steps=500)
    _check_against_greedy(cfg8, params, done)


def test_engine_rejects_cacheless_family():
    cfg = configs.get("mamba2_1_3b", smoke=True)
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        Engine(cfg, params, EngineConfig(n_slots=1, n_blocks=4,
                                         block_size=4, max_len=16))


def test_greedy_decode_ragged_lengths(dense_setup):
    """Right-padded batch + lengths array == per-row decode."""
    cfg, params = dense_setup
    rng = np.random.default_rng(4)
    lens = np.array([11, 5, 16, 8], np.int32)
    s, gen = int(lens.max()), 6
    prompts = np.zeros((len(lens), s), np.int32)
    rows = []
    for i, L in enumerate(lens):
        rows.append(rng.integers(0, cfg.vocab, size=int(L)
                                 ).astype(np.int32))
        prompts[i, :L] = rows[-1]
    got = np.asarray(greedy_decode(cfg, params, jnp.asarray(prompts),
                                   gen, lengths=lens))
    for i, p in enumerate(rows):
        want = np.asarray(greedy_decode(cfg, params,
                                        jnp.asarray(p)[None], gen))[0]
        assert np.array_equal(got[i], want), i
    # lengths == full width must agree with the dense two-scan path
    full = np.asarray(greedy_decode(cfg, params, jnp.asarray(prompts),
                                    gen))
    fullr = np.asarray(greedy_decode(
        cfg, params, jnp.asarray(prompts), gen,
        lengths=np.full(len(lens), s, np.int32)))
    assert np.array_equal(full, fullr)


def test_paged_decode_step_matches_dense_decode(dense_setup):
    """Model-level parity: paged_decode_step through a scattered block
    pool vs decode_step on a contiguous cache, 6 steps."""
    cfg, params = dense_setup
    b, n_blocks, bs = 3, 16, 4
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg.vocab, size=(b, 6)).astype(np.int32)
    bt = np.zeros((b, 4), np.int32)
    perm = rng.permutation(n_blocks)[:b * 2].reshape(b, 2)
    bt[:, :2] = perm                      # scattered physical blocks
    paged = init_paged_cache(cfg, n_blocks, bs)
    cache = lm.init_cache(cfg, b, 8)
    lengths = jnp.zeros((b,), jnp.int32)
    active = jnp.ones((b,), bool)
    from repro.models.common import positions_for
    for t in range(6):
        tok = jnp.asarray(toks[:, t:t + 1])
        lp, paged = lm.paged_decode_step(cfg, params, paged,
                                         jnp.asarray(bt), lengths, tok,
                                         active)
        ld, cache = lm.decode_step(cfg, params, cache, tok,
                                   positions_for(cfg, b, 1, offset=t))
        lengths = lengths + 1
    rel = (float(jnp.max(jnp.abs(lp[:, 0] - ld[:, -1])))
           / float(jnp.max(jnp.abs(ld))))
    assert rel < 1e-4, rel


def test_paged_decode_step_segmentation_is_bit_exact(dense_setup):
    """The stacked pool rides the layer scan's carry: one scan over all
    layers and one segment per layer give bit-identical logits and pool
    over several steps."""
    cfg, params = dense_setup
    b, n_blocks, bs = 3, 16, 4
    rng = np.random.default_rng(7)
    toks = rng.integers(0, cfg.vocab, size=(b, 6)).astype(np.int32)
    bt = jnp.asarray(rng.permutation(n_blocks)[:b * 2].reshape(b, 2),
                     jnp.int32)
    active = jnp.asarray([True, True, False])
    per_layer = tuple((i, i + 1) for i in range(cfg.n_layers))
    assert cfg.n_layers > 1
    pools = {seg: init_paged_cache(cfg, n_blocks, bs)
             for seg in (None, per_layer)}
    steps = {seg: jax.jit(functools.partial(lm.paged_decode_step, cfg,
                                            segments=seg))
             for seg in pools}
    for t in range(6):
        lengths = jnp.full((b,), t, jnp.int32)
        tok = jnp.asarray(toks[:, t:t + 1])
        logits = {}
        for seg in pools:
            logits[seg], pools[seg] = steps[seg](
                params, pools[seg], bt, lengths, tok, active)
        assert np.array_equal(np.asarray(logits[None]),
                              np.asarray(logits[per_layer]))
    for a, c in zip(pools[None], pools[per_layer]):
        assert np.array_equal(np.asarray(a), np.asarray(c))
    # the inactive row wrote nothing, the live rows wrote every layer
    k = np.asarray(pools[None].k)
    assert not np.any(k[:, np.asarray(bt[2])])
    assert np.all(np.any(k[:, np.asarray(bt[0, 0])], axis=(1, 2, 3)))
