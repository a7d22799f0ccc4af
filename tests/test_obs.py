"""The serving engine's own record (``serving/obs.py``): request stamps
that split TTFT exactly, bounded spans and request log, the iteration's
children, the step programs built, and the scope map of a step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import lm
from repro.serving import Engine, EngineConfig, Request, Scheduler, obs

CHILDREN = {"sched.expire", "sched.admit", "sched.plan", "engine.h2d",
            "engine.dispatch", "engine.device_wait", "engine.commit"}


@pytest.fixture(scope="module")
def dense_setup():
    cfg = configs.get("llama2_7b", smoke=True).with_(dtype=jnp.float32)
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _trace(cfg, specs, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=p)
                    .astype(np.int32), max_new=n, arrival=a)
            for i, (p, n, a) in enumerate(specs)]


def _engine(cfg, params, **kw):
    ecfg = dict(n_slots=2, n_blocks=32, block_size=4, max_len=64,
                prefill_chunk=4)
    ecfg.update(kw)
    return Engine(cfg, params, EngineConfig(**ecfg))


def _iterations(rec):
    """{step: {name: span}} of the recorded iterations."""
    out = {}
    for sp in rec.spans:
        out.setdefault(sp[3], {})[sp[0]] = sp
    return out


def test_queue_wait_and_prefill_add_up_to_ttft(dense_setup):
    """More requests than slots, so some wait for a slot: for every
    finished request the two stamps split its TTFT exactly."""
    cfg, params = dense_setup
    reqs = _trace(cfg, [(9, 3, 0.0), (13, 4, 0.0), (6, 3, 1.0),
                        (11, 2, 3.0), (5, 3, 9.0)])
    eng = _engine(cfg, params)
    done = eng.run(reqs, clock="steps", max_steps=500)
    assert all(r.status == "finished" for r in done)
    waits = []
    for r in done:
        assert ((r.admitted - r.arrival) + (r.first_token - r.admitted)
                == r.ttft)
        waits.append(r.admitted - r.arrival)
    assert max(waits) > 0                 # someone queued for a slot
    log = {e["rid"]: e for e in eng.obs.requests}
    assert sorted(log) == [r.rid for r in done]
    for r in done:
        e = log[r.rid]
        assert (e["arrival"], e["admitted"], e["first_token"],
                e["finish"], e["status"]) == (
            r.arrival, r.admitted, r.first_token, r.finish, "finished")
    assert obs.latest() is eng.obs


def test_a_replayed_request_keeps_its_first_admission():
    s = Scheduler(n_slots=1, n_blocks=8, block_size=4, max_len=32)
    req = Request(rid=0, prompt=np.ones(6, np.int32), max_new=4,
                  arrival=0.5)
    s.submit(req)
    assert s.admit(2.0) == [0]
    s.evict(0)
    assert req.status == "queued" and req.n_evictions == 1
    assert s.admit(7.0) == [0]
    assert req.admitted == 2.0


def test_spans_and_request_log_stay_bounded(dense_setup):
    cfg, params = dense_setup
    reqs = _trace(cfg, [(5, 2, float(i)) for i in range(12)], seed=2)
    eng = _engine(cfg, params)
    eng.obs = eng.sched.obs = obs.Recorder(max_spans=20, max_requests=5)
    done = eng.run(reqs, clock="steps", max_steps=500)
    assert eng.n_steps * (len(CHILDREN) + 1) > 20
    assert len(eng.obs.spans) == 20
    assert len(eng.obs.requests) == 5
    last = sorted(r.finish for r in done)[-5]
    assert {e["rid"] for e in eng.obs.requests} <= {
        r.rid for r in done if r.finish >= last}


def test_iteration_children_nest_inside_it(dense_setup):
    cfg, params = dense_setup
    # a gap between arrivals leaves idle iterations, which record nothing
    reqs = _trace(cfg, [(7, 3, 0.0), (9, 2, 20.0)], seed=3)
    eng = _engine(cfg, params)
    eng.run(reqs, clock="steps", max_steps=500)
    its = _iterations(eng.obs)
    assert len(its) == sum(eng.obs.counters[k] for k in
                           ("sched.steps.c4", "sched.steps.c1"))
    for spans in its.values():
        it = spans.pop("engine.iteration")
        assert it[4] is None
        assert set(spans) == CHILDREN
        for name, t0, t1, _, parent in spans.values():
            assert parent == "engine.iteration"
            assert it[1] <= t0 <= t1 <= it[2], name
        order = sorted(spans.values(), key=lambda s: s[1])
        assert [s[0] for s in order][-4:] == [
            "engine.h2d", "engine.dispatch", "engine.device_wait",
            "engine.commit"]


@pytest.mark.parametrize("ahead", [True, False], ids=("compile", "lazy"))
def test_two_step_programs_per_lifetime(dense_setup, ahead):
    cfg, params = dense_setup
    eng = _engine(cfg, params)
    if ahead:
        eng.compile()
        assert eng.obs.counters["engine.builds"] == 2
    eng.run(_trace(cfg, [(9, 5, 0.0), (6, 4, 2.0)], seed=4),
            clock="steps", max_steps=500)
    assert eng.obs.counters["engine.builds"] == 2


def test_scope_map_of_a_tiny_dense_step(dense_setup):
    cfg, params = dense_setup
    eng = _engine(cfg, params)
    eng.compile()
    scopes = set(eng.obs.scopes.values())
    assert {"layer_scan", "attn/wq", "attn/kv_write",
            "attn/paged_attn", "head", "embed", "sample"} <= scopes


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/while/body/closed_call/layer_scan/while/body/closed_call/"
     "attn/wq/jit(slab_nm_matmul)/pallas_call", "attn/wq"),
    ("jit(step)/while/body/closed_call/layer_scan/while/body/"
     "dynamic_slice", "layer_scan"),
    ("jit(step)/while/body/closed_call/layer_scan/while/body/closed_call/"
     "attn/kv_write/squeeze;attn/reshape", "attn/kv_write"),
    ("jit(step)/while/body/dynamic_slice", None),
])
def test_scope_of_takes_the_innermost_run_of_program_scopes(op_name, want):
    names = {"layer_scan", "attn", "wq", "kv_write"}
    assert obs.scope_of(op_name, names) == want


def test_two_programs_that_disagree_map_an_op_to_nothing():
    rec = obs.Recorder()
    names = {"head", "layer_scan"}
    rec.add_scopes('  %copy.3 = f32[2] copy(%p), metadata={op_name='
                   '"jit(step)/head/copy"}\n  %add.1 = f32[2] add(%a, %b)'
                   ', metadata={op_name="jit(step)/layer_scan/add"}\n',
                   names)
    rec.add_scopes('  %copy.3 = f32[2] copy(%q)\n  ROOT %add.1 = f32[2] '
                   'add(%a, %b), metadata={op_name="jit(step)/layer_scan/'
                   'while/body/add"}\n', names)
    assert rec.scopes == {"copy.3": None, "add.1": "layer_scan"}


def test_a_copy_xla_added_takes_the_scope_of_what_it_copies():
    rec = obs.Recorder()
    rec.add_scopes(
        '  %while.4 = (s32[], f32[3,8]) while(%t), condition=%c, body=%b, '
        'metadata={op_name="jit(step)/layer_scan/while"}\n'
        '  %get-tuple-element.9 = f32[3,8]{1,0} get-tuple-element(%while.4)'
        ', index=1\n'
        '  %copy.5 = f32[3,8]{0,1} copy(%get-tuple-element.9)\n'
        '  %copy.6 = f32[3,8]{0,1} copy(%param.1)\n', {"layer_scan"})
    assert rec.scopes == {"while.4": "layer_scan",
                          "get-tuple-element.9": "layer_scan",
                          "copy.5": "layer_scan", "copy.6": None}
