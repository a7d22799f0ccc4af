"""The per-layer metrics that read the program's own record
(``repro.serving.obs``): on a recorder dump beside a hand-built trace
whose clock runs a known 5 ms ahead of the program's, and on a traced
run at a tiny size on the CPU."""
import json
import sys
import time
from collections import Counter
from pathlib import Path

import pytest

from chipbench import harness, spec, trace
from chipbench.harness import Plan, RunRecord
from chipbench.metrics import _program

FX = spec.PKG / "tests" / "fixtures"
SEED = 2 ** 35 + 17
NEW = ("sched.queue_wait_ms", "sched.prefill_ms", "engine.host_ms_per_step",
       "engine.idle_in_host_ms", "layer_scan.copy_share")


def _read(name, run):
    return spec.Layout().metric_reader(name)(run)


@pytest.fixture
def recorded(monkeypatch):
    """(recorder, run) of the fixture: the recorder is the newest
    engine's, the plans are those of the first three iterations."""
    from repro.serving import obs
    d = json.loads((FX / "obs_small.json").read_text())
    rec, dump = obs.Recorder(), d["recorder"]
    rec.spans.extend(tuple(s) for s in dump["spans"])
    rec.requests.extend(dump["requests"])
    rec.counters.update(dump["counters"])
    rec.scopes.update(dump["scopes"])
    rec.clock0_ns = dump["clock0_ns"]
    monkeypatch.setattr(obs, "_latest", rec)
    tr = trace.Trace.from_json(json.dumps(d["trace"]))
    its = [s for s in rec.spans if s[0] == "engine.iteration"][:3]
    plans = [Plan((s[1] - rec.clock0_ns) / 1e9, 8, 8, 1, 8) for s in its]
    run = RunRecord({}, {"lead_in_s": d["lead_in_s"]}, {}, 32, tr.window_s,
                    3, plans, tr)
    return rec, run


def test_queue_wait_and_prefill_of_the_window_requests(recorded):
    _, run = recorded
    # rid 0 arrived in the lead-in; rid 3 got no first token, rid 4 no
    # slot: waits 250, 0, 500 ms; prefills 1750, 500 ms
    assert _read("sched.queue_wait_ms", run) == pytest.approx(250.0)
    assert _read("sched.prefill_ms", run) == pytest.approx(1125.0)


def test_host_ms_per_step_is_the_iteration_less_its_wait(recorded):
    rec, run = recorded
    # the first traced step is left out (the benchmark starts its
    # profiler inside it): give it no wait, and the reading holds
    first = next(s for s in rec.spans if s[0] == "engine.device_wait")
    rec.spans[rec.spans.index(first)] = first[:2] + (first[1],) + first[3:]
    assert _read("engine.host_ms_per_step", run) == pytest.approx(0.25)


def test_idle_time_goes_to_the_program_span_the_host_was_in(recorded):
    rec, run = recorded
    offset, spread = _program.clock_offset(
        _program.traced_steps(rec, run), run.trace)
    assert (offset, spread) == (5_000_000, 2_000)
    idle, n_steps, _ = _program.idle_by_span(rec, run)
    assert n_steps == 3
    assert idle == pytest.approx({
        "sched.expire": 30e3, "sched.admit": 30e3, "sched.plan": 90e3,
        "engine.h2d": 150e3, "engine.dispatch": 60e3,
        "engine.device_wait": 60e3, "engine.commit": 240e3,
        "engine.iteration": 60e3})
    # 660 us of idle outside the wait over three steps
    assert _read("engine.idle_in_host_ms", run) == pytest.approx(0.22)


@pytest.mark.parametrize("jitter", [(-150e3, 0, 150e3), (0, 0)],
                         ids=("spread-over-100us", "unpaired"))
def test_no_idle_reading_when_the_clocks_do_not_align(recorded, jitter):
    _, run = recorded
    marks = sorted(s for s in run.trace.host_spans if s[0] == "step")
    run.trace.host_spans = [(n, s + j, d) for (n, s, d), j
                            in zip(marks, jitter)]
    assert _read("engine.idle_in_host_ms", run) is None


def test_layer_scan_copy_share_counts_the_scan_itself(recorded):
    _, run = recorded
    # per step: busy 760 us; the scan's copy 200, slice 100 and its
    # while's own 20 us, not the kernel (attn/wq) nor the unmapped copy
    assert _read("layer_scan.copy_share", run) == pytest.approx(
        100 * 320 / 760)


def test_a_program_without_the_record_gives_nothing(recorded, monkeypatch):
    import repro.serving
    _, run = recorded
    monkeypatch.delattr(repro.serving, "obs")
    monkeypatch.setitem(sys.modules, "repro.serving.obs", None)
    assert _program.recorder() is None
    assert all(_read(n, run) is None for n in NEW)


def _capturing_layout():
    lay = spec.Layout(root=FX, repo=Path("/"))
    seen = {}

    def reader(name):
        f = spec.Layout().metric_reader(name)

        def read(run):
            seen["run"] = run
            return f(run)
        return read
    lay.metric_reader = reader
    return lay, seen


def test_the_program_counts_what_the_benchmark_recorded(monkeypatch):
    """A traced run whose traced window holds every step: the program's
    own counters equal the sums over the wrapper's plans, and the
    program-span readers read."""
    from repro.serving import obs
    monkeypatch.setattr(harness, "TRACE_SECONDS", 1e6)
    bench = {
        "configs": [{"name": "tiny.dense",
                     "file": str(FX / "configs" / "tiny.dense.json")}],
        "workloads": [{"name": "tiny-whole", "config": "tiny.dense",
                       "traffic": "tiny-whole", "chips": 1, "why": "test"}],
        "end_to_end": [],
        "per_layer": [{"name": n, "unit": "x"} for n in NEW]}
    lay, seen = _capturing_layout()
    r = harness.run_cell(bench, "tiny-whole", SEED, 1.5, True,
                         time.monotonic(), layout=lay, log=lambda *a: None)
    assert r["correct"], r["checks"]
    rec, plans = obs.latest(), seen["run"].plans
    assert plans
    steps = {k: v for k, v in rec.counters.items()
             if k.startswith("sched.steps.")}
    assert steps == dict(Counter(f"sched.steps.c{p.c}" for p in plans))
    assert rec.counters["sched.valid_pairs"] == sum(p.tokens for p in plans)
    assert rec.counters["sched.ctx_tokens"] == sum(p.ctx for p in plans)
    assert rec.counters["engine.builds"] == 2
    for n in ("sched.queue_wait_ms", "sched.prefill_ms",
              "engine.host_ms_per_step"):
        assert r["metrics"][n]["value"] >= 0, n
    assert r["metrics"]["engine.host_ms_per_step"]["value"] > 0
