"""A whole run of the harness at a tiny size on the CPU, past the look
for a chip: the program proves correct against the plain reference, the
control (the reference in the program's place at fp8) does not, and a
token altered where the engine produces it makes ``correct`` false."""
from pathlib import Path

import pytest

from chipbench import harness, spec

FX = spec.PKG / "tests" / "fixtures"
SEED = 2 ** 35 + 17


def _bench(config: str) -> dict:
    return {
        "configs": [{"name": c, "file": str(FX / "configs" / f"{c}.json")}
                    for c in ("tiny.dense", "tiny.slab24")],
        "workloads": [{"name": "tiny", "config": config, "traffic": "tiny",
                       "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "ttft_p50_ms", "unit": "ms"},
                       {"name": "itl_p50_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}


def _run(config: str, control=None):
    import time
    lay = spec.Layout(root=FX, repo=Path("/"))
    return harness.run_cell(_bench(config), "tiny", SEED, 1.5, False,
                            time.monotonic(), layout=lay, control=control,
                            log=lambda *a: None)


@pytest.mark.parametrize("config", ["tiny.dense", "tiny.slab24"])
def test_program_is_correct_and_the_control_is_not(config):
    r = _run(config, control="fp8")
    # the control stands in the program's place: the run it decides is
    # not correct, while the program's own reading is within the limit
    assert not r["correct"], r["checks"]
    limit = r["checks"]["widest_logit_gap"]["limit"]
    assert r["checks"]["widest_logit_gap"]["value"] > limit
    assert r["control"]["program_widest_logit_gap"] <= limit
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"ttft_p50_ms", "itl_p50_ms", "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("config", ["tiny.dense", "tiny.slab24"])
def test_program_alone_is_correct(config):
    r = _run(config)
    assert r["correct"], r["checks"]
    assert "control" not in r


def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch):
    from repro.serving.engine import Engine
    orig = Engine._run_step

    def altered(self, tokens, n_valid, force_nan):
        last, ok = orig(self, tokens, n_valid, force_nan)
        return (last + 1) % self.cfg.vocab, ok

    monkeypatch.setattr(Engine, "_run_step", altered)
    r = _run("tiny.dense")
    assert not r["correct"]
    assert r["checks"]["widest_logit_gap"]["value"] > \
        r["checks"]["widest_logit_gap"]["limit"]


def test_a_traced_run_reports_per_layer_metrics():
    import time
    bench = _bench("tiny.dense")
    bench["per_layer"] = [{"name": "engine.ms_per_step", "unit": "ms"},
                          {"name": "sched.prefill_step_share", "unit": "%"},
                          {"name": "step_mfu", "unit": "%"}]
    lay = spec.Layout(root=FX, repo=Path("/"))
    lay.metric_reader = spec.Layout().metric_reader
    r = harness.run_cell(bench, "tiny", SEED, 1.5, True, time.monotonic(),
                         layout=lay, log=lambda *a: None)
    assert r["correct"], r["checks"]
    # no peaks for the CPU: the utilization reader returns nothing
    assert set(r["metrics"]) == {"engine.ms_per_step",
                                 "sched.prefill_step_share"}
    assert r["metrics"]["engine.ms_per_step"]["value"] > 0
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def _req(rid, status, out=(), ttft=None, times=(), finish=None):
    from types import SimpleNamespace
    return SimpleNamespace(rid=rid, status=status, out=list(out), ttft=ttft,
                           token_times=list(times), finish=finish)


def _spec(arrival, counted, deadline=100.0):
    from types import SimpleNamespace
    return SimpleNamespace(arrival=arrival, counted=counted,
                           deadline=deadline)


def test_an_unserved_request_counts_its_time_to_the_horizon():
    reqs = [_req(0, "finished", [1, 2], 2.0, [12.0, 12.5]),
            _req(1, "timeout", finish=100.0),
            _req(2, "finished", [1], 9.0, [99.0])]
    specs = [_spec(10.0, True), _spec(40.0, True), _spec(5.0, False)]
    ttft, gaps = harness.ttft_and_gaps(reqs, specs, 10.0, 60.0)
    assert ttft == [2000.0, 60000.0]
    assert gaps == [500.0]


def test_a_request_cut_at_the_horizon_is_not_a_failure():
    reqs = [_req(0, "timeout", [1, 2, 3], 1.0), _req(1, "failed"),
            _req(2, "shed"), _req(3, "rejected")]
    specs = [_spec(0, True), _spec(0, True), _spec(0, False),
             _spec(0, True)]
    assert harness.attempted_failed(reqs, specs) == (3, 2)


def test_the_sample_holds_the_longest_and_requests_cut_at_the_horizon():
    reqs = [_req(0, "finished", [1] * 5), _req(1, "timeout", [1] * 50),
            _req(2, "failed", [1] * 80), _req(3, "timeout"),
            _req(4, "finished", [1] * 20)]
    pick = harness.sample_for_check(reqs, seed=3)
    assert pick[0].rid == 1
    assert {r.rid for r in pick} == {0, 1, 4}
