"""BENCHMARK.json and the files its names lead to."""
import copy
import json
from pathlib import Path

import pytest

from chipbench import harness, spec


@pytest.fixture(scope="module")
def bench():
    return spec.Layout().bench()


def test_benchmark_json_is_sound(bench):
    assert spec.validate(bench) == []


def test_every_name_leads_to_its_files(bench):
    lay = spec.Layout()
    for w in bench["workloads"]:
        cfg = lay.config(bench, w["config"])
        assert cfg["name"] == w["config"]
        assert lay.mix(w["traffic"])["kind"] == "poisson"
        lim = lay.limits(w["name"])
        assert 0 < lim["widest_logit_gap"]
    for m in bench["per_layer"]:
        assert callable(lay.metric_reader(m["name"]))


def test_reduced_lists_every_changed_key(bench):
    lay = spec.Layout()
    for c in bench["configs"]:
        cfg = lay.config(bench, c["name"])
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert set(cfg["published"]) == set(c["reduced"])


@pytest.mark.parametrize("bad", [
    "has space", "slash/name", "comma,name", ".dot-first", "x" * 65,
    "greekµs", ""])
def test_bad_names_refused(bench, bad):
    b = copy.deepcopy(bench)
    b["per_layer"][0]["name"] = bad
    assert spec.validate(b)


@pytest.mark.parametrize("good", ["queue_p90_ms", "engine.wait_ms",
                                  "9lives", "_x-y.z"])
def test_good_names_pass(bench, good):
    b = copy.deepcopy(bench)
    b["per_layer"][0]["name"] = good
    assert spec.validate(b) == []


@pytest.mark.parametrize("unit,ok", [
    ("tokens/s", True), ("%", True), ("ms", True), ("us", True),
    ("tokens per s", False), ("µs", False), ("x" * 17, False),
    ("", False)])
def test_units(bench, unit, ok):
    b = copy.deepcopy(bench)
    b["end_to_end"][0]["unit"] = unit
    assert (spec.validate(b) == []) == ok


@pytest.mark.parametrize("key", ["hidden_size", "intermediate_size",
                                 "head_dim", "kv_lora_rank",
                                 "num_experts_per_tok"])
def test_reduced_never_names_a_width(bench, key):
    b = copy.deepcopy(bench)
    b["configs"][0]["reduced"] = [key]
    assert any("width" in e for e in spec.validate(b))


def test_bound_and_run_seconds_limits(bench):
    b = copy.deepcopy(bench)
    b["end_to_end"][0]["bound"] = 0.3
    b["run_seconds"] = 52
    errs = spec.validate(b)
    assert any("bound" in e for e in errs)
    assert any("run_seconds" in e for e in errs)


def _write(p: Path, text: str):
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)


def test_a_dummy_mix_and_metric_are_found_by_files_alone(tmp_path):
    root = tmp_path / "bench"
    _write(root / "mixes" / "dummy.json",
           json.dumps({"kind": "poisson", "rate_per_s": 2.0}))
    _write(root / "metrics" / "dummy.steps_per_s.py",
           "def read(run):\n    return run.window_steps / run.window_s\n")
    lay = spec.Layout(root=root, repo=tmp_path)
    assert lay.mix("dummy")["rate_per_s"] == 2.0
    run = harness.RunRecord({}, {}, {}, 4, 2.0, 10, [])
    assert lay.metric_reader("dummy.steps_per_s")(run) == 5.0
    b = {"workloads": [{"name": "c"}],
         "per_layer": [{"name": "dummy.steps_per_s"}, {"name": "other",
                                                        "workloads": []}]}
    assert [m["name"] for m in spec.metrics_for(b, "c", "per_layer")] == [
        "dummy.steps_per_s"]
