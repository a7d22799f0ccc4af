"""The trace reduction on a small hand-built trace."""
import json

import pytest

from chipbench import spec, trace
from chipbench.harness import Plan, RunRecord

FIXTURES = spec.PKG / "tests" / "fixtures"


@pytest.fixture
def tr():
    d = json.loads((FIXTURES / "trace_small.json").read_text())
    d.pop("about")
    return trace.Trace.from_json(json.dumps(d))


def test_busy_is_the_union_clipped_to_the_window(tr):
    assert trace.busy_ns(tr) == 650
    assert tr.window_s == 1e-6


def test_idle_gaps(tr):
    assert trace.idle_gaps(tr) == [(0, 100), (600, 700), (800, 950)]


def test_self_times_subtract_nested_ops(tr):
    st = trace.self_times(tr.device_ops)
    assert st == {"while": 100, "fusion": 250, "slab_nm_matmul": 200,
                  "closed_call": 100, "copy": 100}


def test_kernel_patterns_of_the_metric_files(tr):
    lay = spec.Layout()
    slab = lay.metric_reader("slab_nm.time_share")
    paged = lay.metric_reader("paged_attn.time_share")
    run = RunRecord({}, {}, {}, 32, 1.0, 1, [], tr)
    assert slab(run) == pytest.approx(100 * 200 / 650)
    assert paged(run) == pytest.approx(100 * 100 / 650)
    idle = lay.metric_reader("device.idle_share")
    assert idle(run) == pytest.approx(35.0)


def test_breakdown_attributes_idle_time_to_host_spans(tr):
    b = trace.breakdown(tr)
    assert b["idle_gaps"] == [["step", 200e-9], ["commit", 150e-9]]
    assert b["device_ops"][0] == ["fusion", 250e-9]
    assert len(b["device_ops"]) == 5


def test_roofline_readers_on_a_hand_checked_step(tr):
    cfg = {"hidden_size": 5120, "intermediate_size": 13824,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "num_hidden_layers": 1, "vocab_size": 100352,
           "format": {"kind": "slab_nm", "pattern": "2:4", "rank": 1}}
    from chipbench import work
    pk = work.peaks("TPU v5 lite")
    plans = [Plan(0.0, 1, 32, 32, 32 * 100)]
    run = RunRecord(cfg, {}, pk, 32, 1.0, 1, plans, tr)
    lay = spec.Layout()
    least = sum(work.least_time(*work.slab_nm_call(a, b, 32, 2, 4, 1), pk)[0]
                for a, b in work.linear_shapes(cfg).values())
    assert lay.metric_reader("slab_nm_roofline")(run) == pytest.approx(
        100 * least / 200e-9)
    f, nb = work.paged_attn_work(3200, 32, cfg)
    assert lay.metric_reader("paged_attn_roofline")(run) == pytest.approx(
        100 * work.least_time(f, nb, pk)[0] / 100e-9)
    mfu = lay.metric_reader("step_mfu")(run)
    assert mfu == pytest.approx(100 * work.model_flops(32, 3200, cfg)
                                / 197e12)


def test_readers_return_nothing_without_a_trace():
    run = RunRecord({"format": {"kind": "dense"}}, {}, {}, 4, 1.0, 0, [])
    lay = spec.Layout()
    for name in ("device.idle_share", "slab_nm.time_share",
                 "paged_attn.time_share", "slab_nm_roofline",
                 "paged_attn_roofline", "step_mfu", "engine.ms_per_step",
                 "sched.prefill_step_share"):
        assert lay.metric_reader(name)(run) is None
