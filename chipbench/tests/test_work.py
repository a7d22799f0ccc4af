"""The metric arithmetic on hand-checked shapes."""
import numpy as np
import pytest

from chipbench import work

V5E = work.peaks("TPU v5 lite")


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
def test_percentile_over_all_samples_matches_numpy(q):
    xs = list(np.random.default_rng(0).lognormal(0, 1, 101))
    assert work.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_hand_checked():
    assert work.percentile([1, 2, 3, 4], 50) == 2.5
    assert work.percentile([10], 90) == 10
    with pytest.raises(ValueError):
        work.percentile([], 50)


def test_peaks_keyed_by_device_kind():
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")


def test_least_time_names_its_bound():
    t, b = work.least_time(197e12, 1.0, V5E)
    assert (t, b) == (pytest.approx(1.0), "compute")
    t, b = work.least_time(1.0, 819e9, V5E)
    assert (t, b) == (pytest.approx(1.0), "memory")


def test_slab_nm_call_bytes_hand_checked():
    # 2:4 at 5120 -> 13824, rank 1, 128 rows, bf16
    d_in, d_out, r = 5120, 13824, 128
    flops, nbytes = work.slab_nm_call(d_in, d_out, r, 2, 4, 1)
    w = d_in * d_out
    assert flops == 2 * r * w
    assert nbytes == w / 2 * 3 + w / 8 + 2 * (d_in + d_out) \
        + r * (d_in + d_out) * 2
    # 1.625 bytes per weight plus vectors; about 157 flop/B at 128 rows
    assert 150 < flops / nbytes < 160
    assert work.least_time(flops, nbytes, V5E)[1] == "memory"


STABLELM = {"hidden_size": 5120, "intermediate_size": 13824,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "num_hidden_layers": 10, "vocab_size": 100352}


def test_linear_shapes_and_params():
    sh = work.linear_shapes(STABLELM)
    assert sh["attn.wq"] == (5120, 5120) and sh["attn.wk"] == (5120, 1280)
    assert sh["mlp.w_down"] == (13824, 5120)
    assert sum(a * b for a, b in sh.values()) == 277_872_640


def test_paged_attention_work_hand_checked():
    flops, nbytes = work.paged_attn_work(1000, 4, STABLELM)
    assert nbytes == 1000 * 2 * 8 * 160 * 2 + 4 * 2 * 32 * 160 * 2
    assert flops == 4 * 1000 * 32 * 160


def test_model_flops_hand_checked():
    f = work.model_flops(10, 0, STABLELM)
    assert f == 10 * 2 * (277_872_640 * 10 + 5120 * 100352)
    g = work.model_flops(0, 100, STABLELM)
    assert g == 100 * 4 * 32 * 160 * 10
