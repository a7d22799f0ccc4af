"""The traffic generator: deterministic for a seed, the same work for
every seed, lengths within their clips, the open-loop shape."""
import copy
import json
from collections import Counter

import numpy as np
import pytest

from chipbench import spec, traffic

BIG_SEED = 2 ** 31 + 12345


MIXES = sorted(p.stem for p in (spec.PKG / "mixes").glob("*.json"))


def _mix(name):
    return json.loads((spec.PKG / "mixes" / f"{name}.json").read_text())


def test_same_seed_same_requests():
    a = traffic.generate(_mix(MIXES[0]), BIG_SEED, 20.0, 1000)
    b = traffic.generate(_mix(MIXES[0]), BIG_SEED, 20.0, 1000)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert (x.arrival, x.max_new, x.counted) == (y.arrival, y.max_new,
                                                       y.counted)
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    a = traffic.generate(_mix(name), 1, 20.0, 1000)
    b = traffic.generate(_mix(name), 2, 20.0, 1000)
    assert Counter(len(s.prompt) for s in a) == Counter(
        len(s.prompt) for s in b)
    assert Counter(s.max_new for s in a) == Counter(s.max_new for s in b)
    assert [len(s.prompt) for s in a] != [len(s.prompt) for s in b]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_within_clips_and_vocab(name):
    mix = _mix(name)
    reqs = traffic.generate(mix, 3, 300.0, 777)
    p, o = mix["prompt"], mix["output"]
    assert all(p["min"] <= len(s.prompt) <= p["max"] for s in reqs)
    assert all(o["min"] <= s.max_new <= o["max"] for s in reqs)
    assert all(s.prompt.min() >= 0 and s.prompt.max() < 777 for s in reqs)
    med = np.median([len(s.prompt) for s in reqs])
    assert abs(med - p["median"]) <= 0.1 * p["median"]


def test_poisson_shape():
    mix = _mix(MIXES[0])
    mix = dict(mix, rate_per_s=4.0, lead_in_s=5)
    reqs = traffic.generate(mix, 4, 50.0, 100)
    lead = [s for s in reqs if not s.counted]
    win = [s for s in reqs if s.counted]
    assert len(lead) == 20 and len(win) == 200
    assert all(0 <= s.arrival < 5 for s in lead)
    assert all(5 <= s.arrival < 55 for s in win)
    arr = [s.arrival for s in reqs]
    assert arr == sorted(arr)
    gaps = np.diff([s.arrival for s in win])
    assert 0.5 < np.std(gaps) / np.mean(gaps) < 1.5   # exponential: CV 1
    assert all(s.deadline == 55 + mix["drain_s"] for s in reqs)


def test_lognormal_quantiles_hand_checked():
    # median of 3 stratified quantiles of any lognormal is its median
    assert traffic.lognormal_lengths(96, 0.8, 16, 256, 3)[1] == 96
    assert traffic.lognormal_lengths(96, 0.8, 16, 256, 1) == [96]
    assert traffic.lognormal_lengths(50, 3.0, 16, 256, 2) == [16, 256]


def test_mix_that_cannot_fit_is_refused():
    mix = copy.deepcopy(_mix(MIXES[0]))
    mix["engine"]["n_blocks"] = 10
    with pytest.raises(ValueError, match="KV blocks"):
        traffic.generate(mix, 1, 1.0, 100)
    mix = copy.deepcopy(_mix(MIXES[0]))
    mix["kind"] = "closed"
    with pytest.raises(ValueError, match="kind"):
        traffic.generate(mix, 1, 1.0, 100)


def test_every_request_shares_the_horizon():
    mix = _mix(MIXES[0])
    reqs = traffic.generate(mix, 8, 40.0, 100)
    assert {s.deadline for s in reqs} == {mix["lead_in_s"] + 40.0
                                          + mix["drain_s"]}


@pytest.mark.parametrize("name", MIXES)
def test_mix_names_its_public_source(name):
    mix = _mix(name)
    assert mix["source"].startswith("https://")
    assert mix["engine"]["max_len"] >= (mix["prompt"]["max"]
                                        + mix["output"]["max"] - 1)
