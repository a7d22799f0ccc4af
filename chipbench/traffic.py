"""One traffic generator for every mix: a mix file holds parameters only.

A mix is open loop (``kind`` ``poisson``): requests are due at a fixed
mean rate over a lead-in (served, not counted) and then over the
measured window. Every request carries the same absolute deadline, the
window's end plus ``drain_s``: the run's horizon, at which the engine
cuts whatever is still queued or running.

Every seed gets the same work: the multiset of prompt lengths, output
lengths and inter-arrival gaps is fixed by stratified quantiles of the
mix's distributions, and the seed only orders them and draws the token
ids. Runs with different seeds then differ by order, not by how much
they ask of the system, and their spread measures the system.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

KINDS = ("poisson",)


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request as plain data (the program's ``Request`` is built
    from it by the harness)."""
    rid: int
    prompt: np.ndarray          # (P,) int32 token ids
    max_new: int
    arrival: float              # seconds after the engine's start
    deadline: float             # absolute, same clock
    counted: bool               # due in the measured window


def lognormal_lengths(median: float, sigma: float, lo: int, hi: int,
                      n: int) -> List[int]:
    """``n`` lengths at the stratified quantiles (i + 0.5) / n of a
    lognormal with this median and sigma, rounded and clipped to
    [lo, hi]."""
    nd = NormalDist()
    return [min(hi, max(lo, int(round(
        median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))))))
        for i in range(n)]


def exponential_gaps(rate: float, n: int) -> List[float]:
    """``n`` inter-arrival gaps at the stratified quantiles of an
    exponential with mean 1 / rate."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def _lengths(mix: dict, n: int, rng: np.random.Generator):
    p, o = mix["prompt"], mix["output"]
    prompts = lognormal_lengths(p["median"], p["sigma"], p["min"],
                                p["max"], n)
    outs = lognormal_lengths(o["median"], o["sigma"], o["min"], o["max"],
                             n)
    return rng.permutation(prompts), rng.permutation(outs)


def _arrivals(rate: float, n: int, start: float, end: float,
              rng: np.random.Generator) -> np.ndarray:
    """``n`` arrivals in [start, end): shuffled exponential gaps,
    scaled so that the segment holds them all."""
    if n == 0:
        return np.zeros((0,))
    gaps = rng.permutation(exponential_gaps(rate, n))
    scale = (end - start) / (gaps.sum() + gaps.mean())
    return start + np.cumsum(gaps) * scale


def validate(mix: dict) -> None:
    if mix.get("kind") not in KINDS:
        raise ValueError(f"mix kind {mix.get('kind')!r} not in {KINDS}")
    eng = mix["engine"]
    worst = mix["prompt"]["max"] + mix["output"]["max"] - 1
    if worst > eng["max_len"]:
        raise ValueError(f"longest request caches {worst} tokens, "
                         f"max_len is {eng['max_len']}")
    need = math.ceil(worst / eng["block_size"])
    if need > eng["n_blocks"]:
        raise ValueError(f"{eng['n_blocks']} KV blocks cannot hold the "
                         f"longest request ({need} blocks)")


def generate(mix: dict, seed: int, seconds: float, vocab: int
             ) -> List[Spec]:
    """The requests of one run of ``mix`` from ``seed``, sorted by
    arrival. The measured window is [lead_in_s, lead_in_s + seconds)."""
    validate(mix)
    rng = np.random.default_rng(seed)
    lead = float(mix["lead_in_s"])
    end = lead + seconds
    out: List[Spec] = []
    rate = float(mix["rate_per_s"])
    deadline = end + float(mix["drain_s"])
    rid = 0
    for start, stop, counted in ((0.0, lead, False), (lead, end, True)):
        n = int(round(rate * (stop - start)))
        p_len, o_len = _lengths(mix, n, rng) if n else ([], [])
        for i, t in enumerate(_arrivals(rate, n, start, stop, rng)):
            out.append(Spec(rid, rng.integers(0, vocab, int(p_len[i]),
                                              dtype=np.int32),
                            int(o_len[i]), float(t), deadline, counted))
            rid += 1
    return out
