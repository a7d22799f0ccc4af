"""The yardstick's arithmetic: percentiles, peaks, and the operations and
bytes each kernel and each step needs, counted from shapes.

Nothing here reads a count the program makes: rows, positions and
context lengths come from the benchmark's own record of each plan.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict, Sequence, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def percentile(xs: Sequence[float], q: float) -> float:
    """The q-th percentile of all of ``xs`` with linear interpolation
    between order statistics (numpy's default method)."""
    if not xs:
        raise ValueError("percentile of no samples")
    s = sorted(xs)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, pk: Dict[str, float]
               ) -> Tuple[float, str]:
    """(seconds, bound): the least time the chip could take, and whether
    compute or memory bandwidth sets it."""
    t_c = flops / pk["bf16_flops_per_s"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def linear_shapes(cfg: dict) -> Dict[str, Tuple[int, int]]:
    """(d_in, d_out) of every linear of one decoder layer."""
    d = cfg["hidden_size"]
    dh = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * dh
    kv = cfg["num_key_value_heads"] * dh
    f = cfg["intermediate_size"]
    return {"attn.wq": (d, q), "attn.wk": (d, kv), "attn.wv": (d, kv),
            "attn.wo": (q, d), "mlp.w_gate": (d, f), "mlp.w_up": (d, f),
            "mlp.w_down": (f, d)}


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def slab_nm_call(d_in: int, d_out: int, rows: int, n: int, m: int,
                 rank: int, itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of one packed SLaB N:M linear over ``rows`` rows:
    the kept values (n/m of the weights) at ``itemsize`` bytes, one int8
    index per kept value, one sign bit per weight, the rank-r factors,
    and the activations in and out. Flops are those of the dense product
    the kernel computes, 2 * rows * d_in * d_out."""
    kept = d_in * d_out * n // m
    nbytes = (kept * (itemsize + 1) + d_in * d_out / 8
              + rank * (d_in + d_out) * itemsize
              + rows * (d_in + d_out) * itemsize)
    return 2.0 * rows * d_in * d_out, nbytes


def paged_attn_work(ctx_tokens: float, rows: float, cfg: dict,
                    itemsize: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of paged decode attention in one layer: each of
    ``ctx_tokens`` valid cached tokens (summed over the rows and
    positions of a step) is read once as K and once as V for every KV
    head; every active (row, position), ``rows`` of them, reads its
    queries and writes its output. Flops: q.k and p.v, 4 * ctx * H * dh."""
    dh = head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    nbytes = (ctx_tokens * 2 * kv * dh * itemsize
              + rows * 2 * h * dh * itemsize)
    return 4.0 * ctx_tokens * h * dh, nbytes


def model_flops(tokens: float, ctx_tokens: float, cfg: dict) -> float:
    """Operations the model needs: 2 per weight of every linear and of
    the output head for each processed token, plus attention's
    4 * ctx * H * dh in every layer. The same count whatever format
    holds the weights."""
    n_lin = sum(a * b for a, b in linear_shapes(cfg).values())
    n_layers = cfg["num_hidden_layers"]
    per_tok = 2.0 * (n_lin * n_layers
                     + cfg["hidden_size"] * cfg["vocab_size"])
    attn = 4.0 * ctx_tokens * cfg["num_attention_heads"] * head_dim(cfg)
    return per_tok * tokens + attn * n_layers
