"""Packed vs dense serving: tokens/s, trace cost, bytes-per-linear.

The perf trajectory for the heterogeneous packed-serving path. Three
measurements:

  1. **tokens/s** on the PR-4 smoke config (stablelm-12b-smoke, mixed
     sparsegpt/hassle/slab plan, extended with one wanda rule so a
     sparse-ell row exists at 50% unstructured sparsity): decode
     throughput for the dense-equivalent weights, the packed model on
     the segmented-scan path (default), and the same packed model
     forced through per-layer segments (the old unrolled behavior).
  2. **trace/lower wall-clock** at depth (n_layers=DEPTH, synthetic
     pruned decs, 3 signature segments): `jax.jit(...).lower()` time of
     the decode step, segmented vs unrolled — the O(#segments) vs O(L)
     compile story.
  3. **bytes-per-linear** per packed variant vs its dense footprint
     (from PackReport.bytes_by_variant). With ELL routing every variant
     of this plan beats dense bytes — the old silent >1.0x on
     slab-dense/lowrank-dense is gone.

Tensor-parallel packed serving on four chips is checked by
``chip_smoke.py --four-chips``.

CPU caveat: the Pallas kernels run in interpret mode here, so absolute
packed tokens/s is NOT meaningful off-TPU — the bytes and trace-cost
numbers are the hardware-independent signal, and the tokens/s columns
become meaningful on a real TPU. Emits
experiments/benchmarks/BENCH_packed_serve.json.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.core.packed_model import pack_plan_decs
from repro.core.pipeline import compress_model
from repro.core.plan import CompressionPlan
from repro.core.slab import SLaBConfig
from repro.data import calibration_batch
from repro.models import lm
from repro.models.common import positions_for

from benchmarks.common import (emit, per_layer_segments,
                               synthetic_pruned_packed)

ARCH = "stablelm_12b"
PLAN = ("attn.wo=wanda; attn.*=sparsegpt@pattern=2:4; "
        "mlp.w_gate=hassle@rank=4; *=slab")
BATCH, STEPS = 4, 8
DEPTH = 24                    # layer count for the trace-cost story

MOE_ARCH = "phi3_5_moe"       # the expert-packed row
MOE_PLAN = "*=slab"
MOE_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def _decode_stepper(cfg, params, segments=None, batch=BATCH, steps=STEPS):
    """Compiled decode closure + a timed-pass runner returning tok/s."""
    dec = jax.jit(lambda c, t, p: lm.decode_step(cfg, params, c, t, p,
                                                 segments=segments))
    tok = jnp.zeros((batch, 1), jnp.int32)

    def one_pass() -> float:
        cache = lm.init_cache(cfg, batch, steps + 1)
        logits, cache = dec(cache, tok, positions_for(cfg, batch, 1))
        jax.block_until_ready(logits)                  # compile outside
        t0 = time.monotonic()
        for t in range(1, steps + 1):
            logits, cache = dec(cache, tok,
                                positions_for(cfg, batch, 1, offset=t))
        jax.block_until_ready(logits)
        return batch * steps / (time.monotonic() - t0)

    return one_pass


def _decode_toks_per_s(steppers, reps: int = 3):
    """Measure several configurations with ALTERNATING timed passes and
    take each one's best rate — this box speeds up over a process's
    lifetime, so back-to-back single passes systematically favor
    whichever configuration runs last."""
    rates = {name: 0.0 for name in steppers}
    for _ in range(reps):
        for name, one_pass in steppers.items():
            rates[name] = max(rates[name], one_pass())
    return rates


def _synthetic_packed(cfg):
    """3-segment signature layout: keep .25 below L/3, keep .5 above,
    layer-0 attn.wq left dense."""
    _, packed, rep = synthetic_pruned_packed(
        cfg, lambda l: 0.25 if l < cfg.n_layers // 3 else 0.5,
        skip={(0, "attn.wq")})
    return packed, rep


def _moe_row():
    """Expert-packed MoE vs dense: decode tok/s plus the bytes of the
    three 3-D expert leaves served by the expert-packed kernels (the
    dense islands the expert-axis PackedStack finally packed)."""
    cfg = configs.get(MOE_ARCH, smoke=True).with_(dtype=jnp.float32)
    params, _ = lm.init(cfg, jax.random.PRNGKey(1))
    cal = calibration_batch(cfg.vocab, n_seq=4, seq_len=32)
    plan = CompressionPlan.parse(MOE_PLAN,
                                 base=SLaBConfig(cr=0.5, iters=4))
    dense_c, _, decs = compress_model(cfg, params, cal, plan=plan,
                                      keep_decompositions=True)
    packed, rep = pack_plan_decs(dense_c, decs, cfg.n_layers, plan)
    rates = _decode_toks_per_s({
        "dense": _decode_stepper(cfg, dense_c),
        "expert_packed": _decode_stepper(cfg, packed),
    })
    pb = sum(sum(a.nbytes
                 for a in jax.tree.leaves(packed["layers"]["moe"][k]))
             for k in MOE_EXPERT_KEYS)
    db = sum(dense_c["layers"]["moe"][k].nbytes for k in MOE_EXPERT_KEYS)
    return {
        "arch": cfg.name,
        "plan": MOE_PLAN,
        "n_packed": rep.n_packed,
        "dense_fallback": len(rep.fallback),
        "by_variant": rep.by_variant,
        "tokens_per_s": rates,
        "expert_bytes_packed": pb,
        "expert_bytes_dense": db,
        "expert_bytes_ratio": pb / db,
    }


def _lower_seconds(cfg, params, segments=None) -> float:
    cache = lm.init_cache(cfg, BATCH, 2)
    tok = jnp.zeros((BATCH, 1), jnp.int32)
    pos = positions_for(cfg, BATCH, 1)
    jax.clear_caches()     # drop warm inner-jit kernel traces: both
    t0 = time.monotonic()  # segmentations start cold, or O(L) hides
    jax.jit(lambda c, t, p: lm.decode_step(cfg, params, c, t, p,
                                           segments=segments)
            ).lower(cache, tok, pos)
    return time.monotonic() - t0


def run():
    cfg = configs.get(ARCH, smoke=True).with_(dtype=jnp.float32)
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    cal = calibration_batch(cfg.vocab, n_seq=4, seq_len=32)
    plan = CompressionPlan.parse(PLAN, base=SLaBConfig(cr=0.5, iters=4))
    dense_c, stats, decs = compress_model(cfg, params, cal, plan=plan,
                                          keep_decompositions=True)
    packed, rep = pack_plan_decs(dense_c, decs, cfg.n_layers, plan)

    rates = _decode_toks_per_s({
        "dense": _decode_stepper(cfg, dense_c),
        "packed": _decode_stepper(cfg, packed),
        "packed_unrolled": _decode_stepper(
            cfg, packed, segments=per_layer_segments(cfg.n_layers)),
    })

    variants = {}
    for var, (per_packed, per_dense) in rep.bytes_by_variant.items():
        variants[var] = {
            "n_linears": rep.by_variant[var],
            "bytes_per_linear_packed": per_packed,
            "bytes_per_linear_dense": per_dense,
            "bytes_ratio": per_packed / per_dense,
        }

    # trace/lower cost at depth: O(#segments) segmented vs O(L) unrolled
    cfg_deep = cfg.with_(n_layers=DEPTH)
    packed_deep, rep_deep = _synthetic_packed(cfg_deep)
    lower_seg = _lower_seconds(cfg_deep, packed_deep)
    lower_unr = _lower_seconds(cfg_deep, packed_deep,
                               segments=per_layer_segments(DEPTH))

    moe = _moe_row()

    rows = {
        "arch": cfg.name,
        "plan": PLAN,
        "backend": jax.default_backend(),
        "interpret_mode": jax.default_backend() == "cpu",
        "n_packed": rep.n_packed,
        "dense_fallback": len(rep.fallback),
        "by_variant": rep.by_variant,
        "n_segments": len(rep.segments),
        "tokens_per_s": rates,
        "trace_lower_s": {"n_layers": DEPTH,
                          "n_segments": len(rep_deep.segments),
                          "segmented": lower_seg,
                          "unrolled": lower_unr},
        "variants": variants,
        "moe": moe,
    }
    emit("BENCH_packed_serve", rows)
    return rows


def check(rows) -> bool:
    """Every linear packs; every byte-reducing variant (N:M, ELL,
    binlr, lowrank) actually beats its dense bytes; the segmented path
    traces faster than the per-layer unrolled equivalent at depth."""
    ok = rows["dense_fallback"] == 0 and rows["n_packed"] > 0
    ok = ok and "sparse-ell" in rows["variants"]
    for var, agg in rows["variants"].items():
        if (var.endswith("-nm") or var.endswith("-ell")
                or var in ("binlr", "lowrank")):
            ok = ok and agg["bytes_ratio"] < 1.0
    tl = rows["trace_lower_s"]
    ok = ok and tl["segmented"] < tl["unrolled"]
    moe = rows["moe"]
    ok = ok and moe["dense_fallback"] == 0
    ok = ok and moe["expert_bytes_ratio"] < 1.0
    return ok


if __name__ == "__main__":
    rows = run()
    print({k: v for k, v in rows.items() if k not in ("variants", "moe")})
    for var, agg in sorted(rows["variants"].items()):
        print(f"  {var}: {agg['bytes_per_linear_packed']/1e3:.1f} kB/linear "
              f"vs dense {agg['bytes_per_linear_dense']/1e3:.1f} kB "
              f"({agg['bytes_ratio']:.2f}x)")
    moe = rows["moe"]
    print(f"  moe[{moe['arch']}]: expert bytes "
          f"{moe['expert_bytes_packed']/1e3:.1f} kB vs dense "
          f"{moe['expert_bytes_dense']/1e3:.1f} kB "
          f"({moe['expert_bytes_ratio']:.2f}x), "
          f"fallback={moe['dense_fallback']}")
    print("packed_serve check:", "PASS" if check(rows) else "FAIL")
