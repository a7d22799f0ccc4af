"""Smoke run of the serving engine on a TPU with StableLM-2-12B's block
at its published widths.

    python chip_smoke.py               # one chip: phases (a) and (b)
    python chip_smoke.py --four-chips  # phase (b) on a (1, 4) mesh vs the
                                       # same phase on one device

It drives the code ``python -m repro.launch.serve --engine --packed``
drives (``serve.serve``): random weights from ``--seed``, the
``stablelm_12b`` config (StableLM-2-12B: LayerNorm, per-head q/k norm,
rotary on 40 of 160 dims, parallel residual) at every published width
with its depth cut to 4 layers (about 2.1 B parameters, 4.3 GB in
bf16), and one seeded trace
of 8 requests (prompts of 64-256 tokens, 16-32 new tokens) through 8
engine slots over 16-token KV blocks.

  (a) dense bf16 weights;
  (b) SLaB at 2:4, CR 0.5, calibrated on 8 x 256 tokens, packed: every
      linear must be served by the fused ``slab-nm`` kernel.

Each phase checks that every request finished, and that the logits of
``lm.paged_decode_step`` — the step the engine scans — over two prompts
agree with a plain float32 ``lm.forward`` of the same weights (for (b),
the dense-equivalent weights the decomposition reconstructs), run on
the host CPU at ``highest`` matmul precision. The seconds printed are
those of one smoke run, not a measurement. The last line of standard
output is ``{"ok": true, "device": {...}}``; any failure raises and
exits non-zero. Without a TPU it exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

N_LAYERS = 4             # depth cut: the widths stay as published
N_SLOTS = 8
BLOCK = 16
N_REQUESTS = 8
PROMPT_LEN = (64, 256)   # inclusive token ranges of the trace
NEW_TOKENS = (16, 32)
CHECK_ROWS, CHECK_LEN = 2, 64
PHASE_B = ["--compress", "slab", "--pattern", "2:4", "--cr", "0.5",
           "--iters", "2", "--calib-seqs", "8", "--calib-len", "256",
           "--packed"]

# Relative L2 error of one position's logits against the f32 reference,
# worst position. The served model keeps activations in bf16 (8
# significant bits, ~4e-3 per rounding) through 4 residual layers plus
# the unembedding; the packed phase adds bf16 rounding of the N:M
# values and the rank-1 factors, which the reference takes exact from
# the reconstruction. Measured with the same weights on the CPU at
# smoke width: about 1e-2; a wrong kernel, layout or mask gives O(1).
LOGIT_TOL = 5e-2
# The (1, 4) mesh against one device: both serve the same bf16 packed
# weights; only the summation order of the sharded reductions differs.
MESH_TOL = 2e-2


def _rel_err(got, want):
    """Worst per-position relative L2 error over the vocabulary."""
    import numpy as np
    num = np.linalg.norm(got - want, axis=-1)
    den = np.maximum(np.linalg.norm(want, axis=-1), 1e-30)
    return float(np.max(num / den))


def make_requests(seed: int, vocab: int):
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, size=int(
                        rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1))),
                    max_new=int(rng.integers(NEW_TOKENS[0],
                                             NEW_TOKENS[1] + 1)),
                    arrival=0.05 * i)
            for i in range(N_REQUESTS)]


def paged_logits(cfg, params, prompts, mesh=None):
    """Logits of every prompt position through ``lm.paged_decode_step``
    over a fresh paged cache: (R, S, V) float32 on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm
    from repro.runtime.meshctx import use_mesh
    from repro.serving.paged_cache import blocks_needed, init_paged_cache
    r, s = prompts.shape
    n_bt = blocks_needed(s, BLOCK)
    tables = jnp.arange(r * n_bt, dtype=jnp.int32).reshape(r, n_bt)

    @jax.jit
    def run(params, paged, tokens):
        def body(carry, tok):
            paged, lens = carry
            logits, paged = lm.paged_decode_step(
                cfg, params, paged, tables, lens, tok[:, None],
                jnp.ones((r,), bool))
            return (paged, lens + 1), logits[:, 0]
        _, ys = jax.lax.scan(body, (paged, jnp.zeros((r,), jnp.int32)),
                             tokens.T)
        return ys

    with use_mesh(mesh):
        ys = run(params, init_paged_cache(cfg, r * n_bt, BLOCK),
                 jnp.asarray(prompts))
    return np.asarray(ys, np.float32).transpose(1, 0, 2)


def reference_logits(cfg, params, prompts):
    """Plain float32 ``lm.forward`` on the host CPU, highest precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import lm
    cpu = jax.devices("cpu")[0]
    cfg32 = cfg.with_(dtype=jnp.float32)
    host = jax.device_put(jax.device_get(params), cpu)

    @jax.jit
    def fwd(p, t):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        return lm.forward(cfg32, p, t)[0]

    with jax.default_matmul_precision("highest"):
        out = fwd(host, jax.device_put(prompts, cpu))
    return np.asarray(out, np.float32)


def run_phase(name: str, argv, cfg, seed: int, reference: bool = True):
    """One serve() call with its checks. Returns the paged logits of the
    check prompts; raises on any failure."""
    import jax
    import numpy as np
    from repro.launch import serve
    args = serve.build_parser().parse_args(
        ["--engine", "--batch", str(N_SLOTS), "--block-size", str(BLOCK),
         "--prompt-len", str(PROMPT_LEN[1]),
         "--gen-len", str(NEW_TOKENS[1]), "--seed", str(seed)] + argv)
    t0 = time.monotonic()
    res = serve.serve(args, cfg=cfg,
                      requests=make_requests(seed, cfg.vocab))
    statuses = {r.rid: r.status for r in res.requests}
    if any(st != "finished" for st in statuses.values()):
        raise RuntimeError(f"phase {name}: unfinished requests {statuses}")
    if args.packed:
        want = {"slab-nm": 7 * cfg.n_layers}
        if dict(res.report.by_variant) != want:
            raise RuntimeError(f"phase {name}: packed variants "
                               f"{dict(res.report.by_variant)}, want {want}")
    rng = np.random.default_rng(seed + 1)
    prompts = rng.integers(0, cfg.vocab, size=(CHECK_ROWS, CHECK_LEN),
                           dtype=np.int32)
    got = paged_logits(cfg, res.params, prompts, res.mesh)
    if not np.all(np.isfinite(got)):
        raise RuntimeError(f"phase {name}: non-finite logits")
    err = None
    if reference:
        want = reference_logits(cfg, res.reference, prompts)
        err = _rel_err(got, want)
        agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
        print(f"phase {name}: logits vs f32 reference: worst relative "
              f"L2 error {err:.3e} (limit {LOGIT_TOL}), top-1 agreement "
              f"{agree:.3f}")
        if not err < LOGIT_TOL:
            raise RuntimeError(f"phase {name}: logits off the f32 "
                               f"reference by {err:.3e}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"phase {name}: {len(statuses)} requests finished, "
          f"{res.metrics['n_tokens_out']} tokens; smoke run, not a "
          f"measurement: engine compile {res.compile_s:.1f}s, trace "
          f"{res.wall_s:.1f}s, phase {time.monotonic() - t0:.1f}s; "
          f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}")
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run phase (b) on a (1, 4) mesh and compare its "
                         "logits with the same phase on one device; "
                         "nothing else")
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro import configs
    from repro.launch.serve import enable_compile_cache
    cache = enable_compile_cache()
    n_dev = len(jax.devices())
    print(f"device: {dev.device_kind} x{n_dev} ({dev.platform}); "
          f"compile cache {cache}")
    cfg = configs.get("stablelm_12b").with_(n_layers=N_LAYERS)
    print(f"config: {cfg.name} at published widths (d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}, {cfg.n_heads} heads / "
          f"{cfg.n_kv} KV x {cfg.d_head}, vocab {cfg.vocab}); depth cut "
          f"{configs.get('stablelm_12b').n_layers} -> {cfg.n_layers} "
          f"layers")
    if a.four_chips:
        if n_dev < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, have "
                  f"{n_dev}", file=sys.stderr)
            return 1
        one = run_phase("b/1-device", PHASE_B, cfg, a.seed,
                        reference=False)
        gc.collect()
        mesh = run_phase("b/mesh-1x4", PHASE_B + ["--mesh", "1,4"], cfg,
                         a.seed, reference=False)
        err = _rel_err(mesh, one)
        print(f"four chips: (1, 4) mesh vs one device, worst relative L2 "
              f"error {err:.3e} (limit {MESH_TOL}); every packed linear, "
              f"q/k/v included, ran sharded on its d_out")
        if not err < MESH_TOL:
            raise RuntimeError(f"mesh logits off one device by {err:.3e}")
    else:
        run_phase("a/dense", ["--compress", "none"], cfg, a.seed)
        gc.collect()
        run_phase("b/slab-2:4-packed", PHASE_B, cfg, a.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
