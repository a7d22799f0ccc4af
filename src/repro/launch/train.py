"""Training driver: config-driven, checkpointed, fault-tolerant.

Runs REAL training at whatever scale the local device set allows (the
CPU container trains the reduced configs; on a pod the same entrypoint
takes the full ones):

  python -m repro.launch.train --arch llama2_7b --smoke --steps 200 \
      --batch 16 --seq 256 --ckpt-dir /tmp/run1

Features exercised end-to-end: synthetic data pipeline keyed by (seed,
step, host), microbatched grad accumulation, remat policy, AdamW +
cosine, atomic async checkpoints, watchdog supervision with restore-and-
replay, elastic restore onto a different mesh (--restore-from).
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.launch.mesh import make_mesh
from repro.data import SyntheticCorpus
from repro.models import lm
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.runtime import specs as specs_lib
from repro.runtime.elastic import elastic_restore
from repro.runtime.fault import FaultConfig, Supervisor
from repro.runtime.meshctx import use_mesh
from repro.runtime.sharding import Planner
from repro.runtime.step import make_train_fn


def build_mesh(data: int, model: int) -> Mesh:
    return make_mesh((data, model), ("data", "model"))


def train(arch: str, smoke: bool, steps: int, batch: int, seq: int,
          ckpt_dir: Optional[str], data_par: int = 1, model_par: int = 1,
          microbatches: int = 1, remat: str = "none",
          lr: float = 3e-4, seed: int = 0, log_every: int = 10,
          ckpt_every: int = 50, restore: bool = False,
          inject_failure_at: Optional[int] = None):
    cfg = configs.get(arch, smoke=smoke)
    mesh = build_mesh(data_par, model_par)
    planner = Planner(mesh, cfg)
    acfg = AdamWConfig(lr=lr, total_steps=max(steps, 2),
                       warmup_steps=max(steps // 20, 1))

    params, axes = lm.init(cfg, jax.random.PRNGKey(seed))
    p_sh = planner.tree_shardings(axes, params)
    params = jax.device_put(params, p_sh)
    opt = adamw_init(params, acfg)

    corpus = SyntheticCorpus(cfg.vocab, seed=seed)
    with use_mesh(mesh):
        fn = make_train_fn(cfg, acfg, planner, microbatches=microbatches,
                           remat=remat)
        step_jit = jax.jit(fn, donate_argnums=(0, 1))

    mgr = CheckpointManager(ckpt_dir, keep=2) if ckpt_dir else None
    start = 0
    state = {"params": params, "opt": opt}
    if restore and mgr and mgr.latest_step() is not None:
        state = elastic_restore(mgr, cfg, acfg, mesh)
        start = mgr.latest_step()
        print(f"restored step {start}")

    def make_batch(step: int):
        b = corpus.batch(step, batch, seq)
        if cfg.input_mode == "embeds":
            rng = np.random.default_rng(step)
            b["inputs"] = rng.standard_normal(
                (batch, seq, cfg.d_model), dtype=np.float32)
        return {k: jnp.asarray(v) for k, v in b.items()}

    losses = []

    def step_fn(state, step):
        if inject_failure_at is not None and step == inject_failure_at:
            # one-shot injection: only fail the first time we reach it
            state.setdefault("_failed", False)
            if not state["_failed"]:
                state["_failed"] = True
                raise RuntimeError("injected")
        p, o, m = step_jit(state["params"], state["opt"], make_batch(step))
        new = {"params": p, "opt": o}
        if "_failed" in state:
            new["_failed"] = state["_failed"]
        return new, m

    def restore_fn(at_step):
        st = elastic_restore(mgr, cfg, acfg, mesh, step=at_step)
        st["_failed"] = True
        return st

    sup = Supervisor(mgr, FaultConfig(ckpt_every=ckpt_every)) if mgr else None

    def on_metrics(step, m):
        losses.append(float(m["loss"]))
        if step % log_every == 0:
            print(f"step {step:5d} loss {float(m['loss']):.4f} "
                  f"gnorm {float(m['grad_norm']):.3f} "
                  f"lr {float(m['lr']):.2e}", flush=True)

    t0 = time.monotonic()
    if sup:
        # supervisor checkpoints {"params","opt"} (drop bookkeeping keys)
        class MgrView:
            def __init__(self, mgr):
                self.m = mgr
            def save(self, step, tree):
                self.m.save(step, {"params": tree["params"],
                                   "opt": tree["opt"]})
            def __getattr__(self, k):
                return getattr(self.m, k)
        sup.mgr = MgrView(mgr)
        state = sup.run(state, start, steps, step_fn, restore_fn,
                        on_metrics)
        print(f"restarts={sup.stats.restarts} "
              f"stragglers={sup.stats.stragglers}")
    else:
        for s in range(start, steps):
            state, m = step_fn(state, s)
            on_metrics(s, m)
    dt = time.monotonic() - t0
    print(f"trained {steps - start} steps in {dt:.1f}s "
          f"({(steps - start) / max(dt, 1e-9):.2f} steps/s); "
          f"final loss {losses[-1]:.4f}")
    return state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2_7b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--restore", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    train(args.arch, args.smoke, args.steps, args.batch, args.seq,
          args.ckpt_dir, args.data_par, args.model_par, args.microbatches,
          args.remat, args.lr, args.seed, ckpt_every=args.ckpt_every,
          restore=args.restore)


if __name__ == "__main__":
    main()
