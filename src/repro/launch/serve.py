"""Serving driver: batched prefill + decode with optionally SLaB-
compressed weights.

  python -m repro.launch.serve --arch llama2_7b --smoke --compress slab \
      --batch 8 --prompt-len 64 --gen-len 32

  # mixed-method per-linear policy (plan DSL, JSON, or @file.json):
  python -m repro.launch.serve --arch deepseek_moe_16b \
      --plan 'attn.*=sparsegpt; moe.shared.*=slab@cr=0.4; *=slab'

  # sensitivity-driven per-layer CRs at a 0.5 global budget (one
  # calibration pass; equivalent: --plan '*=slab@auto; budget=0.5'):
  python -m repro.launch.serve --arch llama2_7b --budget 0.5

Pipeline: load/init params -> (optional) layer-wise compression driven
by a CompressionPlan with calibration data -> prefill the prompt batch
-> greedy decode. ``--compress <method>`` stays as sugar for the
single-rule plan ``*=<method>``; ``--plan`` takes anything
``CompressionPlan.parse`` accepts and wins when both are given;
``--budget`` routes either through ``core.allocator`` (water-filled
per-layer CRs from one calibration pass) and prints the per-layer CR
table. The compressed weights can be served either as dense-equivalent
swaps (XLA path) or through the fused Pallas kernels (--packed;
interpret mode on CPU, compiled Mosaic on TPU). ``--no-smoke`` reaches
the full-size configs. ``serve`` holds the whole sequence (init,
calibrate + compress, pack, engine or static batch); ``main`` and
``chip_smoke.py`` both call it.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from pathlib import Path
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import compressor as compressor_lib
from repro.core.pipeline import compress_model
from repro.core.plan import CompressionPlan
from repro.core.slab import SLaBConfig
from repro.data import SyntheticCorpus, calibration_batch
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.models.common import ArchConfig, positions_for

REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep compiled programs across processes, for entry points to call
    (importing this module changes nothing). ``JAX_COMPILATION_CACHE_DIR``
    wins when set — JAX reads it itself; otherwise the cache lives in a
    fixed ``.jax_cache/`` at the repo root (the directory is part of the
    cache key, so it must not move). Returns the directory in use."""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not d:
        d = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", d)
    return d


def greedy_decode(cfg, params, prompts: jnp.ndarray, gen_len: int,
                  lengths=None):
    """Prefill + greedy generation in TWO dispatches: one ``lax.scan``
    over the prompt positions (the cache tracks its own write offset,
    so scanning the decode step is semantically identical to the old
    token-by-token Python loop — without its O(prompt_len) dispatch
    overhead) and one scanned generation loop. Runs under the ambient
    mesh (``meshctx.use_mesh``) when the caller entered one.

    ``lengths`` (B,) serves a right-padded ragged batch: row ``r``'s
    prompt is ``prompts[r, :lengths[r]]`` and its ``gen_len`` outputs
    start right after it. Implemented as ONE unified scan: at step t a
    row feeds its next prompt token while t < length, its previously
    sampled token after — every row's token stream stays contiguous
    from position 0, so the shared cache offset and positions are exact
    for all rows and no masking is needed. Short rows keep decoding
    past their budget (harmless; extra tokens are dropped by the final
    per-row gather)."""
    b, s = prompts.shape
    if lengths is not None:
        return _greedy_decode_ragged(cfg, params, prompts, gen_len,
                                     jnp.asarray(lengths, jnp.int32))
    s_max = s + gen_len
    cache = lm.init_cache(cfg, b, s_max)

    # the weights are a jit argument, never a closure: closed-over
    # arrays would be compiled into the program as constants
    def step(params, cache, tok, pos):
        return lm.decode_step(cfg, params, cache, tok, pos)

    @jax.jit
    def prefill(params, cache, prompts, pos_all, logits0):
        def body(carry, xs):
            c, _ = carry
            tok, pos = xs
            pos = pos[:, None] if pos.ndim == 1 else pos[:, None, :]
            logits, c = step(params, c, tok[:, None], pos)
            return (c, logits[:, -1]), None
        xs = (jnp.moveaxis(prompts, 1, 0),
              jnp.moveaxis(pos_all, 1, 0))
        (cache, logits), _ = jax.lax.scan(body, (cache, logits0), xs)
        return cache, logits

    @jax.jit
    def generate(params, cache, last_logits):
        first = jnp.argmax(last_logits, -1)

        def body(carry, t):
            cache, tok = carry
            pos = positions_for(cfg, b, 1, offset=t)
            logits, cache = step(params, cache, tok[:, None], pos)
            nxt = jnp.argmax(logits[:, -1], -1)
            return (cache, nxt), nxt

        (cache, _), rest = jax.lax.scan(
            body, (cache, first), jnp.arange(s, s + gen_len - 1))
        return jnp.concatenate([first[:, None],
                                jnp.moveaxis(rest, 0, 1)], axis=1)

    sd = jax.eval_shape(step, params, cache, prompts[:, :1],
                        positions_for(cfg, b, 1))[0]
    logits0 = jnp.zeros((b, cfg.vocab), sd.dtype)
    cache, last_logits = prefill(params, cache, prompts,
                                 positions_for(cfg, b, s), logits0)
    return generate(params, cache, last_logits)


def _greedy_decode_ragged(cfg, params, prompts, gen_len, lengths):
    b, s = prompts.shape
    n_steps = s + gen_len - 1               # longest row: s-1 prompt
    cache = lm.init_cache(cfg, b, s + gen_len)  # steps + gen_len-1 more
    fed = jnp.concatenate(                  # prompt stream, zero-padded
        [prompts.astype(jnp.int32),
         jnp.zeros((b, n_steps - s), jnp.int32)], axis=1)

    @jax.jit
    def run(params, cache, fed, lengths):
        def body(carry, xs):
            cache, prev = carry
            ptok, t = xs
            tok = jnp.where(t < lengths, ptok, prev)
            pos = positions_for(cfg, b, 1, offset=t)
            logits, cache = lm.decode_step(cfg, params, cache,
                                           tok[:, None], pos)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            return (cache, nxt), nxt

        xs = (jnp.moveaxis(fed, 1, 0), jnp.arange(n_steps))
        _, ys = jax.lax.scan(body, (cache, jnp.zeros((b,), jnp.int32)), xs)
        sampled = jnp.moveaxis(ys, 0, 1)    # (B, n_steps)
        idx = lengths[:, None] - 1 + jnp.arange(gen_len)[None, :]
        return jnp.take_along_axis(sampled, idx, axis=1)

    return run(params, cache, fed, lengths)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2_7b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced smoke geometry (--no-smoke for the "
                         "full-size config)")
    ap.add_argument("--compress",
                    choices=["none"] + compressor_lib.available(),
                    default="slab",
                    help="single-method sugar for --plan '*=<method>'")
    ap.add_argument("--plan", default=None,
                    help="CompressionPlan spec: inline DSL "
                         "('attn.*=sparsegpt; *=slab@cr=0.4'), JSON, or "
                         "@/path/to/plan.json; overrides --compress")
    ap.add_argument("--budget", type=float, default=None,
                    help="global CR budget: allocate per-layer CRs by "
                         "sensitivity water-filling (core.allocator) "
                         "over --plan/--compress, from one calibration "
                         "pass")
    ap.add_argument("--packed", action="store_true",
                    help="serve through the fused Pallas kernels (SLaB "
                         "on-HBM format; interpret mode on CPU); every "
                         "decomposition must pack")
    ap.add_argument("--engine", action="store_true",
                    help="serve an open-loop request trace through the "
                         "continuous-batching engine (paged KV cache + "
                         "scheduler, docs/serving_engine.md) instead of "
                         "one static greedy_decode batch; composes with "
                         "--packed/--plan/--mesh")
    ap.add_argument("--requests", type=int, default=8,
                    help="--engine: requests in the synthetic trace")
    ap.add_argument("--block-size", type=int, default=16,
                    help="--engine: paged-cache tokens per block")
    ap.add_argument("--deadline", type=float, default=None,
                    help="--engine: per-request TTL in seconds — a "
                         "request not finished by arrival+TTL times "
                         "out (status 'timeout', partial output kept)")
    ap.add_argument("--max-waiting", type=int, default=None,
                    help="--engine: bound the waiting queue; overflow "
                         "arrivals are load-shed (status 'shed')")
    ap.add_argument("--shed", default="reject",
                    choices=["reject", "evict-oldest-waiting"],
                    help="--engine: load-shedding policy when "
                         "--max-waiting overflows")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="--engine: run under a seeded FaultPlan "
                         "(pool-shrink, forced NaNs, arrival burst — "
                         "serving/faults.py) to exercise the recovery "
                         "paths; same seed, same faults")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="run prefill+decode under a (data, model) "
                         "device mesh, e.g. --mesh 1,4: weights are "
                         "planner-placed and packed leaves are born "
                         "with their per-variant NamedShardings "
                         "(docs/packed_serving.md §Sharding)")
    ap.add_argument("--cr", type=float, default=0.5)
    ap.add_argument("--pattern", default=None)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--calib-seqs", type=int, default=16)
    ap.add_argument("--calib-batch", type=int, default=0,
                    help="stream calibration in chunks of this many "
                         "sequences (0 = single batch)")
    ap.add_argument("--calib-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def make_trace(args, vocab: int) -> list:
    """The synthetic open-loop trace: ``--requests`` requests with
    prompts of [prompt_len/2, prompt_len] tokens and [gen_len/2,
    gen_len] new tokens, exponential inter-arrival gaps (mean 0.2 s)."""
    from repro.serving import Request
    rng = np.random.default_rng(args.seed)
    reqs = []
    t_arr = 0.0
    for i in range(args.requests):
        p_len = int(rng.integers(max(args.prompt_len // 2, 1),
                                 args.prompt_len + 1))
        n_new = int(rng.integers(max(args.gen_len // 2, 1),
                                 args.gen_len + 1))
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, vocab, size=p_len),
            max_new=n_new, arrival=t_arr,
            deadline=(t_arr + args.deadline
                      if args.deadline is not None else None)))
        t_arr += float(rng.exponential(0.2))
    return reqs


@dataclasses.dataclass
class Served:
    """What one ``serve`` call did. ``params`` are the weights served
    (packed leaves under --packed); ``reference`` the dense-equivalent
    weights the compression reconstructs (the original ones when
    nothing was compressed) — what a plain reference forward runs."""
    cfg: ArchConfig
    params: dict
    reference: dict
    report: object = None            # PackReport under --packed
    requests: List = dataclasses.field(default_factory=list)
    metrics: Optional[dict] = None   # engine.summarize() under --engine
    generated: Optional[np.ndarray] = None   # static-batch tokens
    compile_s: float = 0.0
    wall_s: float = 0.0
    mesh: object = None


def serve(args, cfg: Optional[ArchConfig] = None,
          requests: Optional[Sequence] = None) -> Served:
    """init -> calibrate + compress -> pack -> serve, as ``args`` (the
    ``build_parser`` namespace) says. ``cfg`` overrides the --arch
    config; ``requests`` overrides the synthetic --engine trace. Raises
    when --packed packs nothing or leaves a decomposition dense."""
    cfg = cfg or configs.get(args.arch, smoke=args.smoke)
    params, axes = lm.init(cfg, jax.random.PRNGKey(args.seed))
    print(f"{cfg.name}: {lm.param_count(cfg)/1e6:.2f}M params")

    mesh, planner = None, None
    if args.mesh:
        from repro.runtime.sharding import Planner
        d, m = (int(x) for x in args.mesh.split(","))
        if d * m > jax.device_count():
            raise ValueError(
                f"--mesh {args.mesh} needs {d * m} devices, have "
                f"{jax.device_count()} (CPU: set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={d * m})")
        mesh = make_mesh((d, m), ("data", "model"))
        planner = Planner(mesh, cfg)
        print(f"mesh: data={d} x model={m} over {d * m} devices")

    scfg = SLaBConfig(cr=args.cr, pattern=args.pattern, iters=args.iters)
    plan = (CompressionPlan.parse(args.plan, base=scfg)
            if args.plan else None)
    if args.budget is not None and plan is None and args.compress == "none":
        raise ValueError("--budget needs something to allocate: give "
                         "--plan or a --compress method")
    if args.packed and plan is None and args.compress == "none":
        raise ValueError("--packed needs something to pack: give --plan "
                         "or a --compress method")
    report = None
    if plan is not None or args.compress != "none":
        calib = calibration_batch(cfg.vocab, seed=args.seed,
                                  n_seq=args.calib_seqs,
                                  seq_len=args.calib_len)
        if args.calib_batch:
            from repro.core.plan import CalibrationSpec
            calib = CalibrationSpec(calib, batch_size=args.calib_batch)
        t0 = time.monotonic()
        stats_pre = None
        if args.budget is not None:
            from repro.core.allocator import allocate_plan
            alloc = allocate_plan(
                cfg, params, calib, budget=args.budget,
                template=(plan if plan is not None
                          else f"*={args.compress}"), base=scfg)
            plan, stats_pre = alloc.plan, alloc.stats
            print(f"allocated {len(alloc.crs)} CR groups at budget "
                  f"{alloc.budget:.3f} (achieved {alloc.achieved:.3f}, "
                  f"one calibration pass, "
                  f"{alloc.stats.n_forwards} layer forwards)")
        out = compress_model(cfg, params, calib, method=args.compress,
                             scfg=scfg, plan=plan,
                             keep_decompositions=args.packed,
                             stats=stats_pre)
        params, stats = out[0], out[1]
        decs = out[2] if args.packed else None
        del out
        by_method = sorted({s.method for s in stats})
        cr_meas = float(np.mean([s.cr for s in stats])) if stats else 0.0
        print(f"compressed {len(stats)} linears "
              f"({'/'.join(by_method)}) at measured CR={cr_meas:.3f} "
              f"in {time.monotonic() - t0:.1f}s")
        if args.plan is not None or args.budget is not None:
            # per-layer CR table: allocator / plan decisions stay
            # observable without rerunning calibration
            print(f"{'layer':>5}  {'path':<20} {'method':<10} "
                  f"{'cr_req':>7} {'cr':>7} {'err_before':>11} "
                  f"{'err_after':>10}")
            for s in stats:
                print(f"{s.layer:>5}  {s.name:<20} {s.method:<10} "
                      f"{s.cr_requested:>7.3f} {s.cr:>7.3f} "
                      f"{s.err_before:>11.4g} {s.err_after:>10.4g}")
    if planner is not None:
        # place the (dense-equivalent) weights BEFORE packing so packed
        # leaves are born on the mesh, not resharded after
        params = jax.device_put(params, planner.tree_shardings(axes, params))
    reference = params
    if args.packed:
        from repro.core.packed_model import pack_plan_decs
        eff_plan = (plan if plan is not None
                    else CompressionPlan.parse(f"*={args.compress}",
                                               base=scfg))
        params, report = pack_plan_decs(
            params, decs, cfg.n_layers, eff_plan, dtype=cfg.dtype,
            variants={(s.layer, s.name): s.variant for s in stats},
            planner=planner)
        del decs
        _print_pack_report(report, cfg.n_layers)
        if not report.n_packed:
            raise RuntimeError("--packed: the plan produced no packable "
                               "decompositions")
        if report.fallback:
            raise RuntimeError(
                "--packed: decompositions left on the dense path: "
                + ", ".join(f"L{l}/{p}" for l, p in report.fallback))

    res = Served(cfg, params, reference, report, mesh=mesh)
    if args.engine:
        _serve_engine(args, res, planner, requests)
        return res

    from repro.runtime.meshctx import use_mesh
    corpus = SyntheticCorpus(cfg.vocab, seed=args.seed)
    prompts = jnp.asarray(
        corpus.batch(0, args.batch, args.prompt_len)["inputs"])
    t0 = time.monotonic()
    with use_mesh(mesh):
        gen = greedy_decode(cfg, params, prompts, args.gen_len)
        jax.block_until_ready(gen)
    res.wall_s = time.monotonic() - t0
    res.generated = np.asarray(gen)
    return res


def _print_pack_report(rep, n_layers: int) -> None:
    variants = " ".join(f"{v}={c}" for v, c in sorted(rep.by_variant.items()))
    print(f"packed serving: {rep.n_packed} linears on the fused kernel "
          f"path across {len(rep.paths)} paths [{variants}]; dense "
          f"fallback: {len(rep.fallback)}")
    print(f"segment layout: {len(rep.segments)} scan segment(s) over "
          f"{n_layers} layers")
    for seg in rep.segments:
        span = (f"L{seg.lo}" if seg.hi == seg.lo + 1
                else f"L{seg.lo}-L{seg.hi - 1}")
        print(f"  {span}: " + "  ".join(f"{p}={d}" for p, d in seg.sig))
    for var, (pb, db) in sorted(rep.bytes_by_variant.items()):
        flag = "  <-- exceeds dense" if pb > db else ""
        print(f"  bytes/{var}: {pb / 1e3:.1f} kB packed vs "
              f"{db / 1e3:.1f} kB dense ({pb / db:.2f}x){flag}")


def _serve_engine(args, res: Served, planner, requests) -> None:
    from repro.serving import Engine, EngineConfig
    from repro.serving.engine import summarize
    from repro.serving.paged_cache import blocks_needed
    reqs = (list(requests) if requests is not None
            else make_trace(args, res.cfg.vocab))
    max_len = max([args.prompt_len + args.gen_len]
                  + [len(r.prompt) + r.max_new for r in reqs])
    per_req = blocks_needed(max_len, args.block_size)
    ecfg = EngineConfig(
        n_slots=args.batch, block_size=args.block_size,
        n_blocks=per_req * args.batch, max_len=max_len,
        prefill_chunk=min(8, args.prompt_len),
        max_waiting=args.max_waiting, shed=args.shed)
    eng = Engine(res.cfg, res.params, ecfg, mesh=res.mesh, planner=planner)
    faults = None
    if args.chaos is not None:
        from repro.serving.faults import FaultPlan
        faults = FaultPlan.chaos(args.chaos, vocab=res.cfg.vocab,
                                 n_rows=args.batch)
        print(f"chaos: {faults!r}")
    res.compile_s = eng.compile()
    t0 = time.monotonic()
    res.requests = eng.run(reqs, clock="wall", faults=faults)
    res.wall_s = time.monotonic() - t0
    m = res.metrics = summarize(res.requests, res.wall_s)
    statuses = " ".join(f"{k}={v}" for k, v in sorted(m["statuses"].items()))
    print(f"engine: {m['n_requests']} requests [{statuses}], "
          f"{m['n_tokens_out']} tokens in {m['wall_s']:.1f}s "
          f"({m['tokens_per_s']:.1f} tok/s, goodput "
          f"{m['goodput_tokens_per_s']:.1f} tok/s, {eng.n_steps} steps, "
          f"{m['n_evictions']} evictions; {res.compile_s:.1f}s compiling "
          f"before the trace)")
    print(f"  ttft p50/p95/p99: {m['ttft']['p50']:.3f}/"
          f"{m['ttft']['p95']:.3f}/{m['ttft']['p99']:.3f}s")
    lat = m['per_token_latency']
    print(f"  per-token p50/p95/p99: {lat['p50'] * 1e3:.1f}/"
          f"{lat['p95'] * 1e3:.1f}/{lat['p99'] * 1e3:.1f}ms")
    _report_obs(eng.obs)


def _report_obs(rec) -> None:
    """The engine's own record (``serving/obs.py``): mean time per step
    in each span, TTFT split into queue wait and prefill, counters."""
    by_name: dict = {}
    for name, t0, t1, _, _ in rec.spans:
        by_name.setdefault(name, []).append((t1 - t0) / 1e6)
    if by_name:
        print("  observability, mean ms per step: " + ", ".join(
            f"{n} {np.mean(v):.2f}" for n, v in by_name.items()))
    done = [r for r in rec.requests if r["first_token"] is not None]
    if done:
        wait = [r["admitted"] - r["arrival"] for r in done]
        pre = [r["first_token"] - r["admitted"] for r in done]
        print(f"  ttft split p50: queue wait {np.median(wait):.3f}s + "
              f"prefill {np.median(pre):.3f}s")
    print("  counters: " + ", ".join(
        f"{k}={v}" for k, v in sorted(rec.counters.items())))


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    res = serve(args)
    if args.engine:
        print("sample generation:",
              np.asarray(res.requests[0].out, np.int32)[:16])
        return
    n_tok = args.batch * (args.prompt_len + args.gen_len)
    print(f"served {args.batch} seqs x ({args.prompt_len}+{args.gen_len}) "
          f"tokens in {res.wall_s:.1f}s ({n_tok / res.wall_s:.1f} tok/s)")
    print("sample generation:", res.generated[0][:16])


if __name__ == "__main__":
    main()
