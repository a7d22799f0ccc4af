"""One run of one cell: build the served model from the seed, warm up,
drive ``Engine.run(..., clock="wall")`` over the cell's traffic, reduce
what the benchmark recorded to metrics, and decide ``correct`` against
the plain reference.

The harness never copies the engine's loop. It records the loop by
wrapping three public methods of the engine's scheduler instance:
``admit(now)`` gives the engine's clock, ``plan_step()`` each step's
work (positions, rows, context lengths), and ``commit_step`` the step's
end. With ``--trace 1`` the same wrappers open host spans (admit, plan,
step, commit) and start and stop the profiler at the window's edges.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import os
import shutil
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from chipbench import spec, traffic, work

# The traced part of a --trace 1 run: this many seconds from the window's
# start (the whole window when it is shorter).
TRACE_SECONDS = 10.0
SPANS = ("admit", "plan", "step", "commit")
WINDOW_SPAN = "chipbench.window"
# The correctness check samples at least CHECK_TOKENS served tokens, in at
# most CHECK_ROWS requests and CHECK_MAX_TOKENS tokens: the reference's
# fixed shape, so that it compiles once per cell.
CHECK_TOKENS = 384
CHECK_ROWS = 8
CHECK_MAX_TOKENS = 1024


@dataclasses.dataclass(frozen=True)
class Plan:
    """One step as the scheduler planned it."""
    t: float             # engine clock when the iteration began
    c: int               # positions in the step (1 = pure decode)
    tokens: int          # valid (row, position) pairs
    rows: int            # rows with work
    ctx: int             # cached tokens attended, summed over the pairs


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric's ``read(run)`` gets. The window is the
    traced one in a traced run (the profiler's start and stop stall the
    host, so they lie outside it), else the measured window."""
    cfg: dict
    mix: dict
    peaks: Dict[str, float]
    rows: int                        # engine slots
    window_s: float                  # host clock
    window_steps: int                # Engine.n_steps over the window
    plans: List[Plan]                # plans made in the window
    trace: Optional[object] = None   # chipbench.trace.Trace when traced


class Probe:
    """Records the engine's loop through its scheduler's public methods."""

    def __init__(self, engine, lo: float, hi: float,
                 trace_dir: Optional[str] = None):
        import jax
        self.jax = jax
        self.engine, self.sched = engine, engine.sched
        self.lo, self.hi = lo, hi
        self.trace_dir = trace_dir
        self.trace_hi = min(hi, lo + TRACE_SECONDS)
        self.offset: Optional[float] = None     # monotonic - engine clock
        self.now = 0.0
        # (engine clock, n_steps) at the window's edges, and at the
        # traced window's
        self.win: List[Optional[tuple]] = [None, None]
        self.twin: List[Optional[tuple]] = [None, None]
        self.plans: List[Plan] = []
        self.tracing = False
        self._open: Dict[str, object] = {}
        self.window_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile)
        self._orig = {n: getattr(self.sched, n)
                      for n in ("admit", "plan_step", "commit_step")}
        self.sched.admit = self._admit
        self.sched.plan_step = self._plan_step
        self.sched.commit_step = self._commit_step

    def _on_compile(self, event, duration, **kw):
        """Counts programs traced or compiled inside the window (there
        should be none: set-up warms every shape the window uses)."""
        if (event in ("/jax/core/compile/backend_compile_duration",
                      "/jax/core/compile/jaxpr_trace_duration")
                and self.win[0] is not None and self.win[1] is None):
            self.window_compiles += 1

    def _span(self, name):
        if not self.tracing:
            return None
        ann = self.jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        return ann

    @staticmethod
    def _close(ann):
        if ann is not None:
            ann.__exit__(None, None, None)

    def _start_trace(self):
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.jax.profiler.start_trace(self.trace_dir,
                                      profiler_options=opts)
        self.tracing = True
        self._open["window"] = self._span(WINDOW_SPAN)
        self.twin[0] = self._stamp()

    def _stamp(self):
        return (time.monotonic() - self.offset, self.engine.n_steps)

    def stop_trace(self):
        if self.tracing:
            self.twin[1] = self._stamp()
            self._close(self._open.pop("step", None))
            self._close(self._open.pop("window", None))
            self.jax.profiler.stop_trace()
            self.tracing = False

    def _admit(self, now):
        if self.offset is None:
            self.offset = time.monotonic() - now
        self.now = now
        if self.win[0] is None and now >= self.lo:
            self.win[0] = (now, self.engine.n_steps)
            if self.trace_dir is not None:
                self._start_trace()
        if self.tracing and now >= self.trace_hi:
            self.stop_trace()
        if self.win[1] is None and now >= self.hi:
            self.win[1] = (now, self.engine.n_steps)
        ann = self._span("admit")
        try:
            return self._orig["admit"](now)
        finally:
            self._close(ann)

    def _plan_step(self):
        ann = self._span("plan")
        try:
            res = self._orig["plan_step"]()
        finally:
            self._close(ann)
        if res is not None:
            tokens, n_valid, _ = res
            nv = n_valid.astype(np.int64)
            lens = self.sched.lengths.astype(np.int64)
            ctx = int(np.sum(nv * lens + nv * (nv + 1) // 2))
            recording = (self.tracing if self.trace_dir is not None
                         else self.win[0] is not None and self.win[1] is None)
            if recording:
                self.plans.append(Plan(self.now, int(tokens.shape[1]),
                                       int(nv.sum()),
                                       int(np.count_nonzero(nv)), ctx))
            self._open["step"] = self._span("step")
        return res

    def _commit_step(self, n_valid, sampled, now):
        self._close(self._open.pop("step", None))
        ann = self._span("commit")
        try:
            return self._orig["commit_step"](n_valid, sampled, now)
        finally:
            self._close(ann)

    def record_window(self):
        """(seconds, steps) of the window the plans were recorded in."""
        w = self.twin if self.trace_dir is not None else self.win
        if None in w:
            return 0.0, 0
        (t0, s0), (t1, s1) = w
        return t1 - t0, s1 - s0

    def finish(self):
        """Close the window if the run ended inside it."""
        self.stop_trace()
        end = (time.monotonic() - self.offset) if self.offset else 0.0
        if self.win[0] is None:
            self.win[0] = (end, self.engine.n_steps)
        if self.win[1] is None:
            self.win[1] = (end, self.engine.n_steps)
        for n, f in self._orig.items():
            setattr(self.sched, n, f)
        self.jax.monitoring.unregister_event_duration_listener(
            self._on_compile)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else a fixed directory inside the checkout (the path is
    part of the cache key, so it never moves)."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        spec.REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def device_info() -> dict:
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def ttft_and_gaps(reqs, specs, lo, hi):
    """TTFTs (ms) of the requests due in the window [lo, hi), and every
    gap (ms) between consecutive tokens of any request that ends in the
    window. A request that never got its first token counts the time it
    was given, up to the run's horizon: a lower bound on its TTFT, which
    leaves the median exact while fewer than half are cut so."""
    ttft, gaps = [], []
    for r, s in zip(reqs, specs):
        ts = r.token_times
        gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:])
                    if lo <= b < hi)
        if not s.counted:
            continue
        if r.ttft is not None:
            ttft.append(r.ttft * 1e3)
        else:
            ttft.append(((r.finish or s.deadline) - s.arrival) * 1e3)
    return ttft, gaps


def end_to_end(names, reqs, specs, lo, hi, setup_s) -> dict:
    ttft, gaps = ttft_and_gaps(reqs, specs, lo, hi)
    vals = {"setup_s": setup_s}
    if ttft:
        vals["ttft_p50_ms"] = work.percentile(ttft, 50)
    if gaps:
        vals["itl_p50_ms"] = work.percentile(gaps, 50)
        vals["itl_p95_ms"] = work.percentile(gaps, 95)
    return {m["name"]: {"value": float(vals[m["name"]]), "unit": m["unit"]}
            for m in names if m["name"] in vals}


# What the engine reports for a request it gave up on. A request cut at
# the run's horizon ("timeout") was not given up on: the run ended.
GIVEN_UP = ("failed", "rejected", "shed")


def attempted_failed(reqs, specs):
    """(requests due in the window, those the engine gave up on)."""
    counted = [r for r, s in zip(reqs, specs) if s.counted]
    return len(counted), sum(r.status in GIVEN_UP for r in counted)


def sample_for_check(reqs, seed: int):
    """Requests whose served tokens go to the reference: the one with the
    most served tokens, then others drawn from the seed until the sample
    holds CHECK_TOKENS served tokens. Any request that was served tokens
    counts, also one cut at the horizon: its tokens were served all the
    same."""
    done = sorted((r for r in reqs if r.out and r.status not in GIVEN_UP),
                  key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.out), -r.rid))
    rng = np.random.default_rng([seed, 7])
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    pick, n = [longest], len(longest.out)
    for r in rest:
        if n >= CHECK_TOKENS or len(pick) == CHECK_ROWS:
            break
        if n + len(r.out) <= CHECK_MAX_TOKENS:
            pick.append(r)
            n += len(r.out)
    return pick


def run_cell(bench: dict, name: str, seed: int, seconds: float,
             trace: bool, t_start: float,
             layout: Optional[spec.Layout] = None,
             control: Optional[str] = None,
             log=print) -> dict:
    """Run cell ``name`` once and return the result line's object."""
    import jax
    from repro.serving import Engine, EngineConfig, Request
    layout = layout or spec.Layout()
    wl = spec.workload(bench, name)
    cfg = layout.config(bench, wl["config"])
    mix = layout.mix(wl["traffic"])
    limits = layout.limits(name)
    arch = importlib.import_module(f"chipbench.archs.{cfg['arch']}")
    dev = device_info()
    pk = work.peaks(dev["kind"]) if dev["platform"] == "tpu" else None

    from chipbench import weights
    key = weights.root_key(seed)
    t0 = time.monotonic()
    pcfg = arch.program_config(cfg)
    params = arch.program_params(cfg, key)
    jax.block_until_ready(params)
    t_weights = time.monotonic() - t0
    e = mix["engine"]
    engine = Engine(pcfg, params, EngineConfig(
        n_slots=e["n_slots"], n_blocks=e["n_blocks"],
        block_size=e["block_size"], max_len=e["max_len"],
        prefill_chunk=e["prefill_chunk"]))
    del params
    t_compile = engine.compile()
    specs = traffic.generate(mix, seed, seconds, cfg["vocab_size"])
    reqs = [Request(rid=s.rid, prompt=s.prompt, max_new=s.max_new,
                    arrival=s.arrival, deadline=s.deadline) for s in specs]
    lo = float(mix["lead_in_s"])
    hi = lo + seconds
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
        else None
    probe = Probe(engine, lo, hi, trace_dir)
    try:
        engine.run(reqs, clock="wall")
    finally:
        probe.finish()
    setup_s = probe.offset + lo - t_start
    log(f"set-up {setup_s:.1f}s: weights {t_weights:.1f}s, compile "
        f"{t_compile:.1f}s, lead-in {lo:.1f}s; {engine.n_steps} steps; "
        f"{probe.window_compiles} programs traced or compiled in the "
        f"window")
    window_compiles = probe.window_compiles
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    record = RunRecord(cfg, mix, pk, e["n_slots"], *probe.record_window(),
                       probe.plans)
    attempted, failed = attempted_failed(reqs, specs)
    pick = sample_for_check(reqs, seed)
    del engine, probe
    gc.collect()

    result: dict = {"correct": False, "attempted": attempted,
                    "failed": failed}
    if trace:
        from chipbench import trace as tr
        path = tr.find_xplane(Path(trace_dir))
        record.trace = tr.read_xplane(path, SPANS, WINDOW_SPAN)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for m in spec.metrics_for(bench, name, "per_layer"):
            v = layout.metric_reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev["busy_s"] = tr.busy_ns(record.trace) / 1e9
        dev["window_s"] = record.trace.window_s
        result["breakdown"] = tr.breakdown(record.trace)
    else:
        metrics = end_to_end(spec.metrics_for(bench, name, "end_to_end"),
                             reqs, specs, lo, hi, setup_s)
    result["metrics"] = metrics
    result["device"] = dev
    result["window_compiles"] = window_compiles

    t_ref = time.monotonic()
    checks, extra = check(cfg, key, pick, limits, failed, control,
                          seq_len=e["max_len"])
    log(f"reference check over {sum(len(r.out) for r in pick)} served "
        f"tokens of {len(pick)} requests: {time.monotonic() - t_ref:.1f}s")
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in checks.values())
    if extra:
        result["control"] = extra
    # the compared numbers come last
    result["checks"] = checks
    return result


def check(cfg, key, pick, limits, failed, control=None, seq_len=512):
    """Each compared number beside its limit. ``widest_logit_gap``: over
    the sampled requests' served tokens, the widest gap by which a
    served token's logit lies below the reference's best. With
    ``control`` the control stands in the program's place: the compared
    gap is that of the tokens the reference computed at that lower
    precision puts first, and the program's own gap is returned beside
    it."""
    arch = importlib.import_module(f"chipbench.archs.{cfg['arch']}")
    checks = {"failed_requests": {"value": failed, "limit": 0}}
    n_tok = sum(len(r.out) for r in pick)
    checks["sampled_tokens_short"] = {
        "value": max(0, limits["min_check_tokens"] - n_tok), "limit": 0}
    extra = None
    if pick:
        seqs = [np.concatenate([r.prompt, np.asarray(r.out[:-1],
                                                     np.int32)])
                for r in pick]
        pos = [list(range(len(r.prompt) - 1, len(r.prompt) - 1
                          + len(r.out))) for r in pick]
        served = [list(r.out) for r in pick]
        gap, gap_c = arch.logit_gaps(
            cfg, key, seqs, pos, served, control,
            shape=(CHECK_ROWS, seq_len, CHECK_MAX_TOKENS))
        compared = gap if gap_c is None else gap_c
        checks["widest_logit_gap"] = {
            "value": float(np.max(compared)),
            "limit": limits["widest_logit_gap"]}
        if gap_c is not None:
            extra = {"precision": control, "served_tokens": n_tok,
                     "program_widest_logit_gap": float(np.max(gap))}
    return checks, extra
