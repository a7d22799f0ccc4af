"""Continuous-batching decode engine: jitted fixed-shape steps over
dynamic request state.

The engine owns R fixed request slots (the batch rows of every jitted
step), a paged KV cache sized in blocks, and a ``Scheduler``. Each
iteration of ``run``:

  1. consult the ``FaultPlan`` (if any): pool-shrink/restore, arrival
     bursts, artificial delays, forced-NaN rows for this step;
  2. expire past-deadline requests, then admit arrived requests into
     free slots (mid-flight — running streams are untouched);
  3. ask the scheduler for this step's batch: prefill rows consume up
     to ``prefill_chunk`` prompt tokens, decode rows ride along with
     one token each (Orca-style fused iteration). Pure-decode steps
     use the C=1 compilation of the same function;
  4. run ONE jitted step: a ``lax.scan`` over the chunk's token
     positions, each position a ``lm.paged_decode_step`` (the segmented
     layer scan + ``flash_decode_paged`` block-table kernel), with
     per-row validity masks; the pool is donated to the step and
     written in place — shapes never depend on which requests are
     live, so there are exactly two compilations (C and 1) for the
     whole serving lifetime (``obs.counters["engine.builds"]`` counts
     them). The step also reduces a per-row finite-logits flag (one
     ``jnp.isfinite`` all-reduce per position);
  5. quarantine rows that went non-finite (retry once via the
     recompute-replay eviction path, then fail them — neighbors in the
     fused batch never see it), sample greedily at each surviving
     row's last valid position, hand tokens back to the scheduler
     (TTFT / latency bookkeeping, retirement), and loop.

``run`` never raises on a valid trace: unservable submissions come
back ``rejected``, deadline misses ``timeout``, ``max_steps``
exhaustion marks everything unfinished ``timeout`` with partial
``out``, and a permanently-stalled admission queue fails the blocked
head with a block-accounting diagnosis instead of spinning.

Open-loop traces: requests carry ``arrival`` stamps; ``clock="steps"``
replays them against the engine-step counter (deterministic — tests),
``clock="wall"`` against wall time (benchmarks). The engine never
blocks on stragglers: batch composition changes every step.

Observability: ``engine.obs`` (``serving/obs.py``) records one
``engine.iteration`` span per step with its parts as children
(``sched.expire``, ``sched.admit``, ``sched.plan``, ``engine.h2d``,
``engine.dispatch``, ``engine.device_wait``, ``engine.commit``), each
request's arrival / admission / first token / finish, the scheduler's
work counters and the step programs built; the device ops of each
program carry named scopes (``embed``, ``layer_scan``, ``attn/wq``,
``attn/qk_norm``, ``attn/rope``, ``kv_write``, ``paged_attn``, ``head``,
``finite_check``, ``sample``).
"""
from __future__ import annotations

import dataclasses
import re
import time
from collections import Counter
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.common import SCOPE_NAMES, ArchConfig, scope
from repro.serving import obs
from repro.serving.faults import FaultPlan
from repro.serving.paged_cache import (PagedKVCache, init_paged_cache,
                                       paged_cache_axes, table_width)
from repro.serving.scheduler import Request, Scheduler

Array = jax.Array

#: graceful backstop for pathological admit/evict cycles the stall
#: diagnosis cannot prove permanent — finalizes instead of raising.
IDLE_LIMIT = 100_000

# One entry of a compiled module's ``input_output_alias``: the output's
# tuple index, then the parameter whose buffer it is written into.
_ALIAS = re.compile(r"\{(\d+)\}: \(\d+, \{[^}]*\}, \w+-alias\)")


def pool_aliases(hlo_text: str, n_pool: int) -> List[int]:
    """The step outputs among the pool's ``n_pool`` leaves (the first
    outputs) that a compiled step program writes into a donated input
    buffer, read from its ``as_text()``."""
    header = hlo_text.split("\n", 1)[0]
    if "input_output_alias=" not in header:
        return []
    return sorted(int(out) for out in _ALIAS.findall(header)
                  if int(out) < n_pool)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 4              # R: concurrent streams (batch rows)
    n_blocks: int = 64            # KV pool size, in blocks
    block_size: int = 16          # tokens per block
    max_len: int = 256            # per-stream cap (prompt + gen - 1)
    prefill_chunk: int = 8        # prompt tokens per prefill step
    max_waiting: Optional[int] = None   # waiting-queue bound (None: ∞)
    shed: str = "reject"          # "reject" | "evict-oldest-waiting"
    max_evictions: int = 8        # evictions before a stream starves
    max_nan_retries: int = 1      # non-finite replays before quarantine


class Engine:
    """Continuous-batching greedy-decode engine over a paged KV cache.

    ``params`` may be dense, SLaB-compressed dense-equivalent, or
    packed (``PackedStack`` leaves — the fused-kernel serving path);
    the paged decode step drives the same segmented layer scan either
    way. Pass ``mesh``/``planner`` (as built by ``serve.py --mesh``) to
    run the steps under a device mesh with planner-placed pools."""

    def __init__(self, cfg: ArchConfig, params: dict,
                 ecfg: EngineConfig = EngineConfig(),
                 mesh=None, planner=None):
        if cfg.family in ("ssm", "hybrid", "audio"):
            raise ValueError(
                f"engine serves KV-attention families; {cfg.family!r} "
                "has no paged cache")
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.mesh = mesh
        self.obs = obs.for_engine()
        self.sched = Scheduler(ecfg.n_slots, ecfg.n_blocks,
                               ecfg.block_size, ecfg.max_len,
                               ecfg.prefill_chunk,
                               max_waiting=ecfg.max_waiting,
                               shed=ecfg.shed,
                               max_evictions=ecfg.max_evictions,
                               obs=self.obs)
        self.paged = init_paged_cache(cfg, ecfg.n_blocks, ecfg.block_size)
        self._pool_sharding = None
        if planner is not None:
            from repro.models.common import is_axes_leaf
            self._pool_sharding = jax.tree.map(
                lambda ax, leaf: planner.sharding(ax, leaf.shape),
                paged_cache_axes(cfg), self.paged, is_leaf=is_axes_leaf)
            self.paged = jax.device_put(self.paged, self._pool_sharding)
        self._steps: Dict[int, object] = {}     # chunk C -> jitted step
        self.n_steps = 0

    # -- jitted step -------------------------------------------------------

    def _step_fn(self, c: int):
        """Compile (once per chunk size) the fused prefill/decode step:
        scan ``c`` token positions; row r is live at position t iff
        t < n_valid[r]. Returns the greedy token at each row's LAST
        valid position (prefill completion / decode output), the
        updated pool, and a per-row ALL-positions-finite flag (the
        numerical guard; ``force_nan`` poisons chosen rows — the
        fault-injection hook, all zeros in normal serving). The weights
        are an argument, never a closure: closed-over arrays would be
        compiled into the program as constants. The pool is donated:
        the step writes it in place, and the engine keeps only the pool
        that comes out."""
        cfg, pool_sharding = self.cfg, self._pool_sharding

        def step(params, paged: PagedKVCache, tables: Array,
                 lengths: Array, tokens: Array, n_valid: Array,
                 force_nan: Array):
            last0 = jnp.zeros((tokens.shape[0],), jnp.int32)
            ok0 = jnp.ones((tokens.shape[0],), bool)

            def body(carry, xs):
                paged, lens, last, ok = carry
                tok, t = xs
                active = t < n_valid
                logits, paged = lm.paged_decode_step(
                    cfg, params, paged, tables, lens, tok[:, None], active)
                with scope("finite_check"):
                    logits = jnp.where(force_nan[:, None, None], jnp.nan,
                                       logits)
                    ok = ok & (jnp.all(jnp.isfinite(logits[:, 0]), axis=-1)
                               | ~active)
                with scope("sample"):
                    nxt = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
                    last = jnp.where(t == n_valid - 1, nxt, last)
                return (paged, lens + active, last, ok), None

            xs = (jnp.moveaxis(tokens, 1, 0), jnp.arange(c))
            (paged, _, last, ok), _ = jax.lax.scan(
                body, (paged, lengths, last0, ok0), xs)
            if pool_sharding is not None:
                # the pool leaves each step in the layout it came in
                paged = jax.lax.with_sharding_constraint(paged,
                                                         pool_sharding)
            return paged, last, ok

        return jax.jit(step, donate_argnums=(1,))

    def _step_args(self, tokens: np.ndarray, n_valid: np.ndarray,
                   force_nan: np.ndarray):
        return (self.params, self.paged,
                jnp.asarray(self.sched.block_table),
                jnp.asarray(self.sched.lengths),
                jnp.asarray(tokens), jnp.asarray(n_valid),
                jnp.asarray(force_nan))

    def compile(self) -> float:
        """Compile both step programs (chunk C and the C=1 decode step)
        ahead of serving, so that no request waits on the compiler.
        Returns the seconds spent; ``run`` compiles lazily otherwise.
        Each program's ops are mapped to their scopes in ``obs.scopes``,
        and ``engine.pool_donated`` counts the pool leaves each program
        aliases from input to output (all of them when the donation
        took: 2 a program for a bf16 pool, 4 for int8)."""
        from repro.runtime.meshctx import use_mesh
        t0 = time.monotonic()
        r = self.sched.n_slots
        for c in sorted({self.ecfg.prefill_chunk, 1}):
            if c in self._steps:
                continue
            args = self._step_args(np.zeros((r, c), np.int32),
                                   np.zeros((r,), np.int32),
                                   np.zeros((r,), bool))
            with use_mesh(self.mesh):
                self._steps[c] = self._step_fn(c).lower(*args).compile()
            self.obs.count("engine.builds")
            text = self._steps[c].as_text()
            self.obs.add_scopes(text, SCOPE_NAMES)
            self.obs.count("engine.pool_donated",
                           len(pool_aliases(text, len(jax.tree.leaves(
                               self.paged)))))
        return time.monotonic() - t0

    def _run_step(self, tokens: np.ndarray, n_valid: np.ndarray,
                  force_nan: np.ndarray):
        from repro.runtime.meshctx import use_mesh
        c = tokens.shape[1]
        if c not in self._steps:
            self._steps[c] = self._step_fn(c)
            self.obs.count("engine.builds")
        with self.obs.span("engine.h2d"):
            args = self._step_args(tokens, n_valid, force_nan)
        with use_mesh(self.mesh), self.obs.span("engine.dispatch"):
            self.paged, last, ok = self._steps[c](*args)
        with self.obs.span("engine.device_wait"):
            return np.asarray(last), np.asarray(ok)

    # -- fault plumbing ----------------------------------------------------

    def _fire_faults(self, faults: Optional[FaultPlan], fired: set,
                     now: float, injected: List[Request]) -> None:
        """Apply every not-yet-fired plan event due at/by this step."""
        if faults is None:
            return
        for i, ev in enumerate(faults.events):
            if i in fired or ev.step > self.n_steps:
                continue
            fired.add(i)
            if ev.kind == "pool_shrink":
                self.sched.alloc.reserve(ev.n_blocks)
            elif ev.kind == "pool_restore":
                self.sched.alloc.release(
                    ev.n_blocks if ev.n_blocks else None)
            elif ev.kind == "burst":
                for spec in ev.bursts:
                    req = spec.materialize(now)
                    self.sched.submit(req)
                    injected.append(req)
            elif ev.kind == "delay":
                time.sleep(ev.delay_s)
            # "nan" events are consumed by nan_rows() at step-run time

    def _quarantine_nonfinite(self, n_valid: np.ndarray, ok: np.ndarray,
                              now: float) -> None:
        """Handle rows whose logits went non-finite this step: the
        garbage token is never committed; the row is replayed once via
        the recompute eviction path, then failed. Other rows in the
        fused batch are untouched."""
        for row in [r for r in list(self.sched.slots)
                    if n_valid[r] and not ok[r]]:
            req = self.sched.slots[row].req
            if req.n_nan_retries < self.ecfg.max_nan_retries:
                req.n_nan_retries += 1
                self.sched.evict(row)
            else:
                self.sched.fail(row, now=now, error=(
                    f"non-finite logits at step {self.n_steps} "
                    f"(after {req.n_nan_retries} replay(s))"))

    # -- serving loop ------------------------------------------------------

    def _finalize_unfinished(self, status: str, error: str,
                             now: float) -> None:
        """Graceful shutdown: everything still live gets ``status``
        with partial ``out`` — nothing is discarded, nothing raises."""
        for row in list(self.sched.slots):
            req = self.sched._release(row)
            self.sched._finalize(req, status, error=error, now=now)
        for q in (self.sched.waiting, self.sched.pending):
            while q:
                self.sched._finalize(q.pop(0), status, error=error,
                                     now=now)

    def _idle(self, now: float, clock: str,
              faults: Optional[FaultPlan], fired: set,
              idle_guard: int) -> bool:
        """An iteration with nothing to run: wait for the next arrival
        (or heal a stall). Returns True when the run is over."""
        if not self.sched.has_work():
            return True                  # expiry drained the trace
        nxt = self.sched.next_arrival()
        heal = (faults is not None
                and faults.has_restore_after(self.n_steps))
        if (heal and clock == "wall" and nxt is None
                and not self.sched.slots):
            # dead idle on the wall clock never advances n_steps, so a
            # step-indexed restore would never fire — fast-forward it
            # instead of sleeping on it
            for i, ev in enumerate(faults.events):
                if ev.kind == "pool_restore" and i not in fired:
                    fired.add(i)
                    self.sched.alloc.release(
                        ev.n_blocks if ev.n_blocks else None)
            return False
        if (nxt is None and not self.sched.slots
                and self.sched.waiting and not heal):
            # permanent stall: nothing runs, nothing arrives, no
            # scheduled restore — fail the blocked head with the block
            # accounting, keep serving the rest
            diag = self.sched.diagnose_stall() or (
                "admission stalled with free blocks")
            self.sched._finalize(self.sched.waiting.pop(0), "failed",
                                 error=diag, now=now)
            return False
        if idle_guard > IDLE_LIMIT:
            diag = self.sched.diagnose_stall()
            self._finalize_unfinished(
                "failed", f"idle-loop livelock after {IDLE_LIMIT} "
                f"iterations" + (f": {diag}" if diag else ""), now)
            return True
        if clock == "steps":
            self.n_steps += 1
        else:
            time.sleep(min(1e-3, max(nxt - now, 0.0) if nxt else 1e-3))
        return False

    def run(self, requests: Sequence[Request], clock: str = "steps",
            max_steps: Optional[int] = None,
            faults: Optional[FaultPlan] = None) -> List[Request]:
        """Serve an open-loop trace to completion. Returns the requests
        (same objects) with ``status``/``out``/``ttft``/``token_times``
        /``finish`` populated — plus any burst requests ``faults``
        injected — and never raises on a valid trace: failures are
        statuses, not exceptions. Arrival order need not be sorted."""
        if clock not in ("steps", "wall"):
            raise ValueError(clock)
        for req in requests:
            self.sched.submit(req)       # unservable -> status rejected
        injected: List[Request] = []
        fired: set = set()
        t0 = self.obs.clock0_ns = time.perf_counter_ns()
        idle_guard = 0
        while self.sched.has_work():
            t = time.perf_counter_ns()
            now = (float(self.n_steps) if clock == "steps"
                   else (t - t0) / 1e9)
            self.obs.step = self.n_steps
            with self.obs.span("engine.iteration", start=t):
                self._fire_faults(faults, fired, now, injected)
                with self.obs.span("sched.expire"):
                    self.sched.expire(now)
                with self.obs.span("sched.admit"):
                    self.sched.admit(now)
                with self.obs.span("sched.plan"):
                    plan = self.sched.plan_step()
                if plan is None:
                    self.obs.drop()          # an idle pass is no step
                    idle_guard += 1
                    if self._idle(now, clock, faults, fired, idle_guard):
                        break
                    continue
                idle_guard = 0
                tokens, n_valid, _ = plan
                force_nan = np.zeros((self.sched.n_slots,), bool)
                if faults is not None:
                    for row in faults.nan_rows(self.n_steps):
                        force_nan[row] = True
                last, ok = self._run_step(tokens, n_valid, force_nan)
                self.n_steps += 1
                emit_t = (float(self.n_steps) if clock == "steps"
                          else (time.perf_counter_ns() - t0) / 1e9)
                with self.obs.span("engine.commit"):
                    self._quarantine_nonfinite(n_valid, ok, emit_t)
                    self.sched.commit_step(n_valid, last, emit_t)
                if max_steps is not None and self.n_steps >= max_steps:
                    self._finalize_unfinished(
                        "timeout", f"max_steps={max_steps} exhausted",
                        emit_t)
                    break
        # faults are scoped to the run: any still-reserved blocks come
        # back so the pool-leak invariant (n_free == n_blocks once all
        # streams are terminal) holds at trace end
        self.sched.alloc.release()
        return list(requests) + injected


# ----------------------------------------------------------------------
# Metrics
# ----------------------------------------------------------------------

def summarize(requests: Sequence[Request], wall_s: float) -> dict:
    """Aggregate serving metrics over a completed trace: TTFT and
    inter-token latency percentiles (units = the run's clock),
    aggregate generated tokens/s, per-status counts, and goodput —
    tokens/s counting only tokens of requests that FINISHED (partial
    output of timed-out/failed streams is waste, not goods)."""
    ttfts = [r.ttft for r in requests if r.ttft is not None]
    inter: List[float] = []
    for r in requests:
        ts = r.token_times
        inter.extend(b - a for a, b in zip(ts, ts[1:]))
    n_tok = sum(r.n_generated for r in requests)
    n_good = sum(r.n_generated for r in requests
                 if r.status == "finished")

    def pct(xs, q):
        return float(np.percentile(np.asarray(xs), q)) if xs else 0.0

    return {
        "n_requests": len(requests),
        "n_tokens_out": n_tok,
        "wall_s": wall_s,
        "tokens_per_s": n_tok / wall_s if wall_s > 0 else 0.0,
        "goodput_tokens_per_s": n_good / wall_s if wall_s > 0 else 0.0,
        "statuses": dict(Counter(r.status for r in requests)),
        "ttft": {"p50": pct(ttfts, 50), "p95": pct(ttfts, 95),
                 "p99": pct(ttfts, 99)},
        "per_token_latency": {"p50": pct(inter, 50), "p95": pct(inter, 95),
                              "p99": pct(inter, 99)},
        "n_evictions": sum(r.n_evictions for r in requests),
    }
