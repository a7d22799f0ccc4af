"""The serving engine's own record of where its time goes.

Each ``Engine`` owns one ``Recorder`` (``engine.obs``); ``latest()``
returns the newest engine's. It is always on and bounded:

  spans     ``(name, start_ns, end_ns, step, parent)`` in
            ``time.perf_counter_ns()``, the engine step index at the
            iteration's start, and the enclosing span's name. Spans are
            kept one iteration at a time: an iteration that ran no step
            (``drop``) leaves nothing. Each span also enters
            ``jax.profiler.TraceAnnotation(name)``, so in a profile the
            spans sit on the device trace's clock.
  requests  one entry per request that reached a terminal status: rid,
            arrival, first admission, first token, finish (engine
            clock), status, evictions.
  counters  plain integers by name.
  scopes    for each step program ``Engine.compile()`` built: HLO
            instruction name -> innermost program scope (``scope_of``);
            ``None`` for an op outside every scope, or one to which two
            programs give different scopes.
"""
from __future__ import annotations

import collections
import contextlib
import re
import time
from typing import Collection, Dict, Iterator, List, Optional

import jax

# An HLO instruction, and the op_name of its metadata. XLA's own ops,
# such as the copies it inserts to change a buffer's layout, have none:
# one that only moves its operand takes the operand's scope.
_HLO_OP = re.compile(r'^\s*(?:ROOT )?%([\w.-]+) = ')
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_MOVE = re.compile(
    r'\S+ (?:copy|copy-start|copy-done|bitcast|get-tuple-element)'
    r'\(%([\w.-]+)')

_latest: Optional["Recorder"] = None


class Recorder:
    """Spans, per-request stamps, counters and scope maps of one engine."""

    def __init__(self, max_spans: int = 1 << 16,
                 max_requests: int = 1 << 14):
        self.spans: collections.deque = collections.deque(maxlen=max_spans)
        self.requests: collections.deque = collections.deque(
            maxlen=max_requests)
        self.counters: Dict[str, int] = collections.Counter()
        self.scopes: Dict[str, Optional[str]] = {}
        self.clock0_ns = 0      # perf_counter_ns at the engine clock's 0
        self.step = 0           # engine step index the next spans carry
        self._open: List[str] = []
        self._closed: List[tuple] = []
        self._drop = False

    # -- spans -------------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str, start: Optional[int] = None) -> Iterator:
        """Time the block as span ``name`` (from ``start`` when given, a
        ``perf_counter_ns`` reading the caller already took)."""
        parent = self._open[-1] if self._open else None
        step = self.step
        self._open.append(name)
        ann = jax.profiler.TraceAnnotation(name)
        ann.__enter__()
        t0 = time.perf_counter_ns() if start is None else start
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            ann.__exit__(None, None, None)
            self._open.pop()
            self._closed.append((name, t0, t1, step, parent))
            if not self._open:
                if not self._drop:
                    self.spans.extend(self._closed)
                self._closed.clear()
                self._drop = False

    def drop(self) -> None:
        """Keep none of the spans of the outermost open span."""
        self._drop = True

    # -- requests and counters --------------------------------------------

    def request_done(self, req) -> None:
        self.requests.append({
            "rid": req.rid, "arrival": req.arrival,
            "admitted": req.admitted, "first_token": req.first_token,
            "finish": req.finish, "status": req.status,
            "evictions": req.n_evictions})

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] += n

    # -- scopes ------------------------------------------------------------

    def add_scopes(self, hlo_text: str, names: Collection[str]) -> None:
        """Map each instruction of one compiled program (its
        ``as_text()``) to its innermost scope; a name another program
        mapped elsewhere maps to nothing."""
        local: Dict[str, Optional[str]] = {}
        for line in hlo_text.splitlines():
            m = _HLO_OP.match(line)
            if not m:
                continue
            md = _OP_NAME.search(line, m.end())
            if md:
                local[m.group(1)] = scope_of(md.group(1), names)
            else:
                mv = _MOVE.match(line, m.end())
                local[m.group(1)] = local.get(mv.group(1)) if mv else None
        for op, scope in local.items():
            if op in self.scopes and self.scopes[op] != scope:
                scope = None
            self.scopes[op] = scope


def for_engine() -> Recorder:
    """A new recorder, from now on the one ``latest()`` returns."""
    global _latest
    _latest = Recorder()
    return _latest


def latest() -> Optional[Recorder]:
    """The recorder of the newest ``Engine`` in the process (kept after
    the engine itself is gone)."""
    return _latest


def scope_of(op_name: str, names: Collection[str]) -> Optional[str]:
    """The innermost run of program scopes in an op's name, outermost
    first: 'jit(step)/.../layer_scan/while/body/closed_call/attn/wq/
    jit(slab_nm_matmul)/pallas_call' -> 'attn/wq'. ``names`` are the
    scopes the program entered (``models.common.SCOPE_NAMES``); every
    other component (loops, calls, the primitive) is JAX's. Of an op
    XLA merged from several (names joined by ';'), the first counts."""
    parts = op_name.split(";")[0].split("/")[:-1]
    hi = len(parts)
    while hi and parts[hi - 1] not in names:
        hi -= 1
    lo = hi
    while lo and parts[lo - 1] in names:
        lo -= 1
    return "/".join(parts[lo:hi]) or None
