"""What the per-layer metrics read of the program's own record: the
recorder (``repro.serving.obs``) of the run's engine, the steps it
traced and the requests it logged. A program without that record gives
nothing, and neither does a reader that needs it.

The traced steps are the engine iterations whose start on the engine's
clock lies within the first and last plan the benchmark recorded
(``run.plans[*].t``): the engine reads its clock at the instant its
``engine.iteration`` span starts, so the two agree exactly.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from chipbench import trace

# The benchmark's own host span around each step's dispatch and wait,
# and the program span it starts beside.
STEP_MARK = "step"
STEP_START = "engine.h2d"
WAIT = "engine.device_wait"
ITERATION = "engine.iteration"
# Above this quartile spread of the per-step offsets (ns) the two clocks
# are not aligned well enough to put idle time down to program spans.
MAX_SPREAD_NS = 100e3


def recorder():
    """The newest engine's recorder, or None (a program without one)."""
    try:
        from repro.serving import obs
    except ImportError:
        return None
    return obs.latest()


def window_requests(rec, run) -> List[dict]:
    """Logged requests due in the measured window (arrival at or after
    the mix's lead-in)."""
    if rec is None:
        return []
    lead = float(run.mix["lead_in_s"])
    return [r for r in rec.requests if r["arrival"] >= lead]


def traced_steps(rec, run) -> List[Dict[str, tuple]]:
    """Each traced step's spans by name, in order."""
    if rec is None or not run.plans:
        return []
    lo, hi = run.plans[0].t, run.plans[-1].t
    by_step: Dict[int, Dict[str, tuple]] = defaultdict(dict)
    for sp in rec.spans:
        by_step[sp[3]][sp[0]] = sp
    out = []
    for _, spans in sorted(by_step.items()):
        it = spans.get(ITERATION)
        if it is None or WAIT not in spans:
            continue
        if lo <= (it[1] - rec.clock0_ns) / 1e9 <= hi:
            out.append(spans)
    return out


def clock_offset(steps, tr) -> Optional[Tuple[float, float]]:
    """(offset, quartile spread) in ns from the program's clock to the
    trace's: each benchmark ``step`` span starts beside the program's
    ``engine.h2d`` of the same step, so the offset is the median of
    their start differences. None when the two do not pair up."""
    marks = sorted(s for n, s, _ in tr.host_spans if n == STEP_MARK)
    if not steps or len(marks) != len(steps):
        return None
    diffs = [m - st[STEP_START][1] for m, st in zip(marks, steps)]
    if len(diffs) < 2:
        return float(diffs[0]), 0.0
    q1, q2, q3 = statistics.quantiles(diffs, n=4)
    return q2, q3 - q1


def idle_by_span(rec, run) -> Optional[Tuple[Dict[str, float], int, float]]:
    """(device-idle ns by program span, traced steps, offset spread ns).
    Each idle gap of the traced window is split over the spans of the
    traced steps it overlaps: a child span takes its overlap, the
    iteration what none of its children covers. None when the clocks
    cannot be aligned."""
    if run.trace is None:
        return None
    steps = traced_steps(rec, run)
    off = clock_offset(steps, run.trace)
    if off is None or off[1] > MAX_SPREAD_NS:
        return None
    offset = off[0]
    gaps = trace.idle_gaps(run.trace)
    starts = [a for a, _ in gaps]
    idle: Dict[str, float] = defaultdict(float)
    for spans in steps:
        it = spans[ITERATION]
        lo, hi = it[1] + offset, it[2] + offset
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        for a, b in gaps[i:]:
            if a >= hi:
                break
            rest = min(b, hi) - max(a, lo)
            if rest <= 0:
                continue
            for name, s, e, _, _ in spans.values():
                if name == ITERATION:
                    continue
                ov = min(b, e + offset) - max(a, s + offset)
                if ov > 0:
                    idle[name] += ov
                    rest -= ov
            idle[ITERATION] += max(rest, 0.0)
    return dict(idle), len(steps), off[1]


def op_self_times(tr) -> Dict[str, float]:
    """Self time (ns) in the traced window by HLO instruction name
    ('copy.12'): each op clipped to the window, less the ops nested in
    it (a ``while`` holds its body's ops)."""
    lo, hi = tr.window
    evs = []
    for name, s, d in tr.device_ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            evs.append((name.split(" = ", 1)[0].lstrip("%"), a, b))
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []               # [op, end, child_ns, dur]
    for op, a, b in sorted(evs, key=lambda e: (e[1], -e[2])):
        while stack and a >= stack[-1][1]:
            n, _, child, dur = stack.pop()
            out[n] += dur - child
        if stack:
            stack[-1][2] += b - a
        stack.append([op, b, 0.0, b - a])
    while stack:
        n, _, child, dur = stack.pop()
        out[n] += dur - child
    return dict(out)
