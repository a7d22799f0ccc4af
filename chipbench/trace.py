"""Reduction of a profiler trace to device busy and idle time, per-kernel
time, and idle gaps attributed to what the host was doing.

``read_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into a
``Trace``: the device operations of one chip (the ``XLA Ops`` line of
its ``/device:TPU:<n>`` plane) and the benchmark's own host spans, all
in nanoseconds on the profile's one clock. Everything after that works
on plain tuples, so a small hand-built or recorded ``Trace`` (JSON)
checks the arithmetic without a chip.

The ``XLA Ops`` line nests: a ``while`` op spans the ops of its body.
Busy time is the union of all intervals; an op's self time is its
duration less that of the ops nested in it.
"""
from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    device_ops: List[Event]
    host_spans: List[Event]
    window: Tuple[float, float]           # traced window, ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls([tuple(e) for e in d["device_ops"]],
                   [tuple(e) for e in d["host_spans"]],
                   tuple(d["window"]))


def read_xplane(path: Path, span_names: Iterable[str],
                window_span: str, device: int = 0) -> Trace:
    """Device ops of chip ``device`` and the host spans named in
    ``span_names``; the window is the host span ``window_span``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    wanted = set(span_names) | {window_span}
    ops: List[Event] = []
    spans: List[Event] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == device:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, float(e.start_ns),
                                float(e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns),
                              float(e.duration_ns))
                             for e in line.events if e.name in wanted)
    win = [s for s in spans if s[0] == window_span]
    if not win:
        raise ValueError(f"trace has no {window_span!r} span")
    w = max(win, key=lambda s: s[2])
    return Trace(ops, [s for s in spans if s[0] != window_span],
                 (w[1], w[1] + w[2]))


def short_name(op: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion'."""
    head = op.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _clip(events: Iterable[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return sorted(out)


def busy_intervals(events: Iterable[Event], lo: float, hi: float
                   ) -> List[Tuple[float, float]]:
    """The union of the events' intervals inside [lo, hi), merged."""
    merged: List[List[float]] = []
    for a, b in _clip(events, lo, hi):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(tr: Trace) -> float:
    return sum(b - a for a, b in busy_intervals(tr.device_ops, *tr.window))


def idle_gaps(tr: Trace) -> List[Tuple[float, float]]:
    lo, hi = tr.window
    gaps, t = [], lo
    for a, b in busy_intervals(tr.device_ops, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Self time (ns) by short op name: each op's duration less that of
    the ops that start and end inside it."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List] = []               # [name, end, child_ns]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1]:
            n, _, child, dur = stack.pop()
            out[n] += dur - child
        if stack:
            stack[-1][2] += d
        stack.append([short_name(name), s + d, 0.0, d])
    while stack:
        n, _, child, dur = stack.pop()
        out[n] += dur - child
    return dict(out)


def kernel_ns(tr: Trace, pattern: str) -> float:
    """Device time of the ops whose full name matches ``pattern``
    (clipped to the window)."""
    rx = re.compile(pattern)
    return sum(b - a for a, b in _clip(
        (e for e in tr.device_ops if rx.search(e[0])), *tr.window))


def host_activity(tr: Trace, a: float, b: float) -> str:
    """The host span overlapping [a, b) the most; 'engine' (the
    engine's own loop, outside the benchmark's spans) when none does."""
    best, name = 0.0, "engine"
    for n, s, d in tr.host_spans:
        ov = min(b, s + d) - max(a, s)
        if ov > best:
            best, name = ov, n
    return name


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device ops that took most (self) time, and the idle time
    summed by what the host was doing, each as [name, seconds]."""
    lo, hi = tr.window
    inside = [e for e in tr.device_ops if e[1] < hi and e[1] + e[2] > lo]
    ops = sorted(self_times(inside).items(), key=lambda kv: -kv[1])[:top]
    idle: Dict[str, float] = defaultdict(float)
    for a, b in idle_gaps(tr):
        idle[host_activity(tr, a, b)] += b - a
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, v / 1e9] for n, v in ops],
            "idle_gaps": [[n, v / 1e9] for n, v in gaps]}


def find_xplane(root: Path) -> Optional[Path]:
    found = sorted(Path(root).rglob("*.xplane.pb"))
    return found[-1] if found else None
