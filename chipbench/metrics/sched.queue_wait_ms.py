"""sched.queue_wait_ms: median milliseconds from arrival to first
admission into a slot, over the requests due in the measured window
that were admitted. Read from the program's request log
(``Request.admitted``, stamped by ``Scheduler.admit``; a replay after an
eviction keeps the first stamp). Layer: serving/scheduler. Moves
ttft_p50_ms."""
import statistics

from chipbench.metrics import _program


def read(run):
    reqs = _program.window_requests(_program.recorder(), run)
    waits = [(r["admitted"] - r["arrival"]) * 1e3 for r in reqs
             if r["admitted"] is not None]
    return statistics.median(waits) if waits else None
