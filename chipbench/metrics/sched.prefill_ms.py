"""sched.prefill_ms: median milliseconds from first admission to first
token, over the requests due in the measured window that got a first
token (one cut at the horizon before its first token is left out). Read
from the program's request log (``Request.admitted``,
``Request.first_token``, stamped by ``Scheduler.commit_step``). With
``sched.queue_wait_ms`` it splits a request's TTFT. Layer:
serving/scheduler. Moves ttft_p50_ms."""
import statistics

from chipbench.metrics import _program


def read(run):
    reqs = _program.window_requests(_program.recorder(), run)
    ms = [(r["first_token"] - r["admitted"]) * 1e3 for r in reqs
          if r["admitted"] is not None and r["first_token"] is not None]
    return statistics.median(ms) if ms else None
