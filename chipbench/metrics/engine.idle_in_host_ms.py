"""engine.idle_in_host_ms: mean milliseconds per traced step in which
the chip sat idle while the host was in one of the program's own spans
other than ``engine.device_wait`` (the gaps the host leaves between
steps: commit, scheduling, table uploads, dispatch). The program's
spans are put on the trace's clock through the benchmark's ``step``
spans (``_program.clock_offset``); no reading when their offsets spread
over 100 us. Layer: serving/engine. Moves itl_p50_ms."""
from chipbench.metrics import _program


def read(run):
    got = _program.idle_by_span(_program.recorder(), run)
    if got is None or got[1] == 0:
        return None
    idle, n_steps, _ = got
    ns = sum(v for k, v in idle.items() if k != _program.WAIT)
    return ns / n_steps / 1e6
