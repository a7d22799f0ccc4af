"""engine.host_ms_per_step: mean milliseconds per traced step that the
host spends outside its wait for the device: the program's
``engine.iteration`` span less its ``engine.device_wait`` child
(scheduling, table uploads, dispatch, commit). The first traced step is
left out: the benchmark starts its profiler inside that step's
``sched.admit`` (50-90 ms on the chip), which is none of the program's
work. Layer: serving/engine. Moves itl_p50_ms."""
from chipbench.metrics import _program


def read(run):
    steps = _program.traced_steps(_program.recorder(), run)[1:]
    if not steps:
        return None
    ns = [(s[_program.ITERATION][2] - s[_program.ITERATION][1])
          - (s[_program.WAIT][2] - s[_program.WAIT][1]) for s in steps]
    return sum(ns) / len(ns) / 1e6
