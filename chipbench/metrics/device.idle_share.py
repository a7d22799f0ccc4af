"""device.idle_share: percent of the traced window in which no operation
ran on the chip (1 - union of device-op intervals / window). Layer:
device. Moves itl_p50_ms."""
from chipbench import trace


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    busy = trace.busy_ns(run.trace) / 1e9
    return 100.0 * (1.0 - busy / run.trace.window_s)
