"""layer_scan.copy_share: percent of the device's busy time in the
traced window spent in ops whose innermost program scope is the layer
scan itself (``layer_scan``), not a part inside a layer: slicing each
layer's weights and pool out of the stacked arrays, stacking the new
pool, and their copies. Scopes from the map ``Engine.compile()`` keeps
(HLO instruction -> innermost ``jax.named_scope``); self time, so the
scan's ``while`` does not count its body. Layer: models/lm step. Moves
itl_p50_ms."""
from chipbench import trace
from chipbench.metrics import _program

SCOPE = "layer_scan"


def read(run):
    rec = _program.recorder()
    if run.trace is None or rec is None or not rec.scopes:
        return None
    busy = trace.busy_ns(run.trace)
    if busy <= 0:
        return None
    ns = sum(v for op, v in _program.op_self_times(run.trace).items()
             if rec.scopes.get(op) == SCOPE)
    return 100.0 * ns / busy
