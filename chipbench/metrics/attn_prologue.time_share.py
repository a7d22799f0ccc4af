"""attn_prologue.time_share: percent of the device's busy time in the
traced window spent in ops whose innermost program scope is the
attention prologue between the projections and the cache: the per-head
q/k LayerNorm (``attn/qk_norm``) and the rotary (``attn/rope``). Scopes
from the map ``Engine.compile()`` keeps, self time, read as
``layer_scan.copy_share`` reads its scope. A program without those
scopes gives nothing. Layer: models/attention. Moves itl_p50_ms."""
from chipbench import trace
from chipbench.metrics import _program

SCOPES = ("attn/qk_norm", "attn/rope")


def read(run):
    rec = _program.recorder()
    if run.trace is None or rec is None \
            or not set(SCOPES) & set(rec.scopes.values()):
        return None
    busy = trace.busy_ns(run.trace)
    if busy <= 0:
        return None
    ns = sum(v for op, v in _program.op_self_times(run.trace).items()
             if rec.scopes.get(op) in SCOPES)
    return 100.0 * ns / busy
