"""step_mfu: the whole step's share of the chip's bf16 peak over the
traced window, in percent: the operations the model needs for the
tokens processed in it (2 per dense-equivalent weight of every
linear and of the head per token, plus attention's 4 * ctx * H * dh per
layer; the same count whatever format holds the weights) over the
window's length times the peak. Layer: models/lm step. Moves
itl_p50_ms."""
from chipbench import work


def read(run):
    if not run.plans or run.window_s <= 0 or not run.peaks:
        return None
    tokens = sum(p.tokens for p in run.plans)
    ctx = sum(p.ctx for p in run.plans)
    flops = work.model_flops(tokens, ctx, run.cfg)
    return 100.0 * flops / (run.window_s * run.peaks["bf16_flops_per_s"])
