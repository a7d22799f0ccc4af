"""paged_attn_roofline: the paged decode-attention kernel's share of its
roofline in the traced window, in percent: the least time for reading
the valid cached K and V of every (row, position) of each step in the traced window in
every layer, and its queries and outputs (context lengths from the
scheduler at each plan), over the kernel's device time. Layer:
kernels/flash_decode. Moves itl_p50_ms."""
from chipbench import trace, work

KERNEL = r"custom-call\(s32\[\d+,\d+\]"


def read(run):
    if run.trace is None or not run.peaks:
        return None
    k_ns = trace.kernel_ns(run.trace, KERNEL)
    if k_ns <= 0:
        return None
    least = 0.0
    for p in run.plans:
        flops, nbytes = work.paged_attn_work(p.ctx, p.tokens, run.cfg)
        least += work.least_time(flops, nbytes, run.peaks)[0]
    least *= run.cfg["num_hidden_layers"]
    return 100.0 * least / (k_ns / 1e9)
