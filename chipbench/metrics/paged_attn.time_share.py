"""paged_attn.time_share: percent of the device's busy time in the traced
window spent in the paged decode-attention kernel
(``flash_decode_paged``). Layer: kernels/flash_decode. Moves
itl_p50_ms."""
from chipbench import trace

# The kernel's op in the trace: a custom call whose first operand is the
# scalar-prefetched (rows, table width) int32 block table.
KERNEL = r"custom-call\(s32\[\d+,\d+\]"


def read(run):
    if run.trace is None:
        return None
    k = trace.kernel_ns(run.trace, KERNEL)
    busy = trace.busy_ns(run.trace)
    if k <= 0 or busy <= 0:
        return None
    return 100.0 * k / busy
