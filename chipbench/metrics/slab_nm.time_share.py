"""slab_nm.time_share: percent of the device's busy time in the traced
window spent in the packed SLaB N:M kernel (``slab_nm_matmul``). Layer:
kernels/slab_matmul. Moves itl_p50_ms."""
from chipbench import trace

# The kernel's op in the trace: the custom call named after the program's
# jitted ``ops.slab_nm_matmul`` wrapper.
KERNEL = r"^%slab_nm_matmul(\.\d+)? = .* custom-call\("


def read(run):
    if run.trace is None:
        return None
    k = trace.kernel_ns(run.trace, KERNEL)
    busy = trace.busy_ns(run.trace)
    if k <= 0 or busy <= 0:
        return None
    return 100.0 * k / busy
