"""slab_nm_roofline: the packed SLaB N:M kernel's share of its roofline
in the traced window, in percent: the least time the chip could take for
the calls made (each call's larger of operations / peak and bytes / peak
bandwidth, from the configuration's format and shapes, at the engine's
rows) over the kernel's device time. Every position of every step in the traced window
calls each linear of each layer once. Layer: kernels/slab_matmul. Moves
itl_p50_ms."""
from chipbench import trace, work

KERNEL = r"^%slab_nm_matmul(\.\d+)? = .* custom-call\("


def read(run):
    fmt = run.cfg["format"]
    if run.trace is None or not run.peaks or fmt["kind"] != "slab_nm":
        return None
    k_ns = trace.kernel_ns(run.trace, KERNEL)
    if k_ns <= 0:
        return None
    n, m = (int(x) for x in fmt["pattern"].split(":"))
    per_position = 0.0
    for d_in, d_out in work.linear_shapes(run.cfg).values():
        flops, nbytes = work.slab_nm_call(d_in, d_out, run.rows, n, m,
                                          int(fmt["rank"]))
        per_position += work.least_time(flops, nbytes, run.peaks)[0]
    positions = sum(p.c for p in run.plans)
    least = positions * run.cfg["num_hidden_layers"] * per_position
    return 100.0 * least / (k_ns / 1e9)
