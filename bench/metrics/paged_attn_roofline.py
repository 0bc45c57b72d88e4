"""paged_attn_roofline: the least time of every traced call of the paged
decode-attention kernel inside the decode-step programs (the larger of its
operations over peak and its bytes over HBM bandwidth), over the kernel's
device time in the trace, in %. The configuration's family counts a decode
step's operations and bytes over the layers that call the kernel
(``paged_attn_step``), from the live contexts the benchmark recorded at
each step; a family without it reads nothing."""
from bench import flops


def read(run):
    step = getattr(run.family, "paged_attn_step", None)
    t = run.trace
    if step is None or t is None:
        return None
    kernel_s = t.decode_kernel_s()
    if kernel_s <= 0:
        return None
    least = 0.0
    for s in run.steps:
        if s.traced and s.decode_ctx:
            f, b = step(run.arch, s.decode_ctx)
            least += flops.roofline_s(f, b, run.peaks)
    return 100.0 * least / kernel_s
