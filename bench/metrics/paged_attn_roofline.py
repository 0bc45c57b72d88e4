"""paged_attn_roofline: the least time of every traced call of the paged
decode-attention kernel inside the decode-step programs (the larger of its
operations over peak and its bytes over HBM bandwidth, counted from the
live contexts the benchmark recorded at each step), over the kernel's
device time in the trace, in %."""
from bench import flops


def read(run):
    t = run.trace
    if t is None:
        return None
    kernel_s = t.decode_kernel_s()
    if kernel_s <= 0:
        return None
    layers = run.arch["num_hidden_layers"]
    least = 0.0
    for s in run.steps:
        if s.traced and s.decode_ctx:
            f, b = flops.decode_attn_call(run.arch, s.decode_ctx)
            least += layers * flops.roofline_s(f, b, run.peaks)
    return 100.0 * least / kernel_s
