"""mfu: model operations of the window's prompt and output tokens over the
window's seconds times the chip's peak bf16 rate, in %. A token costs two
operations per matmul weight (the tied head included) plus attention over
its live context (bench/flops.py). Prompts count when their first token
was streamed inside the window."""
from bench import flops


def read(run):
    arch = run.arch
    total = 0.0
    for s in run.steps:
        if run.start <= s.t1 < run.end:
            total += sum(flops.token_flops(arch, c) for c in s.decode_ctx)
    for r in run.requests:
        if r.first is not None and run.start <= r.first < run.end:
            total += flops.prompt_flops(arch, r.prompt_len)
    peak = run.peaks["bf16_flops_per_s"]
    return 100.0 * total / (run.seconds * peak) if total else None
