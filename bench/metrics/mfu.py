"""mfu: model operations of the window's prompt and output tokens over the
window's seconds times the chip's peak bf16 rate, in %. The configuration's
family counts them (``token_flops`` for a token over its live context,
``prompt_flops`` for a whole prompt; for ``dense_gqa`` two operations per
matmul weight, the tied head included, plus attention). Prompts count when
their first token was streamed inside the window."""


def read(run):
    family, arch = run.family, run.arch
    total = 0.0
    for s in run.steps:
        if run.start <= s.t1 < run.end:
            total += sum(family.token_flops(arch, c) for c in s.decode_ctx)
    for r in run.requests:
        if r.first is not None and run.start <= r.first < run.end:
            total += family.prompt_flops(arch, r.prompt_len)
    peak = run.peaks["bf16_flops_per_s"]
    return 100.0 * total / (run.seconds * peak) if total else None
