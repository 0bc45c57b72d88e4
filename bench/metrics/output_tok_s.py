"""output_tok_s: output tokens streamed to clients inside the window, over
the window's seconds."""


def read(run):
    n = sum(k for r in run.requests for t, k in r.deliveries
            if run.start <= t < run.end)
    return n / run.seconds
