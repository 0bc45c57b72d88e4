"""gateway_ms.decode: mean time between consecutive ``engine.step`` spans
of the program (the gateway's loop between steps plus the executor hop),
over the window's untraced steps."""
from bench import program_spans


def read(run):
    steps = program_spans.window_steps(run)
    if not steps:
        return None
    gaps = [b.t0 - a.t1 for a, b in program_spans.consecutive(steps)]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
