"""plan_ms.decode: mean time of the program's ``engine.plan`` span (the
scheduler's plan with its admission and preemption callbacks) per engine
step, over the window's untraced steps."""
from bench import program_spans


def read(run):
    steps = program_spans.window_steps(run)
    if not steps:
        return None
    return 1e3 * sum(s.seconds("engine.plan") for s in steps) / len(steps)
