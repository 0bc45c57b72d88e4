"""engine_step_ms.decode: mean host time of one engine.step (the
benchmark's own span around it) over every step inside the window: the
spans' total over their number."""


def read(run):
    spans = [s.t1 - s.t0 for s in run.steps
             if run.start <= s.t0 and s.t1 <= run.end]
    return 1e3 * sum(spans) / len(spans) if spans else None
