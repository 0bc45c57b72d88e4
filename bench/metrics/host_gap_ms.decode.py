"""host_gap_ms.decode: mean time from the end of one engine step's
``engine.sync`` to the start of the next step's first span that hands the
device work (``engine.admit``, ``engine.chunk``, ``engine.reserve`` or
``engine.dispatch``): the serial host path during which the device has
nothing queued. From the program's own spans, over the window's untraced
steps."""
from bench import program_spans


def read(run):
    steps = program_spans.window_steps(run)
    if not steps:
        return None
    gaps = []
    for a, b in program_spans.consecutive(steps):
        end, start = a.last_end("engine.sync"), b.first_start(
            program_spans.DISPATCHING)
        if end is not None and start is not None:
            gaps.append(start - end)
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
