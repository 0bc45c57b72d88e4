"""The program's own host spans over a run's window, for the readers of
the ``program_span`` and ``program_counter`` metrics.

``window_steps(run)`` gives the ``engine.step`` records of
``repro.serving.tracing`` that began inside the window, leaving out those
inside a step the benchmark traced (so the numbers are those of a host
without the profiler), or None: where the program has no span recorder,
or where its ring has overwritten records of the window."""
from typing import Iterator, List, Optional, Tuple

# the spans that hand the device work: a step's first one ends the host's
# serial path since the last sync
DISPATCHING = ("engine.admit", "engine.chunk", "engine.reserve",
               "engine.dispatch")


def window_steps(run) -> Optional[List]:
    try:
        from repro.serving import tracing
    except ImportError:
        return None
    steps = tracing.steps(run.start, run.end)
    if steps is None:
        return None
    traced = [(s.t0, s.t1) for s in run.steps if s.traced]
    return [s for s in steps
            if not any(a <= s.t0 <= b for a, b in traced)]


def consecutive(steps) -> Iterator[Tuple[object, object]]:
    """Pairs of steps that followed each other in the program."""
    for a, b in zip(steps, steps[1:]):
        if b.step == a.step + 1:
            yield a, b
