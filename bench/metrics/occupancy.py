"""occupancy: useful tokens over the token-slots the scheduler planned, over
the engine steps inside the window, in %. Per step, the planned slots and
the useful prompt tokens are the change in the engine's own counters
(``planned_token_slots``, ``useful_prefill_tokens``), and the useful decode
tokens are those the step streamed (the same definition as
``ServingEngine.occupancy()``, taken over the window alone)."""


def read(run):
    steps = [s for s in run.steps if run.start <= s.t0 and s.t1 <= run.end]
    planned = sum(s.planned for s in steps)
    useful = sum(s.useful for s in steps)
    return 100.0 * useful / planned if planned else None
