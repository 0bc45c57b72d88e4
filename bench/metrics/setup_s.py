"""setup_s: process start to the first request of the window (weights,
engine, warm-up and, in a cold run, compilation)."""


def read(run):
    return run.setup_s
