"""Programs traced inside the timed window (each a jit cache miss).
It should read 0."""


def read(run):
    return run.window_compiles
