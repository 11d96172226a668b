"""Seconds of JAX compile events (trace, lowering, backend compile)
during set-up."""


def read(run):
    return run.setup["compile_s"]
