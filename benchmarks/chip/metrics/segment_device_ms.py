"""Device time of the stream program per execution (one execution is
one published segment), from the trace."""
from benchmarks.chip.harness import STREAM_PROGRAM


def stream_ops(tr):
    """The operations of the stream program's executions that lie wholly
    in the traced window, and how many executions those are."""
    runs = [iv for m, ivs in tr["executions"].items()
            if STREAM_PROGRAM in m for iv in ivs]
    ops = [op for op in tr["ops"] if STREAM_PROGRAM in op.module
           and any(s <= op.start < e for s, e in runs)]
    return ops, len(runs)


def read(run):
    tr = run.trace
    if not tr:
        return None
    ops, runs = stream_ops(tr)
    if not runs or not ops:
        return None
    return 1e3 * sum(op.end - op.start for op in ops) / runs
