"""Median time from a read's dispatch to its result on the host."""
from benchmarks.chip.harness import percentile


def read(run):
    t = [rd.done - rd.dispatched for rd in run.reads if rd.done is not None]
    p = percentile(t, 50)
    return None if p is None else 1e3 * p
