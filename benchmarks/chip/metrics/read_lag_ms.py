"""p95 of how late the reader dispatched against its schedule."""
from benchmarks.chip.harness import percentile


def read(run):
    lag = [rd.dispatched - (run.t0 + rd.due) for rd in run.reads
           if rd.dispatched is not None]
    p = percentile(lag, 95)
    return None if p is None else 1e3 * p
