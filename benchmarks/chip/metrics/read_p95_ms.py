"""p95 over all reads due in the window, from the due time of the
open-loop schedule to the result on the host; a read that failed or
never returned counts as waiting until the drain gave up."""
from benchmarks.chip.harness import percentile


def read(run):
    p = percentile([run.read_latency(rd) for rd in run.reads], 95)
    return None if p is None else 1e3 * p
