"""p95 over every batch made visible in the window: from the batch's
stamp to its generation found ready on the device by the watcher."""
from benchmarks.chip.harness import percentile


def read(run):
    lat = [b.visible - b.stamp for b in run.window_batches()]
    p = percentile(lat, 95)
    return None if p is None else 1e3 * p
