"""The least time a segment's necessary work takes on the chip, over
the stream program's device time per segment.  The work comes from the
trigger plans' shapes (``work.py``); the least time is the larger of
bytes over HBM bandwidth and operations over peak (``peaks.json``)."""
from benchmarks.chip import work
from benchmarks.chip.metrics import segment_device_ms


def read(run):
    seg_ms = segment_device_ms.read(run)
    if not seg_ms:
        return None
    least, _ = work.least_seconds(run.segment_bytes, run.segment_flops,
                                  work.peaks(run.device_kind))
    return 100.0 * least / (seg_ms * 1e-3)
