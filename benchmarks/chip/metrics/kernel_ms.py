"""Device time of the Pallas kernels (``kernels/ring_fused.py``,
``kernels/ring_scatter.py``) per stream-program execution, matched by
kernel name in the trace."""
from benchmarks.chip.metrics.segment_device_ms import stream_ops

#: the kernels' function names, as ``pallas_call`` names them, and the
#: custom-call target every Mosaic kernel lowers to
KERNELS = ("_fused_kernel", "_scatter_kernel", "_scatter_dedup_kernel",
           "_gms_kernel", "tpu_custom_call")


def is_kernel(op) -> bool:
    return any(k in op.name or k in op.text for k in KERNELS)


def read(run):
    tr = run.trace
    if not tr:
        return None
    ops, runs = stream_ops(tr)
    kern = [op for op in ops if is_kernel(op)]
    if not runs or not kern:
        return None
    return 1e3 * sum(op.end - op.start for op in kern) / runs
