"""The work a segment of the stream needs, and the least time it takes.

The count follows the trigger plans' shapes: every delta hop (leaf
delta, sibling gather, lift, join) reads its [B, d] plane once, and
every ⊎ read-modify-writes B rows (3 B d).  Each ring product (gather,
join, lift) costs the ring's product per row, and each ⊕ (marginalize,
⊎) one add per payload entry per row.  A fused chain counts as the ops
it fuses.  The count is a floor: what a perfect maintenance program
would still move and compute, whatever implements the plan.

The least time is the larger of bytes over the chip's HBM bandwidth
and operations over its peak, from ``peaks.json`` keyed by the device's
``device_kind``.  A device that is not in the table is an error.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")

_HOPS = ("LeafDelta", "Gather", "Lift", "JoinContract")
_PRODUCTS = ("Gather", "Lift", "JoinContract")
_SUMS = ("Marginalize", "ScatterAccum")


def peaks(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to "
                       f"{os.path.basename(PEAKS)} with its source")
    return table[device_kind]


def _flat(ops):
    for op in ops:
        if type(op).__name__ == "FusedChain":
            yield from _flat(op.ops)
        else:
            yield op


def plan_work(plan, width: int, mul_flops: int) -> tuple[int, int]:
    """``(bytes, flops)`` one batch of ``plan`` needs, for a ring whose
    payload holds ``width`` f32 entries and whose product costs
    ``mul_flops`` operations."""
    b = int(plan.batch)
    row = 4 * width
    nbytes = flops = 0
    for op in _flat(tuple(plan.ops) + tuple(plan.ind_ops)):
        kind = type(op).__name__
        if kind in _HOPS:
            nbytes += b * row
        if kind == "ScatterAccum":
            nbytes += 3 * b * row
        if kind in _PRODUCTS:
            flops += b * mul_flops
        if kind in _SUMS:
            flops += b * width
    return nbytes, flops


def segment_work(plans: dict, order: list, width: int,
                 mul_flops: int) -> tuple[int, int]:
    """Work of one segment: the batches of relations ``order`` in turn,
    each through its plan in ``plans``."""
    total_b = total_f = 0
    for rel in order:
        b, f = plan_work(plans[rel], width, mul_flops)
        total_b += b
        total_f += f
    return total_b, total_f


def least_seconds(nbytes: int, flops: int, peak: dict) -> tuple[float, str]:
    """The least time the work takes on the chip, and what bounds it."""
    t_mem = nbytes / float(peak["hbm_bytes_per_s"])
    t_ops = flops / float(peak["flops_per_s"])
    return (t_mem, "bandwidth") if t_mem >= t_ops else (t_ops, "compute")
