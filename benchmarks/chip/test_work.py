"""The necessary-work count and the peaks table, against a plan counted
by hand."""
from __future__ import annotations

import dataclasses

import pytest

from benchmarks.chip import work


# op stand-ins: the count reads an op's kind by its class name
@dataclasses.dataclass
class LeafDelta:
    pass


@dataclasses.dataclass
class Emit:
    pass


@dataclasses.dataclass
class Marginalize:
    pass


@dataclasses.dataclass
class ScatterAccum:
    pass


@dataclasses.dataclass
class Gather:
    pass


@dataclasses.dataclass
class Lift:
    pass


@dataclasses.dataclass
class FusedChain:
    ops: tuple


@dataclasses.dataclass
class Plan:
    batch: int
    ops: tuple
    ind_ops: tuple = ()


def shop_plan(batch: int) -> Plan:
    """Housing's Shop trigger: ⊎ the leaf view, then five sibling
    gathers, ⊕ over pc and ⊎ the root."""
    return Plan(batch, (LeafDelta(), Emit(), Marginalize(), Emit(),
                        ScatterAccum(), *[Gather() for _ in range(5)],
                        Marginalize(), Emit(), ScatterAccum()))


def test_scalar_plan_by_hand():
    # bytes: leaf 4*4 + ⊎ 3*4*4 + 5 gathers 5*4*4 + ⊎ 3*4*4 = 192
    # flops: ⊕ 4 + ⊎ 4 + 5 products 5*4 + ⊕ 4 + ⊎ 4 = 36
    assert work.plan_work(shop_plan(4), width=1, mul_flops=1) == (192, 36)


def test_fused_chain_counts_its_ops():
    flat = Plan(8, (LeafDelta(), Lift(), Marginalize(), ScatterAccum()))
    fused = Plan(8, (LeafDelta(), FusedChain((Lift(), Marginalize(),
                                              ScatterAccum()))))
    assert work.plan_work(fused, 111, 731) == work.plan_work(flat, 111, 731)
    # width 111: leaf 8*444 + lift 8*444 + ⊎ 3*8*444; lift 8*731,
    # ⊕ 8*111, ⊎ 8*111
    assert work.plan_work(flat, 111, 731) == (5 * 8 * 444,
                                              8 * 731 + 2 * 8 * 111)


def test_segment_and_least_time():
    plans = {"Shop": shop_plan(4)}
    nbytes, flops = work.segment_work(plans, ["Shop", "Shop"], 1, 1)
    assert (nbytes, flops) == (384, 72)
    peak = {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    assert work.least_seconds(384, 72, peak) == (0.384, "bandwidth")
    assert work.least_seconds(10, 72, peak) == (0.072, "compute")


def test_peaks_known_and_unknown_devices():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_ring_arithmetic():
    from benchmarks.chip.rings import sum as sum_ring

    cfg = {"relations": {"A": ["x", "y"], "B": ["y", "z"]}}
    assert (sum_ring.width(cfg), sum_ring.mul_flops(cfg)) == (1, 1)
