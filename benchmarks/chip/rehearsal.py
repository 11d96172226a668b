"""Runs a cell end to end on the CPU at a tiny size, for the tests.

The configuration's ``rehearsal`` entry shrinks its domains; the traffic
takes small batches and few reads; the kernels run in interpret mode
with plan fusion on, so the Pallas paths are the ones exercised.  The
check for a chip is skipped: the tests call :func:`harness.run_cell`
directly.
"""
from __future__ import annotations

import copy
import time

from benchmarks.chip import harness

#: traffic parameters a rehearsal overrides
TRAFFIC = dict(batch=64, read_rate=20.0, trace_after_s=0.5,
               trace_seconds=1.0)


def cell(workload: str):
    """``(bench, cfg, traffic)`` of a cell, shrunk."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, cfg, traffic = harness.cell_files(bench, workload)
    cfg = copy.deepcopy(cfg)
    for key, value in cfg.get("rehearsal", {}).items():
        if isinstance(value, dict):
            cfg[key] = {**cfg[key], **value}
        else:
            cfg[key] = value
    return bench, cfg, {**traffic, **TRAFFIC}


def run(workload: str, seed: int, seconds: float = 3.0,
        trace: bool = False) -> tuple[dict, dict]:
    """One rehearsal: the run's output and its result line."""
    from repro.core import plan as plan_mod
    from repro.kernels import scatter_ops

    bench, cfg, traffic = cell(workload)
    t = time.perf_counter()
    with scatter_ops.use_backend("onehot_interpret"), \
            plan_mod.use_fusion("on"):
        out = harness.run_cell(cfg, traffic, seed, seconds, trace, t,
                               log=lambda s: None)
    return out, harness.result_line(bench, workload, out, trace)
