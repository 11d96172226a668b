"""The trace reduction, checked on a small trace recorded on the CPU.

``testdata/cpu_trace.xplane.pb`` was written by :func:`record`: inside
the ``bench.trace_window`` span, a 50 ms ``bench.generate`` span with the
device idle, then three runs of one jitted program, each in a
``bench.run`` span.
"""
from __future__ import annotations

import os

import pytest

from benchmarks.chip import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                     "cpu_trace.xplane.pb")


def record(directory: str) -> str:
    """Record the test trace (run by hand, on the CPU)."""
    import time

    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((512, 512))
    step(x).block_until_ready()
    jax.profiler.start_trace(directory)
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        with jax.profiler.TraceAnnotation("bench.generate"):
            time.sleep(0.05)
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.run"):
                step(x).block_until_ready()
    jax.profiler.stop_trace()
    return trace_reduce.find_trace(directory)


def test_union_clip_length():
    iv = trace_reduce.union([(3, 5), (0, 1), (0.5, 2), (4, 6)])
    assert iv == [(0, 2), (3, 6)]
    assert trace_reduce.clip(iv, 1, 4) == [(1, 2), (3, 4)]
    assert trace_reduce.length(iv) == 5


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_busy_inside_window(reduced):
    assert 0.05 < reduced["window_s"] < 5.0
    assert 0.0 < reduced["busy_s"] < reduced["window_s"]


def test_program_executions(reduced):
    runs = {m: len(iv) for m, iv in reduced["executions"].items()}
    assert runs == {"jit__lambda": 3}
    assert all(op.module == "jit__lambda" for op in reduced["ops"])
    assert any(n.startswith("dot") for n in reduced["op_seconds"])


def test_idle_gaps_attributed_to_host_spans(reduced):
    name, seconds = reduced["idle_gaps"][0]
    assert name == "bench.generate"
    assert seconds >= 0.05
    idle = sum(s for _, s in reduced["idle_gaps"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"])


def test_breakdown_is_short_and_sorted(reduced):
    b = trace_reduce.breakdown(reduced, top=3)
    assert len(b["device_ops"]) <= 3 and len(b["idle_gaps"]) <= 3
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
