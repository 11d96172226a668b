"""The engine's host spans and counters (``repro.runtime.tracing``)."""
from __future__ import annotations

import threading

import jax
import numpy as np
import pytest

from repro.core import StreamExecutor
from repro.runtime import tracing
from repro.serve import ViewServer
from test_recovery import chaos_engine, chaos_query, chaos_stream


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``; counts entries."""

    def __init__(self):
        self.entered: list[str] = []

    def __call__(self, name):
        outer = self

        class _Ann:
            def __enter__(self):
                outer.entered.append(name)

            def __exit__(self, *exc):
                return False

        return _Ann()


@pytest.fixture
def annotations(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    tracing.disable()
    tracing.drain()
    yield ann
    tracing.disable()
    tracing.drain()


def test_off_records_nothing(annotations):
    with tracing.span("fivm.a") as outer:
        with tracing.span("fivm.a.b"):
            pass
    assert outer.wall > 0 and outer.cpu is None
    assert tracing.drain() == []
    assert annotations.entered == []


def test_on_links_parents_within_a_thread(annotations):
    tracing.enable()

    def other():
        with tracing.span("fivm.other"):
            pass

    with tracing.span("fivm.a") as a:
        with tracing.span("fivm.a.b") as b:
            sum(range(20000))
            t = threading.Thread(target=other)
            t.start()
            t.join()
    tracing.disable()
    log = {r.name: r for r in tracing.drain()}
    assert set(log) == {"fivm.a", "fivm.a.b", "fivm.other"}
    assert log["fivm.a"].parent is None
    assert log["fivm.a.b"].parent == "fivm.a"
    assert log["fivm.other"].parent is None  # another thread's stack
    assert log["fivm.other"].thread != log["fivm.a"].thread
    for r in log.values():
        assert 0 <= r.cpu <= r.wall
    assert (a.wall, a.cpu) == (log["fivm.a"].wall, log["fivm.a"].cpu)
    assert log["fivm.a"].t0 <= log["fivm.a.b"].t0
    assert b.wall <= a.wall
    assert annotations.entered == ["fivm.a", "fivm.a.b", "fivm.other"]


def test_span_entered_while_on_records_after_disable(annotations):
    tracing.enable()
    with tracing.span("fivm.a"):
        tracing.disable()
    assert [r.name for r in tracing.drain()] == ["fivm.a"]


def test_counts_are_taken_per_thread():
    tracing.take_counts()
    tracing.count("copy_bytes", 3)
    tracing.count("copy_bytes", 4)
    seen = {}
    t = threading.Thread(target=lambda: seen.update(tracing.take_counts()))
    t.start()
    t.join()
    assert seen == {}
    assert tracing.take_counts() == {"copy_bytes": 7}
    assert tracing.take_counts() == {}


def test_segment_stats_are_the_span_walls(annotations):
    """``admit_s``, ``dispatch_s`` and ``publish_s`` are the walls of the
    segment's ``fivm.admit``, ``fivm.dispatch`` and ``fivm.publish``
    spans; the first segment counts the state it copies."""
    q = chaos_query()
    eng = chaos_engine("dense")
    ex = StreamExecutor(eng)
    server = ViewServer(ex, segment_updates=3)
    tracing.enable()
    ex.run(chaos_stream(q, "scan", 11))
    tracing.disable()
    log = tracing.drain()
    stats = ex.last_segment_stats
    assert len(stats) == 3

    def walls(name):
        return [r.wall for r in log if r.name == name]

    assert [s["admit_s"] for s in stats] == walls("fivm.admit")
    assert [s["dispatch_s"] for s in stats] == walls("fivm.dispatch")
    assert [s["publish_s"] for s in stats] == walls("fivm.publish")
    assert server.registry.stats()["publish_s"] == walls("fivm.publish")[-1]
    parts = {r.name for r in log if r.parent == "fivm.admit"}
    assert {"fivm.admit.stack", "fivm.admit.plans",
            "fivm.admit.program"} <= parts
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.state))
    assert stats[0]["counts"] == {"copy_bytes": state_bytes}
    assert [s["counts"] for s in stats[1:]] == [{}, {}]


def test_reads_are_spanned(annotations):
    eng = chaos_engine("dense")
    server = ViewServer(StreamExecutor(eng))
    name = sorted(server.registry.latest().views)[0]
    tracing.enable()
    with server.pin() as pin:
        res = pin.point(name, np.zeros((2, len(eng.views[name].schema)),
                                       np.int32))
    res.host()
    tracing.disable()
    assert [r.name for r in tracing.drain()] == [
        "fivm.read.pin", "fivm.read", "fivm.read.host"]
