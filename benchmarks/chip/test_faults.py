"""A run whose timed path is broken underneath comes out not correct:
once for each fault a cell on one chip can have."""
from __future__ import annotations

import numpy as np
import pytest

from benchmarks.chip import harness, rehearsal

CELLS = [w["name"] for w in harness.load_json(
    harness.ROOT, "BENCHMARK.json")["workloads"]]


def unchanged_state(monkeypatch):
    """Every segment's program returns the state it was given."""
    from repro.core.stream import StreamExecutor

    monkeypatch.setattr(StreamExecutor, "compiled",
                        lambda self, prepared: lambda state, xs, tail=(): state)


def half_batch(monkeypatch):
    """The second half of every batch is left out."""
    from repro.core import stream
    from repro.core.relations import COOUpdate

    prepare = stream.prepare_stream

    def halved(engine, sub, *a, **kw):
        out = []
        for rel, u in sub:
            keep = (np.arange(u.batch) < u.batch // 2)
            payload = {c: np.where(keep.reshape((-1,) + (1,) * (np.ndim(p) - 1)),
                                   p, 0).astype(np.float32)
                       for c, p in u.payload.items()}
            out.append((rel, COOUpdate(u.schema, u.keys, payload)))
        return prepare(engine, out, *a, **kw)

    monkeypatch.setattr(stream, "prepare_stream", halved)


def altered_answer(monkeypatch):
    """Point reads come back one higher than the view holds."""
    from repro.serve import server

    point = server.lookup_mod.point
    monkeypatch.setattr(server.lookup_mod, "point", lambda view, keys: {
        c: v + 1 for c, v in point(view, keys).items()})


def altered_range_sum(monkeypatch):
    """Range sums come back one higher than the view holds."""
    from repro.serve import server

    range_sum = server.lookup_mod.range_sum
    monkeypatch.setattr(server.lookup_mod, "range_sum", lambda view, lo, hi: {
        c: v + 1 for c, v in range_sum(view, lo, hi).items()})


def altered_top_k(monkeypatch):
    """Top-k reads return each value one higher than its key holds."""
    from repro.serve import server

    top_k = server.lookup_mod.top_k

    def raised(view, k, **kw):
        keys, values, valid = top_k(view, k, **kw)
        return keys, values + 1, valid

    monkeypatch.setattr(server.lookup_mod, "top_k", raised)


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   altered_answer, altered_range_sum,
                                   altered_top_k])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    out, line = rehearsal.run(workload, seed=31, seconds=2.0)
    assert line["correct"] is False, line["checks"]
