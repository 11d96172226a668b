"""Both cells end to end on the CPU at a tiny size: a valid result line
with ``correct`` true, and the control (the reference in bfloat16 in the
program's place) failing the same comparison."""
from __future__ import annotations

import json

import pytest

from benchmarks.chip import check, harness, rehearsal

CELLS = [w["name"] for w in harness.load_json(
    harness.ROOT, "BENCHMARK.json")["workloads"]]


@pytest.fixture(scope="module", params=CELLS)
def rehearsed(request):
    out, line = rehearsal.run(request.param, seed=2 ** 33 + 17)
    return request.param, out, line


def test_result_line(rehearsed):
    workload, out, line = rehearsed
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    assert json.loads(json.dumps(line)) == line
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"]: m["unit"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    want.pop("peak_hbm_gib")  # the CPU reports no memory peak
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name


def test_control_fails(rehearsed):
    workload, out, _ = rehearsed
    bench, cfg, _ = rehearsal.cell(workload)
    ev = out["evidence"]
    ref_cls = harness.part("references", cfg["name"]).Reference
    numbers = check.compare(cfg, ref_cls, ev["base0"], ev["batches"],
                            ev["reads"], ev["final_views"], ev["final_base"],
                            answers="control")
    numbers["reads_missing"] = 0
    ok, lines = check.verdict(numbers, cfg["limits"])
    assert not ok, lines
    # the program's own numbers sit below the control's
    assert out["numbers"]["view_err"] < numbers["view_err"]


def test_traced_rehearsal(monkeypatch):
    from benchmarks.chip import work

    # the CPU is not in the peaks table; the roofline reader needs a row
    monkeypatch.setattr(work, "peaks", lambda kind: {
        "flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    out, line = rehearsal.run(CELLS[0], seed=5, trace=True)
    assert line["correct"] is True, line["checks"]
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    m = line["metrics"]
    for name in ("build_s", "compile_s", "admit_ms", "publish_ms",
                 "segment_device_ms", "device_idle_pct",
                 "trigger_roofline", "read_service_ms", "read_lag_ms"):
        assert name in m, name
    assert m["window_compiles"]["value"] == 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
