"""Every metric reader on a recorded trace and on a synthetic run.

The readers that were there before the program's spans must read what
they read before (the values below were computed by those readers); the
readers of the program's span walls and counters read ``None`` where a
run has none to read.
"""
from __future__ import annotations

import os

import pytest

from benchmarks.chip import harness, trace_reduce
from benchmarks.chip.generator import Read
from benchmarks.chip.trace_reduce import Op

CPU_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "testdata", "cpu_trace.xplane.pb")
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
#: the readers that read the program's span walls and counters
PROGRAM_READERS = ("dispatch_ms", "state_copy_gib")


def read(name, run):
    return harness.part("metrics", name).read(run)


def synthetic_run(program: bool = False) -> harness.Run:
    """A run of fixed numbers: three segments, eight batches, five
    reads, and a trace of two stream-program executions.  ``program``
    adds what a program with counters records."""
    run = harness.Run({}, {}, 10.0, "TPU v5 lite")
    run.setup = dict(init_s=1.5, data_s=2.0, build_s=0.25, warm_s=3.0,
                     compile_s=2.5, setup_s=6.75)
    run.t0, run.t_end = 100.0, 110.0
    run.window_compiles = 0
    run.peak_bytes = 3 * 2 ** 30 + 12345
    run.segment_bytes, run.segment_flops = 10 ** 8, 10 ** 9
    for i, t in enumerate([99.0, 101.0, 104.0]):
        seg = dict(segment=0, n_steps=2, admit_s=0.1 * (i + 1),
                   dispatch_s=0.01 * (i + 2), save_s=0.0, audit_s=0.0,
                   publish_s=0.002 * (i + 1), t=t)
        if program:
            seg["counts"] = {"copy_bytes": 2 ** 30} if i else {}
        run.segments.append(seg)
    stamps = [99.5, 100.5, 101.0, 102.0, 103.5, 104.0, 108.0, 109.5]
    seen = [100.0, 101.2, 101.9, 103.0, 104.9, 105.0, 109.0, None]
    run.batches = [harness.Batch("R", 1024, s, v)
                   for s, v in zip(stamps, seen)]
    for i, (due, disp, done) in enumerate([(0.0, 100.01, 100.02),
                                           (1.0, 101.5, 101.6),
                                           (2.0, 102.0, 102.3),
                                           (3.0, 103.01, None),
                                           (4.0, 104.2, 104.21)]):
        run.reads.append(Read(i, due, "point", {}, {}, dispatched=disp,
                              done=done))
    ops = [Op("%while.1 = (s32[]) while(...)", 101.0, 101.5,
              "jit_run_stream", ""),
           Op("%custom-call.2 = f32[64,128] custom-call(...)", 101.1, 101.4,
              "jit_run_stream", "_fused_kernel"),
           Op("%sort.3 = s32[1024] sort(...)", 101.45, 101.48,
              "jit_run_stream", ""),
           Op("%dus.4 = f32[8,8,64] dynamic-update-slice(...)", 101.48,
              101.5, "jit_run_stream", ""),
           Op("%while.1 = (s32[]) while(...)", 104.0, 104.6,
              "jit_run_stream", ""),
           Op("%custom-call.2 = f32[64,128] custom-call(...)", 104.1, 104.5,
              "jit_run_stream", "tpu_custom_call"),
           Op("%copy.9 = f32[64] copy(...)", 103.0, 103.2, "jit_copy", "")]
    run.trace = dict(window_s=4.0, busy_s=1.3,
                     op_seconds={"%while.1 = (s32[]) while(...)": 1.1,
                                 "%copy.9 = f32[64] copy(...)": 0.2},
                     idle_gaps=[("bench.run", 2.7)],
                     executions={"jit_run_stream": [(101.0, 101.5),
                                                    (104.0, 104.6)],
                                 "jit_copy": [(103.0, 103.2)]},
                     ops=ops)
    return run


#: the readers' values at the parent program, on :func:`synthetic_run`
PARENT_SYNTHETIC = {
    "admit_ms": 250.0, "build_s": 0.25, "compile_s": 2.5,
    "device_idle_pct": 67.5, "kernel_ms": 350.0000000000085,
    "peak_hbm_gib": 3.0000114971771836, "publish_ms": 5.0,
    "read_lag_ms": 440.0000000000005, "read_p95_ms": 53719.999999999985,
    "read_service_ms": 54.99999999999261,
    "segment_device_ms": 925.0000000000043, "setup_s": 6.75,
    "trigger_roofline": 0.013200013200013138,
    "update_tuples_per_s": 614.4, "visible_p95_ms": 1300.0000000000043,
    "window_compiles": 0,
}

#: the parent's reduction of ``cpu_trace.xplane.pb``
PARENT_CPU_TRACE = dict(
    window_s=0.07048019500000001, busy_s=0.018681704, n_ops=12,
    executions={"jit__lambda": 3}, device_idle_pct=73.49368287076959,
    idle_gaps=[("bench.generate", 0.050630167000000004),
               ("bench.run", 0.0007250869999999993),
               ("bench.run", 0.00038955099999999493)])


@pytest.mark.parametrize("program", [False, True])
@pytest.mark.parametrize("name", sorted(PARENT_SYNTHETIC))
def test_existing_reader_reads_as_before(name, program):
    assert read(name, synthetic_run(program)) == pytest.approx(
        PARENT_SYNTHETIC[name], rel=1e-12)


def test_existing_readers_on_the_cpu_trace():
    reduced = trace_reduce.reduce(CPU_TRACE)
    want = PARENT_CPU_TRACE
    assert reduced["window_s"] == pytest.approx(want["window_s"], rel=1e-12)
    assert reduced["busy_s"] == pytest.approx(want["busy_s"], rel=1e-12)
    assert len(reduced["ops"]) == want["n_ops"]
    assert {m: len(iv) for m, iv in reduced["executions"].items()} \
        == want["executions"]
    assert reduced["idle_gaps"][:3] == want["idle_gaps"]
    run = synthetic_run()
    run.trace = reduced
    assert read("device_idle_pct", run) == pytest.approx(
        want["device_idle_pct"], rel=1e-12)
    for name in ("segment_device_ms", "kernel_ms", "trigger_roofline"):
        assert read(name, run) is None  # no stream program in that trace


@pytest.mark.parametrize("name", PROGRAM_READERS)
def test_program_reader_on_an_untraced_run(name):
    """A run with no segment in its window, and so no span wall or
    counter of one, reads ``None``; so does ``state_copy_gib`` on a
    program without counters."""
    run = synthetic_run()
    run.trace = None
    assert read("state_copy_gib", run) is None
    run.segments = [s for s in run.segments if s["t"] < run.t0]
    assert read(name, run) is None


def test_program_readers_on_a_program_with_spans():
    run = synthetic_run(program=True)
    got = {name: read(name, run) for name in PROGRAM_READERS}
    assert got == pytest.approx({
        "dispatch_ms": 35.0,  # (0.03 + 0.04) / 2 of the window's segments
        "state_copy_gib": 1.0,
    })


def test_every_listed_metric_has_a_reader():
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        assert callable(harness.part("metrics", m["name"]).read), m["name"]
