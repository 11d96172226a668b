"""Self time, engine scopes and program spans in device traces.

``testdata/tpu_trace.xplane.pb`` was written on a TPU v5e by
:func:`record_tpu`: the housing cell at its rehearsal size, one warm
segment, then two segments traced with the program's tracer on;
``testdata/tpu_trace.hlo.txt.gz`` is its stream program's compiled HLO
(:func:`stream_hlo`).
"""
from __future__ import annotations

import gzip
import os

import pytest

from benchmarks.chip import self_time, trace_reduce
from benchmarks.chip.metrics.segment_device_ms import stream_ops
from benchmarks.chip.trace_reduce import Op

TESTDATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata")
TPU_TRACE = os.path.join(TESTDATA, "tpu_trace.xplane.pb")
TPU_HLO = os.path.join(TESTDATA, "tpu_trace.hlo.txt.gz")


def stream_hlo(pipe) -> str:
    """The compiled HLO text of the stream program that ran the
    pipeline's last segment."""
    from repro.core import COOUpdate
    from repro.core.stream import prepare_stream

    cfg = pipe.run.cfg
    stream = [(rel, COOUpdate(tuple(cfg["relations"][rel]), keys,
                              pipe.ring.update_payload(pipe.query, mult)))
              for rel, keys, mult
              in pipe.log[-int(cfg["segment_updates"]):]]
    ex = pipe.executor
    prepared = prepare_stream(ex.engine, stream, check_capacity=False)
    lowered = ex.compiled(prepared).lower(ex.engine.state, prepared.xs,
                                          prepared.tail)
    return lowered.compile().as_text()


def record_tpu(directory: str) -> str:
    """Record the TPU test trace and its program's HLO (run by hand, on
    one chip); returns the trace's path."""
    import time

    import jax

    from repro.core import IVMEngine, StreamExecutor
    from repro.runtime import tracing
    from repro.serve import ViewServer

    from benchmarks.chip import generator, harness, rehearsal

    _, cfg, traffic = rehearsal.cell("housing.stream")
    ring = harness.part("rings", cfg["ring"])
    q = ring.query(cfg)
    db = ring.database(cfg, q, jax.random.PRNGKey(0))
    engine = IVMEngine.build(q, db, var_order=harness.var_order(cfg),
                             strategy="fivm", **cfg.get("build", {}))
    executor = StreamExecutor(engine)
    server = ViewServer(executor,
                        segment_updates=int(cfg["segment_updates"]))
    run = harness.Run(cfg, traffic, 1.0, jax.devices()[0].device_kind)
    gen = generator.UpdateGenerator(cfg, traffic, 0)
    pipe = harness._Pipeline(run, executor, server, ring, q, gen)
    pipe.segment()
    pipe.wait_inflight(1, time.perf_counter() + 60)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # keeps the file small
    options.host_tracer_level = 1  # annotations, not the runtime's events
    options.enable_hlo_proto = False
    jax.profiler.start_trace(directory, profiler_options=options)
    tracing.enable()
    with jax.profiler.TraceAnnotation("bench.trace_window"):
        for _ in range(2):
            pipe.segment()
        pipe.wait_inflight(1, time.perf_counter() + 60)
    tracing.disable()
    jax.profiler.stop_trace()
    with gzip.open(os.path.join(directory, "hlo.txt.gz"), "wt") as f:
        f.write(stream_hlo(pipe))
    pipe.close(time.perf_counter() + 10)
    return trace_reduce.find_trace(directory)


def op(name, start, end, text=""):
    return Op(name, start, end, "jit_run_stream", text)


@pytest.mark.parametrize("ops, want", [
    # while ⊃ call ⊃ custom-call, then a sort in the while
    ([op("while", 0.0, 10.0), op("call", 1.0, 9.0),
      op("custom-call", 2.0, 8.0), op("sort", 9.2, 9.7)],
     [1.5, 2.0, 6.0, 0.5]),
    # two ops that overlap without nesting: the later one runs
    ([op("while", 0.0, 4.0), op("fusion", 1.0, 3.0),
      op("copy", 5.0, 7.0), op("copy", 6.5, 8.0)],
     [2.0, 2.0, 1.5, 1.5]),
])
def test_self_time_counts_each_nanosecond_once(ops, want):
    own = self_time.self_times(ops)
    assert [round(own[id(o)], 9) for o in ops] == want
    busy = trace_reduce.length(trace_reduce.union(
        [(o.start, o.end) for o in ops]))
    assert sum(own.values()) == pytest.approx(busy)


HLO = """
HloModule jit_run_stream

%wide.body (wide.param: (u32[], f32[64], f32[8,8])) -> (u32[], f32[64], f32[8,8]) {
  %wide.param = (u32[], f32[64], f32[8,8]) parameter(0)
  %get-tuple-element.1 = f32[64]{0} get-tuple-element(%wide.param), index=1
  %dynamic-slice.13 = f32[8]{0} dynamic-slice(%get-tuple-element.1, %c), dynamic_slice_sizes={8}
  ROOT %tuple.2 = (u32[], f32[64], f32[8,8]) tuple(%c, %get-tuple-element.1, %dynamic-slice.13)
}

ENTRY %main.83 (p0: f32[64,128]) -> f32[8,8] {
  %fivm.fused_chain.6 = f32[64,128]{1,0} custom-call(f32[64,128]{1,0} %p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(run_stream)/while/body/fivm.fused_chain/pallas_call" source_file="plan.py"}
  %fusion.431 = f32[64]{0} fusion(%fivm.fused_chain.6), kind=kLoop, calls=%fused, metadata={op_name="jit(run_stream)/while/body/fivm.fused_chain/fivm.scatter/scatter-add"}
  %broadcast.892 = f32[8,8]{1,0} broadcast(%constant.1), dimensions={}
  %tuple.181 = (u32[], f32[64], f32[8,8]) tuple(%c, %fusion.431, %broadcast.892)
  %while.38 = (u32[], f32[64], f32[8,8]) while(%tuple.181), condition=%wide.cond, body=%wide.body
  %get-tuple-element.1176 = f32[8,8]{1,0} get-tuple-element(%while.38), index=2, metadata={op_name="jit(run_stream)/while/body/fivm.base_bump/reshape"}
  %copy.55 = f32[64]{0} copy(%fusion.431), metadata={op_name="jit(run_stream)/while/body/copy"}
  %copy.56 = f32[64]{0} copy(%copy.55)
  ROOT %add.1 = f32[8,8]{1,0} add(%get-tuple-element.1176, %copy.56), metadata={op_name="jit(run_stream)/while/body/fivm.gather/add"}
}
"""


def test_hlo_scopes_take_the_innermost_engine_scope():
    """An instruction takes its metadata's innermost ``fivm.`` scope; one
    the compiler made (no metadata) takes its users' where they agree,
    else its computation's caller's."""
    assert self_time.hlo_scopes(HLO) == {
        "fivm.fused_chain.6": "fivm.fused_chain",
        "fusion.431": "fivm.scatter",
        # no metadata: users (the loop, then the get-tuple-element)
        "while.38": "fivm.base_bump",
        "tuple.181": "fivm.base_bump",
        "broadcast.892": "fivm.base_bump",
        # no metadata, in the loop's body: the loop's scope
        "tuple.2": "fivm.base_bump",
        "dynamic-slice.13": "fivm.base_bump",
        "get-tuple-element.1": "fivm.base_bump",
        "wide.param": "fivm.base_bump",
        "get-tuple-element.1176": "fivm.base_bump",
        "add.1": "fivm.gather",
        "copy.56": "fivm.gather",
    }  # copy.55 has metadata outside every engine scope
    tpu = op("%dynamic-update-slice.13 = f32[8,1024]{1,0} dynamic-update-"
             "slice(...)", 0, 1)
    assert self_time.instruction(tpu) == "dynamic-update-slice.13"
    assert self_time.instruction(op("copy.28", 0, 1)) == "copy.28"


def test_tpu_trace_scopes_and_spans():
    """A TPU ``XLA Ops`` event carries no name stack (its stats are
    device times only), so the scopes come from the program's HLO; and
    the program's spans lie on the host plane beside the benchmark's."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(TPU_TRACE)
    stats, host = set(), set()
    for plane in data.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:") and line.name == "XLA Ops":
                for ev in line.events:
                    stats |= set(trace_reduce._stats(ev))
            elif not plane.name.startswith("/device:"):
                host |= {ev.name for ev in line.events}
    assert stats == {"device_offset_ps", "device_duration_ps",
                     "Time Scale Multiplier"}
    assert {"fivm.admit", "fivm.admit.stack", "fivm.dispatch",
            "fivm.publish", "bench.run"} <= host

    reduced = trace_reduce.reduce(TPU_TRACE)
    ops, runs = stream_ops(reduced)
    assert runs == 2
    with gzip.open(TPU_HLO, "rt") as f:
        scopes = self_time.hlo_scopes(f.read())
    by_scope = self_time.scope_seconds(ops, scopes)
    assert {"fivm.fused_chain", "fivm.scatter", "fivm.base_bump"} \
        <= set(by_scope)
    busy = trace_reduce.length(trace_reduce.union(
        [(o.start, o.end) for o in ops]))
    assert sum(by_scope.values()) == pytest.approx(busy)
    assert by_scope.get(None, 0.0) < 0.2 * busy
