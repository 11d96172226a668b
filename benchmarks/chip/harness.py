"""Runs one cell of the chip benchmark once.

A cell is a configuration (``configs/<name>.json``, with its plain
reference in ``references/<name>.py`` and its ring in
``rings/<ring>.py``) under a traffic mix (``traffic/<name>.json``).
The harness finds each file by the name that ``BENCHMARK.json`` gives,
and each metric by its name in ``metrics/<name>.py``.

One run, in one process, goes through the user's path:

1. set-up: the database is drawn on the device from the seed,
   ``IVMEngine.build`` builds the engine, a ``StreamExecutor`` gets a
   ``ViewServer`` (so every segment publishes one generation), and one
   segment and one read of each kind warm every program the window
   runs;
2. the window: update batches arrive as numpy arrays and go through
   ``StreamExecutor.run``, one segment per call, with at most
   ``inflight_segments`` generations not yet visible.  A watcher thread
   waits for each published generation on the device; a reader thread
   sends reads on an open-loop schedule against the newest generation,
   pinned, and a completer thread takes each result to the host;
3. after the window: the peak of device memory is read, the state is
   taken to the host and freed, and the reference replays every batch
   and answers the reads (``check.py``).

Set-up is everything from the process start to the window.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import os
import queue
import shutil
import sys
import threading
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: the persistent compile cache, at a fixed path inside the checkout
CACHE_DIR = os.path.join(HERE, ".jax_cache")
#: where a traced run writes its profile (removed once it is reduced)
TRACE_DIR = os.path.join(HERE, ".trace")
#: how long after the window's close a segment or a read may still end
DRAIN_S = 60.0
#: the jitted stream program (``StreamExecutor._build``)
STREAM_PROGRAM = "run_stream"

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_TRACE_EVENT = _COMPILE_EVENTS[0]


class _Events:
    """JAX's compile events, stamped with the host clock.  One listener
    per process forwards to :data:`EVENTS`."""

    def __init__(self):
        self.log: list = []

    def on(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.log.append((time.perf_counter(), event, duration))


EVENTS = _Events()
_listening = [False]


def _listen() -> None:
    import jax

    if not _listening[0]:
        jax.monitoring.register_event_duration_secs_listener(EVENTS.on)
        _listening[0] = True


def use_compile_cache() -> None:
    """Every compile goes to the fixed cache inside the checkout, the
    small eager ones of the build too."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def part(kind: str, name: str):
    """The module ``<kind>/<name>.py`` of the benchmark."""
    return importlib.import_module(f"benchmarks.chip.{kind}.{name}")


def cell_files(bench: dict, workload: str) -> tuple[dict, dict, dict]:
    """The cell, its configuration and its traffic parameters."""
    from . import generator as traffic_mod

    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    cfg = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = traffic_mod.load(load_json(HERE, "traffic",
                                         cell["traffic"] + ".json"))
    return cell, cfg, traffic


def metric_names(bench: dict, workload: str, trace: bool) -> list:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def seed32(seed: int) -> int:
    """A 31-bit device seed from any whole number."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0] % (2 ** 31))


# ---------------------------------------------------------------------------
# what a run records, for the metric readers
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Batch:
    rel: str
    tuples: int
    stamp: float  # host clock: handed over by the generator
    visible: float | None = None  # its generation found ready on device


@dataclasses.dataclass
class Run:
    cfg: dict
    traffic: dict
    seconds: float
    device_kind: str
    setup: dict = dataclasses.field(default_factory=dict)
    batches: list = dataclasses.field(default_factory=list)
    segments: list = dataclasses.field(default_factory=list)
    reads: list = dataclasses.field(default_factory=list)
    t0: float = 0.0  # window opens (host clock)
    t_end: float = 0.0  # window closes
    window_compiles: int = 0
    peak_bytes: int = -1
    trace: dict | None = None
    segment_bytes: int = 0  # necessary work of one segment
    segment_flops: int = 0

    def window_batches(self) -> list:
        """Batches handed over in the window whose generation became
        visible before it closed."""
        return [b for b in self.batches if b.stamp >= self.t0
                and b.visible is not None and b.visible <= self.t_end]

    def window_segments(self) -> list:
        return [s for s in self.segments if s["t"] >= self.t0]

    def read_latency(self, rd) -> float:
        """Due time to result on the host; a read that failed or never
        returned counts as waiting until the drain gave up."""
        due = self.t0 + rd.due
        done = rd.done if rd.done is not None else self.t_end + DRAIN_S
        return done - due


def percentile(values, q: float):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
class _Pipeline:
    """The update side of the window: segments through the executor,
    the watcher that finds each generation ready, and the reads."""

    def __init__(self, run: Run, executor, server, ring, query, gen):
        self.run = run
        #: served view names by the variable they sit at
        self.view_names = {v.rsplit("@", 1)[1]: v
                           for v in executor.engine.views}
        self.executor = executor
        self.server = server
        self.ring = ring
        self.query = query
        self.gen = gen
        self.offset = 0
        self.log: list = []  # every applied batch: (rel, keys, mult)
        self.cond = threading.Condition()
        self.inflight = 0
        self.watch_q: queue.Queue = queue.Queue()
        self.done_q: queue.Queue = queue.Queue()
        self.errors: list = []
        self.threads = [threading.Thread(target=self._watch, daemon=True),
                        threading.Thread(target=self._complete, daemon=True)]
        for t in self.threads:
            t.start()

    # ----------------------------------------------------------- segments
    def segment(self) -> None:
        """Generate one segment, run it, hand its generation to the
        watcher.  Each batch is stamped when it is handed over."""
        import jax
        from repro.core import COOUpdate

        run = self.run
        n = int(run.cfg["segment_updates"])
        stream, idx = [], []
        with jax.profiler.TraceAnnotation("bench.generate"):
            for _ in range(n):
                rel, keys, mult = self.gen.next()
                self.log.append((rel, keys, mult))
                run.batches.append(Batch(rel, len(mult), time.perf_counter()))
                idx.append(len(run.batches) - 1)
                stream.append((rel, COOUpdate(
                    tuple(run.cfg["relations"][rel]), keys,
                    self.ring.update_payload(self.query, mult))))
        with self.cond:
            self.inflight += 1
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.run"):
            self.executor.run(stream, _offset=self.offset)
        self.offset += n
        snap = self.server.registry.latest()
        stats = dict(self.executor.last_segment_stats[-1])
        stats["t"] = t
        run.segments.append(stats)
        if snap.offset != self.offset:
            raise RuntimeError(f"generation {snap.generation} covers "
                               f"{snap.offset} batches, not {self.offset}")
        self.watch_q.put((snap, idx))

    def wait_inflight(self, limit: int, deadline: float) -> None:
        with self.cond:
            while self.inflight >= limit and time.perf_counter() < deadline:
                self.cond.wait(timeout=0.05)

    def _watch(self) -> None:
        import jax

        while True:
            item = self.watch_q.get()
            if item is None:
                return
            snap, idx = item
            try:
                with jax.profiler.TraceAnnotation("bench.watch"):
                    jax.block_until_ready(snap.views)
                t = time.perf_counter()
                for i in idx:
                    self.run.batches[i].visible = t
            except Exception:  # the run goes on; the batches stay unseen
                self.errors.append(traceback.format_exc())
            with self.cond:
                self.inflight -= 1
                self.cond.notify_all()

    # -------------------------------------------------------------- reads
    def issue(self, rd):
        """Send one read against the newest generation, pinned."""
        import jax

        with jax.profiler.TraceAnnotation("bench.read"):
            pin = self.server.pin()
            try:
                rd.generation, rd.offset = pin.generation, pin.offset
                view = self.view_names[rd.spec["view"]]
                if rd.kind == "point":
                    res = pin.point(view, rd.params["keys"])
                elif rd.kind == "range_sum":
                    res = pin.range_sum(view, rd.params["lo"],
                                        rd.params["hi"])
                else:
                    kw = ({"component": rd.spec["component"]}
                          if rd.spec.get("component") else {})
                    res = pin.top_k(view, int(rd.spec["k"]), **kw)
            except Exception:
                pin.release()
                raise
        return pin, res

    def read_loop(self, reads: list, t0: float) -> None:
        for rd in reads:
            delay = t0 + rd.due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            rd.dispatched = time.perf_counter()
            try:
                pin, res = self.issue(rd)
            except Exception as e:
                rd.error = repr(e)
                continue
            self.done_q.put((rd, pin, res))

    def _complete(self) -> None:
        import jax

        while True:
            item = self.done_q.get()
            if item is None:
                return
            rd, pin, res = item
            try:
                with jax.profiler.TraceAnnotation("bench.read_wait"):
                    rd.result = res.host()
                rd.done = time.perf_counter()
            except Exception as e:
                rd.error = repr(e)
            finally:
                pin.release()

    def close(self, deadline: float) -> None:
        self.watch_q.put(None)
        self.done_q.put(None)
        for t in self.threads:
            t.join(timeout=max(deadline - time.perf_counter(), 0.1))


def var_order(cfg):
    from repro.core import chain

    vo = cfg["var_order"]
    return chain(list(vo["chain"]), {k: [list(c) for c in v]
                                     for k, v in vo.get("branches",
                                                        {}).items()})


def resolved_backends(engine, batch: int) -> list:
    """Kernel backends the ``batch``-tuple plans resolved to."""
    from repro.core import plan as plan_mod
    from repro.core.storage import comp_width, payload_width
    from repro.kernels import ring_fused, scatter_ops

    out = set()
    for plan in engine.precompile(batch).values():
        for op in plan.ops + plan.ind_ops:
            if isinstance(op, plan_mod.FusedChain):
                out.add(ring_fused.resolve_backend(op.ops[-1].backend))
            elif isinstance(op, plan_mod.ScatterAccum) and op.backend:
                out.add(op.backend)
    d = payload_width(engine.query.ring)
    for rel in engine.base.values():
        out.add(scatter_ops.resolve_backend(comp_width(rel.domains), batch,
                                            d))
    return sorted(out)


def run_cell(cfg: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, t_process: float, log=print) -> dict:
    """One run; returns the pieces of the result line (see
    :func:`result_line`).  The caller has checked the device."""
    import jax

    from repro.core import IVMEngine, StreamExecutor
    from repro.core.storage import as_dense
    from repro.serve import ViewServer

    from . import check as check_mod
    from . import generator as traffic_mod
    from . import work as work_mod

    _listen()
    n_events = len(EVENTS.log)
    dev = jax.devices()[0]
    run = Run(cfg, traffic, float(seconds), dev.device_kind)
    ring = part("rings", cfg["ring"])
    t = time.perf_counter()
    run.setup["init_s"] = t - t_process

    # ------------------------------------------------------------ set-up
    q = ring.query(cfg)
    key = jax.random.PRNGKey(seed32(seed))
    db = ring.database(cfg, q, key)
    jax.block_until_ready(db)
    t1 = time.perf_counter()
    run.setup["data_s"] = t1 - t
    engine = IVMEngine.build(q, db, var_order=var_order(cfg),
                             strategy="fivm", **cfg.get("build", {}))
    jax.block_until_ready(engine.state)
    del db
    t2 = time.perf_counter()
    run.setup["build_s"] = t2 - t1
    executor = StreamExecutor(engine)
    server = ViewServer(executor,
                        segment_updates=int(cfg["segment_updates"]))
    gen = traffic_mod.UpdateGenerator(cfg, traffic, seed)
    pipe = _Pipeline(run, executor, server, ring, q, gen)
    key_space = {}
    for spec in cfg["reads"]:
        v = engine.views[pipe.view_names[spec["view"]]]
        dims = tuple(int(cfg["domains"][x]) for x in v.schema)
        key_space[spec["view"]] = (tuple(v.schema), dims,
                                   int(np.prod(dims)) if dims else 1)
    for _ in range(int(traffic["warm_segments"])):
        pipe.segment()
        pipe.wait_inflight(1, time.perf_counter() + DRAIN_S)
    warm = traffic_mod.read_schedule(cfg, 1.0, seed + 1, len(cfg["reads"]),
                                     key_space, weights=[1] * len(cfg["reads"]))
    for rd in warm:
        pin, res = pipe.issue(rd)
        res.host()
        pin.release()
    batch = int(traffic["batch"])
    backends = resolved_backends(engine, batch)
    log(f"resolved backends at B={batch}: {backends}")
    plans = engine.precompile(batch)
    order = [gen.order[i % len(gen.order)]
             for i in range(int(cfg["segment_updates"]))]
    run.segment_bytes, run.segment_flops = work_mod.segment_work(
        plans, order, ring.width(cfg), ring.mul_flops(cfg))
    t3 = time.perf_counter()
    run.setup["warm_s"] = t3 - t2
    run.setup["compile_s"] = sum(d for _, _, d in EVENTS.log[n_events:])
    run.setup["setup_s"] = t3 - t_process
    log("set-up: " + ", ".join(f"{k} {v:.3f}" for k, v in run.setup.items()))

    # ------------------------------------------------------------ window
    reads = traffic_mod.read_schedule(cfg, traffic["read_rate"], seed, seconds,
                                      key_space)
    run.reads = reads
    n_events = len(EVENTS.log)
    run.t0 = t0 = time.perf_counter()
    run.t_end = t_end = t0 + float(seconds)
    reader = threading.Thread(target=pipe.read_loop, args=(reads, t0),
                              daemon=True)
    reader.start()
    tracing = None
    depth = int(traffic["inflight_segments"])
    while True:
        pipe.wait_inflight(depth, t_end)
        now = time.perf_counter()
        if now >= t_end:
            break
        if trace and tracing is None and now >= t0 + traffic["trace_after_s"]:
            jax.profiler.start_trace(TRACE_DIR)
            tracing = [jax.profiler.TraceAnnotation("bench.trace_window"),
                       time.perf_counter()]
            tracing[0].__enter__()
        elif (tracing and tracing[1] is not None
              and now >= tracing[1] + traffic["trace_seconds"]):
            tracing[0].__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing[1] = None
        pipe.segment()
    if tracing and tracing[1] is not None:
        tracing[0].__exit__(None, None, None)
        jax.profiler.stop_trace()
    deadline = t_end + DRAIN_S
    reader.join(timeout=max(deadline - time.perf_counter(), 0.1))
    pipe.wait_inflight(1, deadline)
    pipe.close(deadline)
    run.window_compiles = sum(
        1 for ts, ev, _ in EVENTS.log[n_events:]
        if ev == _TRACE_EVENT and ts <= t_end)
    for err in pipe.errors + [f"read {rd.index}: {rd.error}"
                              for rd in reads if rd.error][:5]:
        log(err)

    # ------------------------------------------------------ after it
    stats = dev.memory_stats() or {}
    run.peak_bytes = int(stats.get("peak_bytes_in_use", -1))
    if trace and tracing:
        from . import trace_reduce

        run.trace = trace_reduce.reduce(trace_reduce.find_trace(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    final_views = {}
    for name, v in engine.views.items():
        d = as_dense(v)
        final_views[name] = (tuple(d.schema), jax.device_get(d.payload))
    final_base = None
    if cfg.get("build", {}).get("store_base"):
        final_base = {r: np.asarray(jax.device_get(m))
                      for r, m in ring.multiplicities(engine.base).items()}
    del engine, executor, server, pipe.executor, pipe.server
    gc.collect()
    db0 = ring.database(cfg, q, key)
    base0 = {r: np.asarray(jax.device_get(m), np.float64)
             for r, m in ring.multiplicities(db0).items()}
    del db0
    checked = [rd for rd in reads if rd.result is not None]
    ref_cls = part("references", cfg["name"]).Reference
    numbers = check_mod.compare(cfg, ref_cls, base0, pipe.log, checked,
                                final_views, final_base)
    numbers["reads_missing"] = sum(1 for rd in reads if rd.result is None)
    correct, lines = check_mod.verdict(numbers, cfg["limits"])
    failed = numbers["reads_missing"] + sum(
        1 for b in run.batches if b.stamp >= t0 and b.visible is None)
    attempted = len(reads) + sum(1 for b in run.batches if b.stamp >= t0)
    evidence = dict(base0=base0, batches=pipe.log, reads=checked,
                    final_views=final_views, final_base=final_base)
    return dict(run=run, correct=correct, checks=lines, attempted=attempted,
                failed=failed, numbers=numbers, evidence=evidence)


def result_line(bench: dict, workload: str, out: dict, trace: bool) -> dict:
    """The last line of standard output."""
    import jax

    run: Run = out["run"]
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    metrics = {}
    for m in metric_names(bench, workload, trace):
        value = part("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
        else:  # left out of the line; say so where it can be seen
            print(f"metric {m['name']} found nothing to read",
                  file=sys.stderr)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.peak_bytes}
    line = {"correct": bool(out["correct"]), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        from . import trace_reduce

        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = trace_reduce.breakdown(run.trace)
    # a number that is not finite (a read of the wrong shape) prints null
    line["checks"] = {name: {"value": value if np.isfinite(value) else None,
                             "limit": limit}
                      for name, value, limit, _ in out["checks"]}
    return line


def main(argv=None, t_process: float | None = None) -> int:
    import argparse

    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one cell of the chip "
                                 "benchmark once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, cfg, traffic = cell_files(bench, args.workload)

    import jax

    use_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        print(f"this benchmark needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    out = run_cell(cfg, traffic, args.seed, args.seconds, bool(args.trace),
                   t_process, log=lambda s: print(s, flush=True))
    line = result_line(bench, args.workload, out, bool(args.trace))
    print(json.dumps(line), flush=True)
    for name, value, limit, ok in out["checks"]:
        print(f"check {name} {value!r} limit {limit!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    return 0
