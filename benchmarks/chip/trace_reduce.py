"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Device operations are the events of each device plane's ``XLA Ops``
line (on the CPU backend, which has no device plane, every event that
carries an ``hlo_op`` stat).  Each operation is tied to its program
(``hlo_module`` stat, else the ``XLA Modules`` event that holds it).
Host spans are the benchmark's own ``TraceAnnotation`` events, named
``bench.<what>``; the one named ``bench.trace_window`` bounds the window
that is reduced.

The reduction gives the device's busy time (the union of operation
intervals, averaged over the device planes), the time per operation
name, the program executions seen, and the idle gaps, each attributed
to the host span that overlaps it most.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import warnings

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.trace_window"


@dataclasses.dataclass
class Op:
    name: str
    start: float  # seconds, trace clock
    end: float
    module: str
    text: str  # the event's string stats, for matching kernel names


def _stats(event) -> dict:
    with warnings.catch_warnings():  # jaxlib's stats type warns on access
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(event.stats)


def union(intervals) -> list:
    """Sorted, merged ``[(start, end)]``."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def find_trace(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def read(path: str) -> dict:
    """Device operations, program executions and host spans of a trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}  # plane name -> [Op]
    executions: dict = collections.defaultdict(list)  # module -> [(s, e)]
    spans: list = []
    cpu_ops: list = []
    cpu_runs: dict = {}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if is_device and line.name == "XLA Modules":
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    executions[ev.name.split("(")[0]].append(
                        (s, s + ev.duration_ns * 1e-9))
                continue
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, s, e))
                    continue
                if is_device and line.name == "XLA Ops":
                    st = _stats(ev)
                    text = " ".join(str(v)[:4000] for v in st.values()
                                    if isinstance(v, str))
                    devices.setdefault(plane.name, []).append(
                        Op(ev.name, s, e, str(st.get("hlo_module", "")),
                           text))
                elif not is_device and line.name != "python":
                    st = _stats(ev)
                    if "hlo_op" in st:
                        mod = str(st.get("hlo_module", ""))
                        cpu_ops.append(Op(ev.name, s, e, mod, ""))
                        run = (mod, st.get("run_id"))
                        lo, hi = cpu_runs.get(run, (s, e))
                        cpu_runs[run] = (min(lo, s), max(hi, e))
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
        for (mod, _), iv in cpu_runs.items():
            executions[mod].append(iv)
    for ops in devices.values():
        for op in ops:
            if not op.module:  # tie the op to the execution that holds it
                for mod, ivs in executions.items():
                    if any(s <= op.start < e for s, e in ivs):
                        op.module = mod
                        break
    return dict(devices=devices, executions=dict(executions), spans=spans)


def reduce(path: str) -> dict:
    """The numbers the metric readers use, over the traced window."""
    raw = read(path)
    devices, spans = raw["devices"], raw["spans"]
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    all_ops = [op for ops in devices.values() for op in ops]
    if window:
        lo, hi = window[0]
    elif all_ops:
        lo = min(op.start for op in all_ops)
        hi = max(op.end for op in all_ops)
    else:
        lo = hi = 0.0
    window_s = hi - lo
    busy = []
    gaps: list = []
    per_name: dict = collections.Counter()
    ops_in = []
    for ops in devices.values():
        inside = [op for op in ops if op.end > lo and op.start < hi]
        ops_in += inside
        iv = union(clip([(op.start, op.end) for op in inside], lo, hi))
        busy.append(length(iv))
        edges = [lo] + [x for se in iv for x in se] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
        for op in inside:
            per_name[op.name] += min(op.end, hi) - max(op.start, lo)
    n_dev = max(len(devices), 1)
    host = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    idle = []
    for s, e in gaps:
        best, name = 0.0, "no host span"
        for n, hs, he in host:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, n
        idle.append((name, e - s))
    idle.sort(key=lambda g: -g[1])
    # program executions that lie wholly inside the window
    execs = {m: [(s, e) for s, e in iv if s >= lo and e <= hi]
             for m, iv in raw["executions"].items()}
    return dict(
        window_s=window_s,
        busy_s=sum(busy) / n_dev,
        op_seconds=dict(per_name),
        idle_gaps=idle,
        executions={m: iv for m, iv in execs.items() if iv},
        ops=ops_in,
    )


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: the device operations that
    took most time and the longest idle gaps, by host span."""
    ops = sorted(reduced["op_seconds"].items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n, s] for n, s in ops[:top]],
            "idle_gaps": [[n, s] for n, s in reduced["idle_gaps"][:top]]}
