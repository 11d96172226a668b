"""Host spans and counters of the engine: one facility for its telemetry.

``span(name)`` times a block on the host clock, always; callers read its
``.wall`` to fill their own telemetry (``last_segment_stats``,
``SnapshotRegistry.stats``).  Only while tracing is enabled
(:func:`enable`) does a span also

* enter a ``jax.profiler.TraceAnnotation`` of its name, so a profiler
  trace shows it on the host timeline, on the clock of the device ops;
* read the thread's CPU clock (``.cpu``); and
* append a :class:`Record` to a bounded log that :func:`drain` takes.

Off, a span costs one module-level boolean test beyond its clock reads.
No span reads a device value: a span around a dispatch times the
dispatch, not the device work.

``count(name, n)`` adds to the calling thread's counters; the segment
loop takes them (:func:`take_counts`) into each segment's stats entry.

Span names start with ``fivm.``; a dotted suffix names a part of the
span of that prefix (``fivm.admit.stack`` is a part of ``fivm.admit``).
"""
from __future__ import annotations

import collections
import threading
import time
from typing import NamedTuple

import jax

#: most records the log keeps; the oldest go first
LOG_LIMIT = 1 << 16


class Record(NamedTuple):
    name: str
    parent: str | None  # the enclosing span on the same thread
    thread: int  # threading.get_ident()
    t0: float  # time.perf_counter() at entry
    wall: float  # seconds
    cpu: float  # the thread's CPU seconds inside the span


_on = False
_log: collections.deque = collections.deque(maxlen=LOG_LIMIT)
_local = threading.local()


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def drain() -> list[Record]:
    """Take every record logged so far."""
    out = []
    while _log:
        out.append(_log.popleft())
    return out


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """``with span(name) as s: ...``; then ``s.wall`` (and ``s.cpu``,
    ``None`` unless tracing was enabled at entry)."""

    __slots__ = ("name", "wall", "cpu", "_t0", "_c0", "_ann", "_parent")

    def __init__(self, name: str):
        self.name = name
        self.wall = 0.0
        self.cpu = None
        self._ann = None

    def __enter__(self) -> "span":
        if _on:
            stack = _stack()
            self._parent = stack[-1] if stack else None
            stack.append(self.name)
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
            # the CPU interval lies inside the wall one, so cpu <= wall
            self._t0 = time.perf_counter()
            self._c0 = time.thread_time()
        else:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._ann is None:
            self.wall = time.perf_counter() - self._t0
            return
        self.cpu = time.thread_time() - self._c0
        self.wall = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        _stack().pop()
        _log.append(Record(self.name, self._parent, threading.get_ident(),
                           self._t0, self.wall, self.cpu))


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the calling thread's counter ``name``."""
    counts = _counts()
    counts[name] = counts.get(name, 0) + n


def take_counts() -> dict:
    """The calling thread's counters since the last take; resets them."""
    counts = _counts()
    out = dict(counts)
    counts.clear()
    return out


def _counts() -> dict:
    counts = getattr(_local, "counts", None)
    if counts is None:
        counts = _local.counts = {}
    return counts
