"""Version-stamped view snapshots published at segment boundaries.

The serving plane's consistency primitive (DESIGN.md §12): while the
stream executor's fused segments run on *donated* state buffers, readers
only ever touch :class:`Snapshot` objects — device-side ``jnp.copy``
copies of the read-visible views, stamped with a monotonically
increasing generation and the cumulative stream offset they correspond
to.  The copies dispatch without a host sync and are ordered by XLA
after the producing segment and before the next segment's donation, so
publication rides the same overlap discipline as the async checkpoint
save (DESIGN.md §10) — and the checkpointer *reuses* these copies when
both are attached (``StreamCheckpointer.save_boundary(view_copies=)``).

Consistency contract:

* a generation is published atomically under the registry lock — a
  reader pinning generation ``g`` sees **every** view at ``g`` (the
  whole view hierarchy was copied from the same post-segment,
  post-audit engine state), never a mix of generations and never the
  in-flight carry;
* generations are immutable once published — pins are refcounts, not
  locks on the writer;
* retention is double-buffered by default (``retain=2``): the newest
  ``retain`` generations stay readable without pinning, older ones are
  dropped unless pinned.  ``pin`` protects a generation from eviction
  for multi-query reads spanning segment boundaries.

Thread safety: ``publish`` runs on the stream thread, ``pin`` /
``release`` / ``latest`` on any reader thread; all registry state is
guarded by one lock.  The device arrays themselves are immutable, so
lookups on a pinned snapshot need no lock at all.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp

from repro.runtime import tracing


@dataclasses.dataclass
class Snapshot:
    """One published generation: immutable device-side view copies.

    ``offset`` is the cumulative stream offset the views correspond to
    (how many leading updates of the run's stream are fully applied) —
    the replay cursor an offline recomputation of this generation uses;
    -1 when unknown (bootstrap publish of a pre-existing engine state).
    """

    generation: int
    offset: int
    segment: int
    views: dict[str, Any]
    published_at: float
    meta: dict = dataclasses.field(default_factory=dict)
    #: host wall of the first read against this generation (staleness
    #: telemetry; None until read)
    first_read_at: float | None = None


class SnapshotRegistry:
    """Double-buffered, generation-stamped view snapshots.

    ``views`` restricts publication to a subset of the engine's views
    (cheaper copies when only some views are served); ``None`` publishes
    the whole hierarchy.  ``segment_updates`` caps the number of stream
    updates between publications the same way the checkpointer's knob
    does — the executor splits segments so fresh generations appear even
    when capacity segmentation never would.
    """

    def __init__(self, retain: int = 2,
                 segment_updates: int | None = None,
                 views: Sequence[str] | None = None):
        if retain < 1:
            raise ValueError("retain must be >= 1")
        if segment_updates is not None and segment_updates < 1:
            raise ValueError("segment_updates must be >= 1")
        self.retain = int(retain)
        self.segment_updates = segment_updates
        self.view_names = tuple(views) if views is not None else None
        self._lock = threading.Lock()
        self._snaps: dict[int, Snapshot] = {}
        self._pins: dict[int, int] = {}
        #: newest published generation (-1 before the first publish)
        self.generation: int = -1
        self.publishes: int = 0
        #: wall of the last ``fivm.publish`` span
        self.last_publish_seconds: float = 0.0
        #: publish→first-read latencies (seconds) of retired generations
        self._first_read_s: list[float] = []

    # ------------------------------------------------------------- publish
    def publish(self, views: Mapping[str, Any], offset: int = -1,
                segment: int = -1, meta: dict | None = None) -> Snapshot:
        """Copy the read-visible views and stamp a new generation.

        Called by the stream thread at segment boundaries (after the
        audit hook, so a repaired state — never a drifted one — is what
        readers see).  The ``jnp.copy`` dispatches device-side without a
        host sync; the copies are safe against the next segment's buffer
        donation.  Returns the new :class:`Snapshot`.
        """
        with tracing.span("fivm.publish") as span:
            names = (self.view_names if self.view_names is not None
                     else tuple(views))
            copies = {n: jax.tree.map(jnp.copy, views[n]) for n in names}
            with self._lock:
                gen = self.generation + 1
                snap = Snapshot(generation=gen, offset=int(offset),
                                segment=int(segment), views=copies,
                                published_at=time.perf_counter(),
                                meta=dict(meta or {}))
                self._snaps[gen] = snap
                self.generation = gen
                self.publishes += 1
                self._evict_locked()
        self.last_publish_seconds = span.wall
        return snap

    def _evict_locked(self) -> None:
        floor = self.generation - self.retain + 1
        for g in [g for g in self._snaps
                  if g < floor and not self._pins.get(g)]:
            snap = self._snaps.pop(g)
            if snap.first_read_at is not None:
                self._first_read_s.append(
                    snap.first_read_at - snap.published_at)

    # ----------------------------------------------------------------- read
    def latest(self) -> Snapshot:
        """The newest published generation (no pin — the snapshot object
        stays valid even if evicted, but new reads should re-fetch)."""
        with self._lock:
            if self.generation < 0:
                raise LookupError("no generation published yet")
            return self._snaps[self.generation]

    def get(self, generation: int) -> Snapshot:
        with self._lock:
            snap = self._snaps.get(generation)
        if snap is None:
            raise LookupError(
                f"generation {generation} is not retained (newest is "
                f"{self.generation}, retain={self.retain}) — pin "
                "generations you need across publishes")
        return snap

    def pin(self, generation: int | None = None) -> Snapshot:
        """Pin a generation (default: newest) against eviction.

        Every pin must be matched by a :meth:`release`; a pinned
        generation survives arbitrarily many later publishes, so a
        reader can issue a multi-query, multi-view session against one
        consistent state while the stream advances.
        """
        with self._lock:
            g = self.generation if generation is None else int(generation)
            snap = self._snaps.get(g)
            if snap is None:
                raise LookupError(
                    f"generation {g} is not retained (newest is "
                    f"{self.generation})")
            self._pins[g] = self._pins.get(g, 0) + 1
            return snap

    def release(self, generation: int) -> None:
        with self._lock:
            g = int(generation)
            n = self._pins.get(g, 0)
            if n <= 1:
                self._pins.pop(g, None)
            else:
                self._pins[g] = n - 1
            self._evict_locked()

    def note_read(self, snap: Snapshot) -> None:
        """Record the first read against a generation (publish-to-first-
        read latency telemetry)."""
        if snap.first_read_at is None:
            snap.first_read_at = time.perf_counter()

    # ------------------------------------------------------------ telemetry
    def stats(self) -> dict:
        with self._lock:
            lat = list(self._first_read_s)
            lat += [s.first_read_at - s.published_at
                    for s in self._snaps.values()
                    if s.first_read_at is not None]
            return dict(
                generation=self.generation,
                publishes=self.publishes,
                retained=len(self._snaps),
                pinned={g: n for g, n in self._pins.items()},
                publish_s=self.last_publish_seconds,
                publish_to_first_read_s=(
                    sorted(lat)[len(lat) // 2] if lat else None),
            )
