"""ViewServer: the consumer-facing front end of the serving plane.

``ViewServer(executor, views=...)`` attaches a :class:`~repro.serve.
registry.SnapshotRegistry` to a :class:`~repro.core.stream.
StreamExecutor` (the executor publishes at every segment boundary from
then on) and answers batched point / range / top-k lookups against the
published generations while segments execute.

Request discipline (sync-free batching):

* every lookup is *batched* — callers hand whole key batches, the
  server pads them to the next power of two (bounding the jit cache to
  one compilation per size class per view layout) and slices the pad
  back off;
* results are **device-resident** :class:`ReadResult` objects; nothing
  in the request path blocks on a device→host transfer.  Materialize
  explicitly with ``ReadResult.host()`` — the serving analogue of the
  storage layer's ``payload_sync`` discipline (the sync-guard test's
  rule: the hot path never syncs implicitly);
* multi-query consistency comes from generation pinning: ``with
  server.pin() as snap:`` answers every lookup inside the block against
  one generation of *every* view, no matter how many segments the
  stream completes meanwhile.

Staleness telemetry rides in :meth:`ViewServer.stats`: current
generation, generation lag of the last unpinned read, publish-to-first-
read latency, and the executor's per-segment pipeline stats
(admit/dispatch/publish walls, straggler verdicts).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp

from repro.core.storage import next_pow2
from repro.runtime import tracing

from . import lookup as lookup_mod
from .registry import Snapshot, SnapshotRegistry

#: smallest padded batch — tiny interactive lookups share one compilation
MIN_BATCH = 8


@dataclasses.dataclass
class ReadResult:
    """Device-resident lookup result, stamped with its generation."""

    view: str
    kind: str  # "point" | "range_sum" | "range_scan" | "top_k"
    generation: int
    data: Any  # pytree of device arrays

    def host(self):
        """Explicit device→host materialization (the only sync)."""
        with tracing.span("fivm.read.host"):
            return jax.device_get(self.data)


class PinnedGeneration:
    """Context manager binding lookups to one pinned generation."""

    def __init__(self, server: "ViewServer", snap: Snapshot):
        self._server = server
        self._snap = snap
        self._released = False

    @property
    def generation(self) -> int:
        return self._snap.generation

    @property
    def offset(self) -> int:
        return self._snap.offset

    def point(self, view: str, keys, **kw) -> ReadResult:
        return self._server.point(view, keys, snapshot=self._snap, **kw)

    def range_sum(self, view: str, lo, hi) -> ReadResult:
        return self._server.range_sum(view, lo, hi, snapshot=self._snap)

    def range_scan(self, view: str, lo, hi, k: int) -> ReadResult:
        return self._server.range_scan(view, lo, hi, k,
                                       snapshot=self._snap)

    def top_k(self, view: str, k: int, **kw) -> ReadResult:
        return self._server.top_k(view, k, snapshot=self._snap, **kw)

    def release(self) -> None:
        if not self._released:
            self._released = True
            self._server.registry.release(self._snap.generation)

    def __enter__(self) -> "PinnedGeneration":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class ViewServer:
    """Serve point/range/top-k lookups against a maintained hierarchy.

    ``executor`` is a :class:`StreamExecutor`; attaching the server sets
    ``executor.registry`` so every subsequent segmented run publishes a
    generation per boundary (and ``segment_updates`` caps boundary
    spacing like the checkpointer's knob).  The engine's *current* state
    is published immediately as the bootstrap generation
    (``offset=bootstrap_offset``), so reads work before any stream runs.
    ``views`` restricts serving (and snapshot copies) to a subset of
    the hierarchy.
    """

    def __init__(self, executor, views: Sequence[str] | None = None,
                 retain: int = 2, segment_updates: int | None = None,
                 registry: SnapshotRegistry | None = None,
                 bootstrap_offset: int = 0):
        self.executor = executor
        self.engine = executor.engine
        if views is not None:
            missing = sorted(set(views) - set(self.engine.views))
            assert not missing, f"unknown views: {missing}"
        self.registry = registry if registry is not None else \
            SnapshotRegistry(retain=retain,
                             segment_updates=segment_updates, views=views)
        executor.registry = self.registry
        self.registry.publish(self.engine.views, offset=bootstrap_offset,
                              segment=-1, meta=dict(bootstrap=True))
        #: generation of the most recent unpinned read (staleness lag)
        self._last_read_generation: int = self.registry.generation

    # ----------------------------------------------------------- snapshots
    def pin(self, generation: int | None = None) -> PinnedGeneration:
        """Pin a generation (default newest) for multi-query reads."""
        with tracing.span("fivm.read.pin"):
            return PinnedGeneration(self, self.registry.pin(generation))

    def _resolve(self, snapshot: Snapshot | None,
                 generation: int | None) -> Snapshot:
        if snapshot is not None:
            return snapshot
        snap = (self.registry.latest() if generation is None
                else self.registry.get(generation))
        self._last_read_generation = snap.generation
        return snap

    def _view(self, snap: Snapshot, name: str):
        view = snap.views.get(name)
        assert view is not None, (
            f"view {name!r} is not served (registry publishes "
            f"{sorted(snap.views)})")
        self.registry.note_read(snap)
        return view

    @staticmethod
    def _pad_keys(keys) -> tuple[jnp.ndarray, int]:
        keys = jnp.asarray(keys, jnp.int32)
        if keys.ndim == 1:
            keys = keys[:, None]
        b = keys.shape[0]
        padded = max(MIN_BATCH, next_pow2(b))
        if padded != b:
            pad = jnp.full((padded - b, keys.shape[1]), -1, jnp.int32)
            keys = jnp.concatenate([keys, pad], axis=0)
        return keys, b

    # ------------------------------------------------------------- lookups
    def point(self, view: str, keys, *, generation: int | None = None,
              snapshot: Snapshot | None = None) -> ReadResult:
        """Batched point lookup; absent keys read ring zero."""
        with tracing.span("fivm.read"):
            snap = self._resolve(snapshot, generation)
            v = self._view(snap, view)
            padded, b = self._pad_keys(keys)
            out = lookup_mod.point(v, padded)
            data = {c: arr[:b] for c, arr in out.items()}
            return ReadResult(view, "point", snap.generation, data)

    def range_sum(self, view: str, lo, hi, *,
                  generation: int | None = None,
                  snapshot: Snapshot | None = None) -> ReadResult:
        """⊕ over linearized key ids in [lo, hi)."""
        with tracing.span("fivm.read"):
            snap = self._resolve(snapshot, generation)
            v = self._view(snap, view)
            data = lookup_mod.range_sum(v, jnp.int32(lo), jnp.int32(hi))
            return ReadResult(view, "range_sum", snap.generation, data)

    def range_scan(self, view: str, lo, hi, k: int, *,
                   generation: int | None = None,
                   snapshot: Snapshot | None = None) -> ReadResult:
        """First ``k`` live keys in [lo, hi), ascending linearized order:
        data = dict(keys=[k, nk], payload={comp: [k, *shp]}, valid=[k])."""
        with tracing.span("fivm.read"):
            snap = self._resolve(snapshot, generation)
            v = self._view(snap, view)
            keys, payload, valid = lookup_mod.range_scan(
                v, jnp.int32(lo), jnp.int32(hi), int(k))
            return ReadResult(view, "range_scan", snap.generation,
                              dict(keys=keys, payload=payload, valid=valid))

    def top_k(self, view: str, k: int, *, component: str | None = None,
              index: tuple = (), generation: int | None = None,
              snapshot: Snapshot | None = None) -> ReadResult:
        """Top-``k`` live keys by one payload-plane entry: data =
        dict(keys=[k, nk], values=[k], valid=[k])."""
        with tracing.span("fivm.read"):
            snap = self._resolve(snapshot, generation)
            v = self._view(snap, view)
            keys, values, valid = lookup_mod.top_k(
                v, int(k), component=component, index=tuple(index))
            return ReadResult(view, "top_k", snap.generation,
                              dict(keys=keys, values=values, valid=valid))

    # ----------------------------------------------------------- telemetry
    def stats(self) -> dict:
        """Serving-plane health: registry generation/staleness telemetry
        plus the executor's per-segment pipeline stats (schema pinned by
        tests/test_serve.py::test_viewserver_stats_schema)."""
        reg = self.registry.stats()
        return dict(
            generation=reg["generation"],
            publishes=reg["publishes"],
            retained=reg["retained"],
            pinned=reg["pinned"],
            publish_s=reg["publish_s"],
            publish_to_first_read_s=reg["publish_to_first_read_s"],
            generation_lag=reg["generation"] - self._last_read_generation,
            last_segment_stats=list(self.executor.last_segment_stats),
            straggler_baseline=self.executor.stragglers.baseline,
        )
