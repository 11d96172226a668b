"""Durable IVM engine snapshots for the stream executor (DESIGN.md §10).

A snapshot is the engine's *canonical state* — every dense view plane,
every hashed-COO key table and payload plane (zombie slots and all, so
occupancy budgets survive the round-trip), stored base relations, and
indicator planes — plus a manifest ``meta`` carrying what leaf arrays
alone cannot reconstruct:

* ``offset``   — how many stream updates the snapshot has fully applied
  (the replay cursor: ``StreamExecutor.resume`` skips exactly this many),
* ``segment``  — the boundary index that produced the save (telemetry),
* ``layouts``  — per-view physical layout (``storage.export_layout``);
  sparse capacities are leaf *shapes*, not pytree aux, so the restore
  template must be rebuilt to the checkpointed capacity or every leaf
  shape check fails,
* ``storage_sig`` — the ``plan.storage_signature`` fingerprint of the
  snapshot; restoring changes the engine's storage signature, which is
  exactly the :class:`repro.core.plan.PlanCache` key component that makes
  stale compiled plans unreachable (no explicit invalidation needed).

Checkpoints are written at segment boundaries, asynchronously: the state
handed to the writer is a fresh device copy (``jnp.copy`` dispatches
without a host sync), because the next segment's compiled program
*donates* the original buffers — by the time the writer's device→host
transfer runs, the originals may already be deleted.  The copy waits on
the producing segment inside XLA's dependency graph, so the main thread
never blocks; commit atomicity and writer-error surfacing live in
:class:`repro.checkpoint.checkpointer.Checkpointer`.

Restores are layout-aware and mesh-agnostic: leaves are logical arrays,
so a run killed on a 4-device mesh restores onto 1 or 2 (the executor
re-derives its :class:`ShardPlan` for the current devices and re-places
the state).  A torn or corrupt newest step falls back to the previous
committed one.
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

from repro.core import plan as plan_mod
from repro.core import storage as storage_mod
from repro.core.ivm import canonical_state

from .checkpointer import CORRUPTION_ERRORS, Checkpointer

log = logging.getLogger("repro.checkpoint")


class StreamCheckpointer:
    """Segment-boundary engine snapshots over a :class:`Checkpointer`.

    ``segment_updates`` additionally caps how many stream updates run
    between boundaries: capacity segmentation only splits where a sparse
    table must grow, which on a dense-only or generously-sized engine is
    *never* — a durability knob must not depend on storage pressure.
    ``None`` checkpoints only at capacity boundaries (plus the final
    state)."""

    def __init__(self, directory: str, keep: int = 3,
                 segment_updates: int | None = None):
        self.ckpt = Checkpointer(directory, keep=keep)
        if segment_updates is not None and segment_updates < 1:
            raise ValueError("segment_updates must be >= 1")
        self.segment_updates = segment_updates

    # ------------------------------------------------------------------ save
    def save_boundary(self, engine, offset: int, segment: int,
                      blocking: bool = False,
                      view_copies: dict | None = None) -> None:
        """Snapshot ``engine`` as having applied ``offset`` stream updates.

        Async by default: hands the writer thread fresh device copies
        (the caller is about to donate the originals to the next
        segment's program) and returns without a host sync.

        ``view_copies`` are already-dispatched device copies of (some
        of) the engine's views — the serving plane's registry publishes
        generation-stamped copies at the same boundary, and a boundary
        that both publishes and checkpoints must not copy each view
        twice: the executor passes the registry's stamped copies here
        and only the remaining leaves (unserved views, base relations,
        indicators) are copied fresh."""
        state = engine.canonical_state()
        meta = {
            "offset": int(offset),
            "segment": int(segment),
            "layouts": {name: storage_mod.export_layout(v)
                        for name, v in engine.views.items()},
            "storage_sig": [list(entry) for entry in
                            plan_mod.storage_signature(engine.views)],
        }
        if blocking:
            self.ckpt.save(state, step=int(offset), blocking=True,
                           meta=meta, sync_copy=True)
        else:
            if view_copies:
                views, base, indicators = state
                views = {n: (view_copies[n] if n in view_copies
                             else jax.tree.map(jnp.copy, v))
                         for n, v in views.items()}
                copies = canonical_state(
                    (views, jax.tree.map(jnp.copy, base),
                     jax.tree.map(jnp.copy, indicators)))
            else:
                copies = jax.tree.map(jnp.copy, state)
            self.ckpt.save(copies, step=int(offset), blocking=False,
                           meta=meta, sync_copy=False)

    def wait(self) -> None:
        """Block until the pending boundary save committed (re-raising a
        writer failure — see ``Checkpointer.wait``)."""
        self.ckpt.wait()

    # -------------------------------------------------------------- telemetry
    @property
    def write_seconds(self) -> float:
        """Cumulative writer wall seconds across committed saves."""
        return self.ckpt.total_write_seconds

    @property
    def saves_committed(self) -> int:
        return self.ckpt.saves_committed

    # --------------------------------------------------------------- restore
    def latest_offset(self) -> int | None:
        """Stream offset of the newest committed snapshot, or None."""
        steps = self.ckpt.all_steps()
        return steps[-1] if steps else None

    def restore_into(self, engine) -> dict | None:
        """Restore the newest *readable* snapshot into ``engine``.

        The restore template is rebuilt per step from the manifest's
        ``layouts`` (the engine's live capacities — or even backends —
        need not match the checkpoint's).  A step whose manifest or
        leaves are torn, fail the checksum, or mismatch the snapshot's
        *own* layout manifest is quarantined (``corrupt_step_*`` — out of
        the restorable set and the ``keep=`` retention count) and the
        restore falls back to the previous committed step.  Returns the
        restored step's ``meta`` (offset/segment/layouts), or None when
        nothing is restorable; leaves arrive unsharded — a mesh-aware
        caller re-places them (mesh-elastic)."""
        for step in reversed(self.ckpt.all_steps()):
            try:
                meta = self.ckpt.read_meta(step)
                layouts = meta["layouts"]
                views_t = {
                    name: storage_mod.layout_template(v, layouts[name])
                    for name, v in engine.views.items()
                }
                template = canonical_state(
                    (views_t, engine.base, engine.indicators))
                state = self.ckpt.restore(template, step)
            except CORRUPTION_ERRORS + (AssertionError,) as e:
                # the template came from the snapshot's own manifest, so
                # a leaf-shape assertion here is self-inconsistency of
                # the snapshot — corruption, not a caller mismatch
                log.warning(
                    "snapshot step %d unreadable (%r); quarantining and "
                    "falling back to the previous committed step", step, e)
                self.ckpt.quarantine_step(step)
                continue
            except Exception as e:  # noqa: BLE001 — fall back to older step
                log.warning(
                    "snapshot step %d unreadable (%r); falling back to the "
                    "previous committed step", step, e)
                continue
            engine.set_state(state)
            # restoring may change capacities → storage signature → the
            # PlanCache key: stale plans become unreachable automatically
            got = [list(entry)
                   for entry in plan_mod.storage_signature(engine.views)]
            assert got == meta["storage_sig"], (
                "restored storage signature diverges from the snapshot "
                "fingerprint — layout template bug")
            return meta
        return None
