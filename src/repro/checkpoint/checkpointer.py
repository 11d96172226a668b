"""Sharded, mesh-elastic checkpointing with async writes and atomic commit.

Layout: one directory per step containing
    manifest.json      — pytree structure, leaf shapes/dtypes, step, meta
    leaf_<i>.npy       — one file per leaf (logical, unsharded array)

Design points for the 1000+-node regime:
  * **Mesh-elastic**: leaves are stored as *logical* arrays; restore
    re-shards onto whatever mesh/shardings the restoring job uses — a run
    can restart on a different pod count after a failure (elastic scaling).
  * **Atomic commit**: writes go to ``<dir>.tmp`` and are renamed only
    after fsync — a job killed mid-save never corrupts the latest
    checkpoint; ``restore_latest`` picks the newest *committed* step.
  * **Async**: ``save(..., blocking=False)`` hands the work to a writer
    thread so the TPU step loop is not blocked by the filesystem.  With
    ``sync_copy=True`` (default) the device→host copy happens on the
    calling thread — the caller may donate or mutate its buffers as soon
    as ``save`` returns.  ``sync_copy=False`` moves the device→host
    transfer into the writer thread too, so the caller never blocks on
    in-flight device computation; the caller then *must* hand over buffers
    it will not donate or overwrite (the stream checkpointer passes fresh
    device copies — see ``repro.checkpoint.stream_state``).
  * **Failure transparency**: an exception in the writer thread (disk
    full, injected fault) is captured and re-raised on the next
    ``wait()``/``save()`` — an async save can never silently *not* commit
    while the caller keeps running as if it had.  Stale ``*.tmp``
    directories from a previous crashed process are swept on
    ``__init__``.
  * On a real multi-host pod each host writes its addressable shards and
    the manifest records the global shape (single-process here; the format
    already stores logical arrays so the multi-host writer only changes
    the gather step).

The IVM stream executor's durable snapshots build on this file format
with layout-aware templates (``repro.checkpoint.stream_state``).
"""
from __future__ import annotations

import json
import logging
import os
import shutil
import threading
import zlib
from typing import Any

import jax
import numpy as np

from repro.runtime import faults, tracing

log = logging.getLogger("repro.checkpoint")


class ChecksumError(RuntimeError):
    """A leaf file's content does not match its manifest fingerprint —
    the snapshot was corrupted *after* commit (bit rot, torn sector)."""


#: error classes that mean "this snapshot directory is damaged" (as
#: opposed to "the caller passed an incompatible template"): these are
#: the classes :meth:`Checkpointer.restore_latest` and the stream
#: checkpointer quarantine on, so retention (`keep=`) only ever counts
#: restorable snapshots
CORRUPTION_ERRORS = (ChecksumError, OSError, EOFError, ValueError, KeyError)


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3,
                 verify_checksums: bool = True):
        self.directory = directory
        self.keep = keep
        #: verify per-leaf crc32 fingerprints on restore (DESIGN.md §11);
        #: manifests without fingerprints (older snapshots) restore as
        #: before — the check is backward compatible
        self.verify_checksums = verify_checksums
        os.makedirs(directory, exist_ok=True)
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None
        #: wall seconds of the completed ``_write``s (device→host
        #: transfer included when ``sync_copy=False``) — the BENCH_stream
        #: checkpointing-leg telemetry
        self.total_write_seconds: float = 0.0
        self.saves_committed: int = 0
        #: steps quarantined (renamed ``corrupt_step_*``) this process —
        #: integrity telemetry for tests and the supervisor
        self.quarantined: list[int] = []
        # sweep torn writes of a previous process: a ``*.tmp`` directory
        # is by construction uncommitted (the rename is the commit), and
        # a ``corrupt_step_*`` directory was already diagnosed unreadable
        for name in os.listdir(directory):
            if name.endswith(".tmp") or name.startswith("corrupt_step_"):
                log.warning("sweeping stale checkpoint dir %s", name)
                shutil.rmtree(os.path.join(directory, name),
                              ignore_errors=True)

    # ------------------------------------------------------------------ save
    def save(self, tree: Any, step: int, blocking: bool = True,
             meta: dict | None = None, sync_copy: bool = True) -> None:
        """Write ``tree`` as step ``step``.  ``meta`` (JSON-serializable)
        is stored in the manifest and read back via :meth:`read_meta`.
        See the module docstring for the ``blocking`` × ``sync_copy``
        contract; a pending async failure re-raises here first."""
        self.wait()  # serialize with (and surface errors of) a prior save
        leaves, treedef = jax.tree.flatten(tree)
        if sync_copy:
            leaves = [np.asarray(x) for x in leaves]  # device -> host copy
        if blocking:
            self._write(leaves, str(treedef), step, meta)
        else:
            self._thread = threading.Thread(
                target=self._write_guarded,
                args=(leaves, str(treedef), step, meta))
            self._thread.start()

    def wait(self) -> None:
        """Join a pending async save; re-raise its failure if it had one."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def discard_pending(self) -> None:
        """Join a pending async save and swallow its failure — the
        recovery path's entry point: an interrupted run may have died
        with a save in flight, and recovery restarts from the last
        *committed* step regardless of how that save ended."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self._error = None

    def _write_guarded(self, host_leaves, treedef_str, step, meta) -> None:
        try:
            self._write(host_leaves, treedef_str, step, meta)
        except BaseException as e:  # noqa: BLE001 — surfaced on next wait()
            self._error = e

    def _write(self, leaves, treedef_str: str, step: int,
               meta: dict | None = None) -> None:
        with tracing.span("fivm.checkpoint.write") as write:
            # device -> host copy (no-op for host arrays): on the writer
            # thread this is where an async save blocks on in-flight
            # device computation instead of the caller doing so
            host_leaves = [np.asarray(x) for x in leaves]
            final = os.path.join(self.directory, f"step_{step:08d}")
            tmp = final + ".tmp"
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            manifest = {
                "step": step,
                "n_leaves": len(host_leaves),
                "treedef": treedef_str,
                # per-leaf content fingerprint: restore re-hashes each leaf
                # file and refuses a snapshot whose bytes changed after
                # commit — the atomic rename protects against torn writes,
                # the crc32 against silent post-commit corruption
                "leaves": [{"shape": list(x.shape), "dtype": str(x.dtype),
                            "crc32": zlib.crc32(np.ascontiguousarray(x)
                                                .tobytes()) & 0xFFFFFFFF}
                           for x in host_leaves],
                "meta": meta or {},
            }
            for i, x in enumerate(host_leaves):
                np.save(os.path.join(tmp, f"leaf_{i}.npy"), x)
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            # a kill between here and the rename must leave the newest
            # *committed* step untouched (the chaos suite injects this)
            faults.crossing("mid_checkpoint_write", step=step)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)  # atomic commit
            # bit-flip fault point: the snapshot is durable and
            # GC-visible — a "bitflip" plan corrupts it here, post-commit
            faults.crossing("snapshot_committed", step=step,
                            path=os.path.join(final, "leaf_0.npy"))
        self.total_write_seconds += write.wall
        self.saves_committed += 1
        self._gc()

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"),
                          ignore_errors=True)

    # --------------------------------------------------------------- restore
    def all_steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and not name.endswith(".tmp"):
                if os.path.exists(os.path.join(self.directory, name,
                                               "manifest.json")):
                    out.append(int(name[5:]))
        return sorted(out)

    def read_manifest(self, step: int) -> dict:
        d = os.path.join(self.directory, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            return json.load(f)

    def read_meta(self, step: int) -> dict:
        return self.read_manifest(step).get("meta", {})

    def restore(self, template: Any, step: int, shardings: Any = None):
        """Restore into the structure of ``template``; if ``shardings`` is
        given (pytree of NamedSharding), leaves are placed sharded — this is
        the mesh-elastic path (any mesh, any partitioning)."""
        manifest = self.read_manifest(step)
        d = os.path.join(self.directory, f"step_{step:08d}")
        t_leaves, treedef = jax.tree.flatten(template)
        assert manifest["n_leaves"] == len(t_leaves), (
            f"checkpoint has {manifest['n_leaves']} leaves; template has "
            f"{len(t_leaves)} — incompatible structure")
        sh_leaves = (treedef.flatten_up_to(shardings)
                     if shardings is not None else [None] * len(t_leaves))
        out = []
        for i, (tl, sh) in enumerate(zip(t_leaves, sh_leaves)):
            x = np.load(os.path.join(d, f"leaf_{i}.npy"))
            if self.verify_checksums:
                want = manifest["leaves"][i].get("crc32")
                if want is not None:
                    got = zlib.crc32(np.ascontiguousarray(x)
                                     .tobytes()) & 0xFFFFFFFF
                    if got != want:
                        raise ChecksumError(
                            f"step {step} leaf_{i}.npy checksum mismatch "
                            f"(manifest {want:#010x} != content {got:#010x})"
                            " — snapshot corrupted after commit")
            assert tuple(x.shape) == tuple(tl.shape), (i, x.shape, tl.shape)
            if sh is not None:
                out.append(jax.device_put(x, sh))
            else:
                out.append(jax.numpy.asarray(x, dtype=tl.dtype))
        return jax.tree.unflatten(treedef, out)

    def quarantine_step(self, step: int) -> None:
        """Take a damaged snapshot out of the restorable set: rename
        ``step_<n>`` to ``corrupt_step_<n>`` so :meth:`all_steps` no
        longer lists it — and therefore :meth:`_gc`'s ``keep=`` retention
        only counts *restorable* snapshots (a corrupt newest step must
        not push a good old one past the retention horizon).  Falls back
        to deletion if the rename fails."""
        src = os.path.join(self.directory, f"step_{step:08d}")
        dst = os.path.join(self.directory, f"corrupt_step_{step:08d}")
        try:
            if os.path.exists(dst):
                shutil.rmtree(dst)
            os.rename(src, dst)
        except OSError:
            shutil.rmtree(src, ignore_errors=True)
        self.quarantined.append(step)
        log.warning("quarantined unrestorable checkpoint step %d", step)

    def restore_latest(self, template: Any, shardings: Any = None):
        """Restore the newest *readable* committed step.

        A truncated manifest, a missing/corrupt leaf file, or a checksum
        mismatch (a crash can tear anything that was not atomically
        committed, and disks rot) quarantines the damaged step and falls
        back to the previous committed step instead of raising
        mid-recovery; returns None when no step is restorable."""
        for step in reversed(self.all_steps()):
            try:
                return self.restore(template, step, shardings), step
            except CORRUPTION_ERRORS as e:
                log.warning("checkpoint step %d unreadable (%r); "
                            "falling back to the previous committed step",
                            step, e)
                self.quarantine_step(step)
            except Exception as e:  # noqa: BLE001 — fall back to older step
                # e.g. a template/structure mismatch: the snapshot itself
                # may be fine for another caller — skip, don't quarantine
                log.warning("checkpoint step %d not restorable into this "
                            "template (%r); falling back", step, e)
        return None
