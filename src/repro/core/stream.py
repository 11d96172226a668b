"""Fused update-stream executor: one XLA program per update stream.

The per-call trigger path (``IVMEngine.make_trigger``) pays host dispatch,
pytree flattening, and donation bookkeeping once per update batch — at small
batch sizes that overhead dominates measured throughput (ISSUE 1; the
batched-trigger execution path of the F-IVM system paper).  This module
compiles an *entire multi-relation stream* into a single program:

  1. **Bucketing** — updates are grouped by schedule position and padded to
     a per-position bucket size.  Padding rows carry key ``0`` and ring-zero
     payloads: scatter-adding ring 0 is an exact no-op, and indicator
     maintenance gates its ±1 deltas on per-row transitions, so padded rows
     are bit-transparent.
  2. **Stacking** — keys/payloads are stacked into ``[n_steps, B, ...]``
     device arrays (one host→device transfer per stream).
  3. **Dispatch** — three compiled shapes, picked by schedule structure:

     * ``scan``   — single-relation streams: ``jax.lax.scan`` over steps,
       the carry is the engine state.  The loop body is a linear dataflow
       chain, so XLA updates the donated state buffers in place.
     * ``rounds`` — (near-)periodic mixed schedules: scan over *rounds*;
       the body applies one trigger per pattern position in sequence.
       Still branch-free linear dataflow — this is the fast path for the
       paper's round-robin workloads, and each position keeps its own
       bucket size.  Schedules are canonicalized by shift-matching
       (``sched[i] == sched[i-p]``), so rotated streams and streams ending
       in a partial round compile as rounds too — the trailing partial
       round is applied once after the scan instead of forcing the whole
       stream into switch dispatch.
     * ``switch`` — aperiodic mixed schedules: scan over steps with
       ``jax.lax.switch`` over the relation id.  An HLO conditional cannot
       alias untouched carry buffers through its branches (each branch
       yields a fresh copy of everything it returns), so the state is
       partitioned into the leaves some trigger actually replaces (threaded
       through the carry and the switch) and the provably-constant rest
       (passed as a non-donated loop invariant).  The partition derives
       from the embedded trigger plans' write-sets
       (``plan.state_write_mask``) — the plan is the authority on what a
       trigger replaces.

Since the trigger-plan refactor (DESIGN.md §8) every dispatch mode is
generated from the same compiled :class:`repro.core.plan.TriggerPlan`
objects the eager path executes: ``prepare_stream`` fetches one plan per
schedule position from the engine's plan cache and embeds them in the
:class:`PreparedStream`; ``_build`` replays those plans inside the scan /
rounds / switch bodies.  Rounds bodies additionally apply plan-level CSE:
sibling gather planes shared by several positions' plans (and written by
none) are computed once per step (``plan.shared_prep_ops``).

Every trigger body emits the canonical state signature
(``ivm.canonical_state``), which is what lets one scan carry serve all
relations' triggers.  The state is donated at the jit boundary, so a whole
stream executes with exactly one dispatch and no per-step host round-trip.
The per-call trigger path is kept as the correctness oracle
(tests/test_stream.py).

Mixed view storage threads through unchanged: a hashed-COO
``SparseRelation`` (repro.core.storage) is a registered pytree whose table
and payload plane ride in the carry next to dense views — its capacity is
part of the (static) state signature, so sparse tables never grow inside a
compiled stream.  A raw stream whose worst-case insert budget would cross
the load-factor bound mid-run is split into **segments**: between segments
the affected tables rehash to a larger capacity and the remainder is
re-prepared (plans recompile against the new storage layout) instead of
silently dropping rows.  ``prepare_stream`` itself audits the same budget
(:func:`check_stream_capacity`) and refuses to prepare a stream that could
overflow — a directly-prepared stream bypasses segmentation, and the
failure it would otherwise hit is a *silent* row drop.  The segment loop
runs as a two-deep pipeline: segment i+1's admission (rehash dispatch,
bucketing, host→device stacking, plan fetch) is issued with segment i
still executing, intermediate segments donate their carry, and the host
never blocks between segments — the overlap is bounded by device-side
execution time (see ``_run_segmented``).

Multi-device execution (DESIGN.md §9): construct the executor with a
``repro.core.shard.ShardPlan`` and the scan carry partitions across the
plan's mesh — sharded views split their key/slot axis per device, the scan
body re-asserts the planned shardings each step, and GSPMD materializes
the plan's collectives at cross-shard read sites.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import faults, tracing
from repro.runtime.fault_tolerance import StragglerMonitor

from . import plan as plan_mod
from . import storage as storage_mod
from .ivm import IVMEngine, canonical_state
from .relations import COOUpdate

#: longest schedule period compiled as an unrolled rounds-scan body; longer
#: periods fall back to switch dispatch to bound compile time
MAX_ROUNDS_PERIOD = 16


@dataclasses.dataclass
class PreparedStream:
    """A bucketed, stacked, device-resident update stream."""

    mode: str  # "scan" | "rounds" | "switch"
    rel_order: tuple[str, ...]  # distinct relations in first-seen order
    schemas: tuple[tuple[str, ...], ...]  # per-rel_order COO schemas
    pattern: tuple[str, ...]  # per-position relations ("rounds": one round)
    xs: Any  # pytree of stacked arrays, leading dim = n_steps / n_rounds
    n_steps: int
    buckets: tuple[int, ...]  # padded batch size per pattern position
    n_tuples: int  # true (unpadded) tuple count across the stream
    tail: Any = ()  # per-position (keys, payload) of the trailing partial round
    tail_len: int = 0
    #: embedded trigger plans: per pattern position (scan/rounds) or per
    #: rel_order entry (switch) — the same compiled plans the eager path
    #: executes, fetched from the engine's plan cache at prepare time
    plans: tuple = ()
    #: storage layout the plans were compiled against
    #: (``plan.storage_signature`` of the engine views at prepare time)
    storage_sig: tuple = ()
    #: scatter-backend override active at prepare time (plans bake the
    #: resolved backends in)
    backend_sig: str | None = None
    #: plan-fusion mode active at prepare time (fused plans embed
    #: FusedChain ops; a mode flip must re-prepare, not replay)
    fusion_sig: str | None = None
    #: mesh-replicated (xs, tail) cache of a sharded executor — the
    #: original xs/tail stay untouched so the same prepared stream can
    #: also feed an unsharded executor
    placed: Any = None

    @property
    def signature(self):
        """Compilation cache key: everything the traced program depends on.
        Includes the storage layout and the scatter-backend override — a
        stream prepared after a rehash (or under a different
        ``use_backend`` scope) embeds plans compiled for that layout /
        backend and must not replay a program built around another."""
        return (self.mode, self.rel_order, self.schemas, self.pattern,
                self.n_steps, self.buckets, self.tail_len, self.storage_sig,
                self.backend_sig, self.fusion_sig)


def _schedule_period(sched: Sequence[str]) -> int | None:
    """Smallest period p ≤ MAX_ROUNDS_PERIOD with sched[i] == sched[i - p]
    for every i ≥ p; None if the schedule is aperiodic.

    This is schedule canonicalization by shift-matching: the canonical
    pattern is simply the first p positions, so rotated round-robin streams
    (a stream that starts mid-round) and near-periodic streams with a
    trailing partial round all canonicalize to (pattern, n_full_rounds,
    tail) instead of falling back to switch dispatch.  A period must
    actually repeat (≥ 2 full rounds) — otherwise every stream would
    trivially "tile" once and the rounds body would unroll the whole
    stream; p == 1 (single relation) is always a real period."""
    T = len(sched)
    for p in range(1, min(MAX_ROUNDS_PERIOD, T) + 1):
        if p > 1 and T // p < 2:
            break
        if all(sched[i] == sched[i - p] for i in range(p, T)):
            return p
    return None


class StreamCapacityError(RuntimeError):
    """A stream prepared as one compiled program could overflow a sparse
    view's hash table.  Capacities are static inside a compiled stream,
    and an overflowing insert *silently drops its row* — run the raw
    stream through ``StreamExecutor.run(stream)`` instead: the raw path
    splits it into capacity segments with rehash + plan recompile between
    them."""


def check_stream_capacity(engine: IVMEngine, stream, views=None) -> None:
    """Worst-case insert-budget audit for a stream compiled as one
    program; raises :class:`StreamCapacityError` when any sparse view
    could cross the load-factor bound.

    The model is the capacity-segmentation budget, tightened per (view,
    relation) from per-batch row counts to the number of *distinct*
    projected update keys across the whole stream (a host-side read of
    the update batches — admission-time cost, never on the replay path):
    inserts into a view are bounded by distinct bound-key combinations ×
    the unbound-domain extent, clamped to the view's domain product.
    Occupancy counts zombie slots (``num_slots_used_sync``): deletes keep
    their slot until a rehash compacts them, and a compiled stream never
    rehashes.  Tables whose capacity covers their domain product are
    skipped — they can never overflow.

    ``views`` overrides the state the stream will actually run against
    (occupancy and capacities are read off it); default: the engine's
    own views.  ``StreamExecutor.run`` passes the caller's explicit
    state here — auditing the engine while executing against a fuller
    (or fresher) caller state would miss the very overflow the audit
    exists to catch.
    """
    views = engine.views if views is None else views
    caps: dict[str, tuple] = {}
    for name, v in views.items():
        if not isinstance(v, storage_mod.SparseRelation):
            continue
        dom_prod = storage_mod.comp_width(v.domains)
        if v.capacity >= storage_mod.next_pow2(dom_prod):
            continue
        caps[name] = (v, v.num_slots_used_sync(), dom_prod)
    if not caps:
        return
    by_rel: dict[str, list[COOUpdate]] = {}
    for rel, upd in stream:
        by_rel.setdefault(rel, []).append(upd)
    rel_keys = {rel: np.concatenate([np.asarray(u.keys) for u in upds])
                for rel, upds in by_rel.items()}
    offenders = []
    for name, (v, occ, dom_prod) in caps.items():
        budget = 0
        for rel, upds in by_rel.items():
            wv, _, _ = engine.plans.write_sets(engine, rel)
            if name not in wv:
                continue
            sch = tuple(upds[0].schema)
            extra = 1
            for var in v.schema:
                if var not in sch:
                    extra *= int(v.domain_of(var))
            cols = [sch.index(var) for var in v.schema if var in sch]
            if cols:
                distinct = np.unique(rel_keys[rel][:, cols], axis=0).shape[0]
            else:
                distinct = 1
            budget += min(distinct * extra, dom_prod)
        budget = min(budget, dom_prod)
        if occ + budget > storage_mod.LOAD_FACTOR * v.capacity:
            offenders.append(
                f"{name}: {occ} occupied + worst-case {budget} inserts > "
                f"{storage_mod.LOAD_FACTOR:.0%} of capacity {v.capacity}")
    if offenders:
        raise StreamCapacityError(
            "prepared stream could overflow sparse view(s) — "
            + "; ".join(offenders)
            + ".  Pass the raw stream to StreamExecutor.run() so it is "
            "split into capacity segments (rehash + recompile between "
            "them), or size the tables with more headroom "
            "(storage_opts=dict(headroom=...)).")


def capacity_segments(engine: IVMEngine, stream):
    """Split a raw stream so no sparse view's worst-case insert budget
    crosses the load-factor bound inside one prepared segment.

    Returns ``[(sub_stream, grow_caps), ...]``: ``grow_caps`` maps view
    names to the capacity they must rehash to *before* the segment
    runs.  Budgets are worst-case (B × unbound-domain product, as in
    the eager growth path) and occupancy is tracked conservatively, so
    a compiled segment can never overflow-drop; capacities stop
    growing at the domain product (such a table cannot overflow)."""
    caps: dict[str, int] = {}
    occ: dict[str, int] = {}
    full: dict[str, int] = {}
    for name, v in engine.views.items():
        if isinstance(v, storage_mod.SparseRelation):
            caps[name] = v.capacity
            occ[name] = v.num_slots_used_sync()
            full[name] = storage_mod.next_pow2(
                storage_mod.comp_width(v.domains))
    if not caps:
        return [(list(stream), {})]
    touched: dict[str, list[str]] = {}
    for rel in {r for r, _ in stream}:
        wv, _, _ = engine.plans.write_sets(engine, rel)
        touched[rel] = [n for n in wv if n in caps]

    def budget(name: str, rel: str, upd: COOUpdate) -> int:
        # the eager growth path's worst-case model, clamped to the
        # domain product (there are never more distinct keys)
        v = engine.views[name]
        return min(engine._insert_budget(v, rel, upd),
                   storage_mod.comp_width(v.domains))

    segments: list = []
    cur: list = []
    grow: dict[str, int] = {}
    for rel, upd in stream:
        need: dict[str, int] = {}
        for name in touched[rel]:
            b = budget(name, rel, upd)
            c = caps[name]
            while (c < full[name]
                   and occ[name] + b > storage_mod.LOAD_FACTOR * c):
                c *= 2
            if c != caps[name]:
                need[name] = c
        if need and cur:
            segments.append((cur, grow))
            cur, grow = [], {}
        if need:
            grow.update(need)
            caps.update(need)
        cur.append((rel, upd))
        for name in touched[rel]:
            occ[name] = min(occ[name] + budget(name, rel, upd),
                            full[name])
    segments.append((cur, grow))
    return segments


def split_segments(segments, max_updates: int | None):
    """Subdivide capacity segments so no segment spans more than
    ``max_updates`` stream updates — the durability knob: capacity
    segmentation only splits where a sparse table must grow, which on a
    dense-only (or generously-sized) engine is never, so a checkpointed
    run caps boundary spacing independently of storage pressure.  The
    pre-segment rehash (``grow_caps``) stays attached to the first
    chunk."""
    if max_updates is None:
        return segments
    out = []
    for sub, grow in segments:
        for lo in range(0, len(sub), max_updates):
            out.append((sub[lo:lo + max_updates], grow if lo == 0 else {}))
    return out


def prepare_stream(
    engine: IVMEngine, stream: Sequence[tuple[str, COOUpdate]],
    check_capacity: bool = True,
) -> PreparedStream:
    """Bucket, pad, and stack a ``[(rel, COOUpdate), ...]`` stream, and
    fetch the trigger plan of every schedule position from the engine's
    plan cache (compiled once per (relation, schema, bucket, storage
    layout); replayed streams hit the cache).

    ``check_capacity`` (default on) runs :func:`check_stream_capacity`
    first: a prepared stream bypasses raw-run segmentation, so a sparse
    view that could cross its load-factor bound must fail loudly here
    rather than silently overflow-drop rows mid-program.  The segmented
    runner passes ``False`` — its segments are budgeted already."""
    assert stream, "empty update stream"
    if check_capacity:
        check_stream_capacity(engine, list(stream))
    ring = engine.query.ring
    sched = [rel for rel, _ in stream]
    rel_order = tuple(dict.fromkeys(sched))
    schemas: dict[str, tuple[str, ...]] = {}
    for rel, upd in stream:
        assert isinstance(upd, COOUpdate), (
            "the fused executor takes COO streams; factorized updates go "
            "through the per-call path")
        sch = tuple(upd.schema)
        assert schemas.setdefault(rel, sch) == sch, (
            f"inconsistent update schemas for {rel}")
    n_tuples = sum(upd.batch for _, upd in stream)
    comp_names = tuple(ring.components)
    storage_sig = plan_mod.storage_signature(engine.views)
    backend_sig = plan_mod.active_backend_override()
    fusion_sig = plan_mod.fusion_mode()

    def plan_for(rel: str, bucket: int):
        return engine.plans.lookup_sig(
            engine, rel, ("coo", schemas[rel], bucket))

    def verified(plans: tuple):
        """Step-level static race check (DESIGN.md §14, rule
        race/memo-write): the CSE memo a fused step builds once must not
        name a view any plan in the step writes.  Rides stream
        preparation, not replay — compiled programs re-run free."""
        from repro.analysis import verifier as verifier_mod

        if verifier_mod.verify_mode() == "on":
            verifier_mod.check_step(plans)
        return plans

    def stack(upds: list[COOUpdate], bucket: int):
        padded = [u.pad_to(ring, bucket) for u in upds]
        keys = jnp.stack([u.keys for u in padded])  # [n, B, k]
        payload = {c: jnp.stack([u.payload[c] for u in padded])
                   for c in comp_names}
        return keys, payload

    period = _schedule_period(sched)
    if period is not None:
        # "scan" (single relation, period 1) or "rounds" (periodic pattern):
        # per-position buckets, xs = tuple of per-position stacks.  A
        # near-periodic schedule leaves a trailing partial round: its
        # updates ride along per position (sharing the position's bucket)
        # and the compiled program applies them once after the rounds scan.
        pattern = tuple(sched[:period])
        with tracing.span("fivm.admit.stack"):
            cols = [[u for (r, u) in stream[j::period]]
                    for j in range(period)]
            n_full = len(stream) // period
            tail_len = len(stream) % period
            buckets = tuple(max(u.batch for u in col) for col in cols)
            xs = tuple(stack(col[:n_full], b)
                       for col, b in zip(cols, buckets))
            tail_upds = [cols[j][n_full].pad_to(ring, buckets[j])
                         for j in range(tail_len)]
            tail = tuple((u.keys, u.payload) for u in tail_upds)
            if period == 1:
                xs = xs[0]
        with tracing.span("fivm.admit.plans"):
            plans = verified(tuple(plan_for(r, b)
                                   for r, b in zip(pattern, buckets)))
            _count_fused_chains(plans[j % period]
                                for j in range(len(stream)))
        return PreparedStream(
            mode="scan" if period == 1 else "rounds",
            rel_order=rel_order,
            schemas=tuple(schemas[r] for r in rel_order),
            pattern=pattern,
            xs=xs,
            n_steps=n_full,
            buckets=buckets,
            n_tuples=n_tuples,
            tail=tail,
            tail_len=tail_len,
            plans=plans,
            storage_sig=storage_sig,
            backend_sig=backend_sig,
            fusion_sig=fusion_sig,
        )

    # aperiodic: uniform bucket + key width, switch over the schedule
    bucket = max(upd.batch for _, upd in stream)
    k_max = max(len(schemas[r]) for r in rel_order)
    with tracing.span("fivm.admit.stack"):
        padded = [u.pad_to(ring, bucket) for _, u in stream]
        keys = jnp.stack([
            jnp.pad(u.keys, ((0, 0), (0, k_max - u.keys.shape[1])))
            for u in padded
        ])  # [T, B, k_max]
        payload = {c: jnp.stack([u.payload[c] for u in padded])
                   for c in comp_names}
        sched_ids = jnp.asarray(np.array([rel_order.index(r) for r in sched],
                                         np.int32))
    with tracing.span("fivm.admit.plans"):
        plans = verified(tuple(plan_for(r, bucket) for r in rel_order))
        _count_fused_chains(plans[rel_order.index(r)] for r in sched)
    return PreparedStream(
        mode="switch",
        rel_order=rel_order,
        schemas=tuple(schemas[r] for r in rel_order),
        pattern=(),
        xs=(sched_ids, keys, payload),
        n_steps=len(stream),
        buckets=(bucket,),
        n_tuples=n_tuples,
        plans=plans,
        storage_sig=storage_sig,
        backend_sig=backend_sig,
        fusion_sig=fusion_sig,
    )


def _count_fused_chains(batch_plans) -> None:
    """Count the fused chains that a stream's batches run
    (``fused_chains``) and those of them lowered through the compact ⊎
    (``fused_chains_compact``), one plan per batch; a segment's stats
    entry takes both (``counts``)."""
    from repro.kernels import ring_fused

    for plan in batch_plans:
        for op in plan.ops:
            if isinstance(op, plan_mod.FusedChain):
                tracing.count("fused_chains")
                tracing.count("fused_chains_compact", int(
                    ring_fused.resolve_backend(op.ops[-1].backend)
                    in ring_fused.COMPACT_BACKENDS))


class StreamExecutor:
    """Compiles and runs fused update streams against one engine.

    Compiled programs are cached per :attr:`PreparedStream.signature`, so a
    benchmark sweep that replays same-shaped streams compiles once.

    ``shard`` (a :class:`repro.core.shard.ShardPlan`) makes the executor
    mesh-aware: input state and stream ``xs`` are placed per the plan
    (sharded views split their key/slot axis, updates replicate so every
    shard sees every row), and the scan/rounds bodies re-assert the
    planned shardings on the carry each step, so GSPMD keeps ScatterAccum
    writes routed to the owning shard and lowers cross-shard sibling
    reads to the plan's collectives.  A rehash between capacity segments
    keeps the plan valid: power-of-two capacities stay divisible by the
    mesh, so placement decisions survive growth.

    ``checkpoint`` (a :class:`repro.checkpoint.stream_state.
    StreamCheckpointer`) makes raw engine-state runs *durable*: the run
    always takes the segmented path (further subdivided by the
    checkpointer's ``segment_updates`` cap), and every segment boundary
    snapshots the engine asynchronously — the save's device copies
    dispatch while the next segment's admission proceeds, mirroring how
    admission already overlaps execution.  :meth:`resume` restores the
    newest committed snapshot and replays the stream from its offset,
    re-deriving the shard plan for the current device count
    (mesh-elastic).

    ``integrity`` (a :class:`repro.runtime.integrity.IntegrityConfig`)
    adds the runtime integrity layer (DESIGN.md §11): raw engine-state
    runs take the segmented path so every segment's updates pass
    validated admission (strict / quarantine / permissive), the audited
    Reevaluate pass runs every ``audit_interval`` boundaries, and
    capacity pressure degrades gracefully (emergency re-segmentation on
    the segmented path, eager per-batch spill on explicit-state runs)
    instead of raising :class:`StreamCapacityError`.  Validation and
    audits read device values at admission/boundary time — integrity is
    priced at segment boundaries, never inside the compiled hot loop.

    Every segmented run also feeds the per-segment wall (admit +
    dispatch) to a :class:`repro.runtime.fault_tolerance.
    StragglerMonitor`; its EWMA verdicts ride in
    :attr:`last_segment_stats` (``straggler`` / ``straggler_baseline``).
    """

    def __init__(self, engine: IVMEngine, shard=None, checkpoint=None,
                 integrity=None, stragglers: StragglerMonitor | None = None,
                 registry=None):
        self.engine = engine
        self.shard = shard
        self.checkpoint = checkpoint
        self.integrity = integrity
        #: serving-plane snapshot registry (repro.serve): when attached —
        #: usually by ``serve.ViewServer`` — every segment boundary
        #: publishes a generation-stamped device copy of the read-visible
        #: views, after the audit hook (a repaired state, never a drifted
        #: one, is what readers see) and before the next segment's
        #: donation; the boundary checkpoint reuses the same copies
        self.registry = registry
        self.stragglers = (stragglers if stragglers is not None
                           else StragglerMonitor())
        self._compiled: dict[Any, Any] = {}
        #: shared prep-op keys of the last rounds build (CSE telemetry)
        self.last_shared_ops: tuple = ()
        #: per-segment admit/dispatch/save host seconds of the last
        #: segmented run (the pipeline-overlap telemetry BENCH_stream
        #: records)
        self.last_segment_stats: list = []

    def _integrity_active(self) -> bool:
        return self.integrity is not None and self.integrity.active

    # ------------------------------------------------------- mutable leaves
    def _mutable_mask(self, prepared: PreparedStream) -> tuple[bool, ...]:
        """Per-state-leaf mask: True iff some embedded plan's write-set
        names the leaf's state entry.  Derived straight from the trigger
        plans (one compiler feeds eager, per-call, and fused execution), so
        the switch partition can never drift from what triggers write."""
        wv: set[str] = set()
        wb: set[str] = set()
        wi: set[str] = set()
        for p in prepared.plans:
            v, b, i = p.write_sets()
            wv |= set(v)
            wb |= set(b)
            wi |= set(i)
        return plan_mod.state_write_mask(self.engine.state, wv, wb, wi)

    # ---------------------------------------------------------------- build
    def _build(self, prepared: PreparedStream):
        engine = self.engine
        schema_of = dict(zip(prepared.rel_order, prepared.schemas))

        if prepared.mode in ("scan", "rounds"):
            pattern = prepared.pattern
            tail_pattern = pattern[:prepared.tail_len]
            bodies = [engine.trigger_body(rel, plan)
                      for rel, plan in zip(pattern, prepared.plans)]
            # plan-level CSE: sibling prepare steps shared by ≥ 2 positions
            # (and written by none) compute once per round, not per position
            shared = (plan_mod.shared_prep_ops(prepared.plans)
                      if prepared.mode == "rounds" else ())
            self.last_shared_ops = shared

            def step(state, x):
                cols = (x,) if prepared.mode == "scan" else x
                memo = None
                if shared:
                    with jax.named_scope("fivm.gather"):
                        memo = plan_mod.build_prep_memo(shared, state[0])
                for rel, body, (keys, payload) in zip(pattern, bodies, cols):
                    state = body(state,
                                 COOUpdate(schema_of[rel], keys, payload),
                                 memo)
                if self.shard is not None:
                    # keep the carry partitioned step to step: GSPMD
                    # routes each position's scatters to the owning shard
                    # and places the plan's read collectives against this
                    # constraint instead of drifting to a replicated carry
                    state = self.shard.constrain(state)
                return state, None

            def run_stream(state, xs, tail):
                state = canonical_state(state)
                state, _ = jax.lax.scan(step, state, xs)
                # trailing partial round of a near-periodic schedule
                for rel, body, (keys, payload) in zip(tail_pattern, bodies,
                                                      tail):
                    state = body(state,
                                 COOUpdate(schema_of[rel], keys, payload))
                return state

            return jax.jit(run_stream, donate_argnums=(0,)), None

        # switch mode: thread only plan-written leaves through the
        # carry/branches; pass the constant rest as a loop invariant.
        # Under a shard plan the input placements propagate through the
        # flat mut/const leaf lists (HLO conditionals copy branch outputs,
        # so a per-step constraint would force collectives inside every
        # branch; input-sharding propagation keeps the partition instead)
        bodies = {rel: engine.trigger_body(rel, plan)
                  for rel, plan in zip(prepared.rel_order, prepared.plans)}
        mask = self._mutable_mask(prepared)
        treedef = jax.tree_util.tree_structure(engine.state)
        mut_idx = [i for i, m in enumerate(mask) if m]
        const_idx = [i for i, m in enumerate(mask) if not m]

        def merge(mut_leaves, const_leaves):
            leaves = [None] * len(mask)
            for i, leaf in zip(mut_idx, mut_leaves):
                leaves[i] = leaf
            for i, leaf in zip(const_idx, const_leaves):
                leaves[i] = leaf
            return jax.tree_util.tree_unflatten(treedef, leaves)

        def extract_mut(state):
            leaves = jax.tree_util.tree_leaves(state)
            return [leaves[i] for i in mut_idx]

        def run_stream(mut_leaves, const_leaves, xs):
            mut_leaves = [canonical_state(x) for x in mut_leaves]
            const_leaves = [canonical_state(x) for x in const_leaves]

            branches = []
            for rel in prepared.rel_order:
                sch = schema_of[rel]

                def branch(carry, keys, payload, _body=bodies[rel], _sch=sch):
                    state = merge(carry, const_leaves)
                    new = _body(state, COOUpdate(_sch, keys[:, : len(_sch)],
                                                 payload))
                    return extract_mut(new)

                branches.append(branch)

            def step(carry, x):
                sched_t, keys, payload = x
                return jax.lax.switch(sched_t, branches, carry, keys,
                                      payload), None

            carry, _ = jax.lax.scan(step, mut_leaves, xs)
            return carry

        fn = jax.jit(run_stream, donate_argnums=(0,))

        def call(state, xs, tail=()):
            leaves = jax.tree_util.tree_leaves(state)
            mut = [leaves[i] for i in mut_idx]
            const = [leaves[i] for i in const_idx]
            new_mut = fn(mut, const, xs)
            return merge(new_mut, const)

        return call, mask

    def compiled(self, prepared: PreparedStream):
        entry = self._compiled.get(prepared.signature)
        if entry is None:
            entry = self._compiled[prepared.signature] = self._build(prepared)
        return entry[0]

    # ------------------------------------------------- capacity segmentation
    def _capacity_segments(self, stream):
        """See :func:`capacity_segments` (module-level: shared with the
        ``prepare_stream`` capacity audit and the tests)."""
        return capacity_segments(self.engine, stream)

    # ------------------------------------------------------------------ run
    def run(self, stream_or_prepared, state=None, update_engine: bool = True,
            donate_input: bool = False, pipeline: bool = True,
            _offset: int = 0):
        """Apply the whole stream in one fused call; returns the new state.

        Unless ``donate_input=True``, the input state is copied before the
        call: the compiled program donates its state argument, and both the
        engine's state and states derived from it can alias the caller's
        database buffers (materialized leaf views alias the database).

        A *raw* stream run against the engine's own state (``state=None``)
        is first split into capacity segments (see
        :func:`capacity_segments`): sparse tables that would cross the
        load-factor bound mid-stream rehash to a larger capacity between
        segments and the remainder re-prepares (the plan cache recompiles
        for the new storage layout); ``pipeline=False`` disables the
        two-deep segment pipeline (blocking between stages — the additive
        baseline for the overlap benchmark).  With ``update_engine=False``
        the engine's views/base/indicators are all restored afterwards —
        snapshots of the container dicts, taken before any segment runs
        and restored even if a mid-segment prepare or compile raises — and
        only the returned state carries the grown tables.

        Explicit-state runs keep the caller's sizing: a *raw* stream is
        audited against the caller's state (``check_stream_capacity``),
        while replaying an already-``PreparedStream`` trusts its
        prepare-time audit — the replay path is the sync-free hot loop
        (see the sync-guard test) and cannot re-read occupancy per call,
        so callers replaying against states other than the engine's own
        must size those states like the engine's."""
        prepared = stream_or_prepared
        if not isinstance(prepared, PreparedStream):
            stream = list(prepared)
            if state is None:
                assert update_engine or not donate_input, (
                    "donating the engine's own state without updating the "
                    "engine would leave it pointing at deleted buffers")
                segments = self._capacity_segments(stream)
                if self.checkpoint is not None:
                    assert update_engine, (
                        "a checkpointed run must update the engine — "
                        "boundary snapshots capture the engine's state")
                    segments = split_segments(
                        segments, self.checkpoint.segment_updates)
                if self._integrity_active():
                    # integrity boundaries must exist even when capacity
                    # segmentation never splits (dense / generously-sized
                    # engines): cap segment length like the checkpointer
                    segments = split_segments(
                        segments, self.integrity.segment_updates)
                if self.registry is not None:
                    assert update_engine, (
                        "a registry-attached run must update the engine — "
                        "published generations snapshot the engine's state")
                    segments = split_segments(
                        segments, self.registry.segment_updates)
                if (self.checkpoint is not None or len(segments) > 1
                        or segments[0][1] or self._integrity_active()
                        or self.registry is not None):
                    saved = None
                    if not update_engine:
                        # snapshot the container dicts, not just the live
                        # state tuple: the restore must hold against any
                        # in-place mutation of engine.views between here
                        # and the last segment, and must run even when a
                        # mid-segment prepare/compile raises
                        saved = (dict(self.engine.views),
                                 dict(self.engine.base),
                                 dict(self.engine.indicators))
                    try:
                        new_state = self._run_segmented(segments,
                                                        pipeline=pipeline,
                                                        base_offset=_offset)
                    finally:
                        if saved is not None:
                            self.engine.set_state(saved)
                    return new_state
                # segmentation found no overflow risk, so skip the
                # (strictly tighter) prepare-time audit and its host syncs
                prepared = prepare_stream(self.engine, stream,
                                          check_capacity=False)
            else:
                # explicit-state run: audit the state the program will
                # actually mutate — the engine's own occupancy says
                # nothing about the caller's tables
                try:
                    check_stream_capacity(self.engine, stream,
                                          views=state[0])
                except StreamCapacityError as e:
                    if (self._integrity_active()
                            and self.integrity.capacity_degrade):
                        # graceful degradation (DESIGN.md §11): spill to
                        # the eager per-batch path, which grows tables
                        # host-side instead of overflow-dropping rows
                        return self._eager_spill(
                            stream, state, update_engine=update_engine,
                            error=e)
                    raise
                prepared = prepare_stream(self.engine, stream,
                                          check_capacity=False)
        if state is None:
            assert update_engine or not donate_input, (
                "donating the engine's own state without updating the engine "
                "would leave it pointing at deleted buffers")
            state = self.engine.state
        if not donate_input:
            state = jax.tree.map(
                lambda x: x.copy() if hasattr(x, "copy") else x, state)
            tracing.count("copy_bytes", sum(
                x.nbytes for x in jax.tree.leaves(state)
                if hasattr(x, "nbytes")))
        xs, tail = prepared.xs, prepared.tail
        if self.shard is not None:
            state = self.shard.place(state)
            # replicate the stream inputs once per prepared object: every
            # shard consumes every update row.  Cached beside (not in
            # place of) the originals, so the same prepared stream can
            # still feed an unsharded executor
            mesh_key = self.shard.mesh
            if prepared.placed is None or prepared.placed[0] != mesh_key:
                prepared.placed = (mesh_key,
                                   self.shard.replicate(xs),
                                   self.shard.replicate(tail) if tail
                                   else tail)
            _, xs, tail = prepared.placed
            # trace under the mesh: the kernels see it and run per device
            # (kernels.ring_scatter.per_device)
            with jax.set_mesh(self.shard.mesh):
                new_state = self.compiled(prepared)(state, xs, tail)
        else:
            new_state = self.compiled(prepared)(state, xs, tail)
        if update_engine:
            self.engine.set_state(new_state)
        return new_state

    def _admit_segment(self, sub_stream, grow_caps, offset: int = 0):
        """Admission stage of the segment pipeline: dispatch the
        pre-segment rehash (device work queued on the previous segment's
        still-in-flight outputs), bucket/pad/stack the segment's updates
        (the host→device upload), and fetch its trigger plans + compiled
        program entry.  Without an integrity config nothing here reads a
        device value, so the whole stage overlaps the previous segment's
        execution.

        With integrity attached, admission additionally (a) runs
        validated admission over the segment (strict raises *here*,
        before the segment can run or snapshot; quarantine masks rows
        into transparency), and (b) re-audits the capacity budget
        against *live* occupancy — run-start budgets are conservative,
        but quarantine repair and supervisor healing can replace tables
        mid-run, so pressure found here degrades to an emergency
        re-segmentation (split + rehash) instead of overflow-dropping.
        Both read device values: integrity is priced at admission.

        Returns ``(prepared, admit_seconds, admitted_sub, deferred)``
        where ``admitted_sub`` is the (possibly sanitized, possibly
        shortened) update list this segment will actually apply and
        ``deferred`` is the emergency-split remainder (``[(sub, grow),
        ...]``) the segmented runner must splice after this segment.
        ``admit_seconds`` is the wall of the ``fivm.admit`` span; its
        parts are spans too (``fivm.admit.integrity``, ``.rehash``,
        ``.stack``, ``.plans``, ``.program``)."""
        with tracing.span("fivm.admit") as admit:
            faults.crossing("mid_admit", updates=len(sub_stream))
            sub_stream, deferred = self._admit_updates(sub_stream, grow_caps,
                                                       offset)
            prepared = prepare_stream(self.engine, sub_stream,
                                      check_capacity=False)
            with tracing.span("fivm.admit.program"):
                self.compiled(prepared)
        return prepared, admit.wall, sub_stream, deferred

    def _admit_updates(self, sub_stream, grow_caps, offset: int):
        """Validated admission, the pre-segment rehash and the live
        capacity re-audit of :meth:`_admit_segment`; returns the admitted
        updates and the deferred remainder."""
        engine = self.engine
        cfg = self.integrity
        if cfg is not None and cfg.policy != "permissive":
            from repro.runtime import integrity as integrity_mod

            with tracing.span("fivm.admit.integrity"):
                sub_stream = integrity_mod.admit_stream(
                    engine, sub_stream, cfg, base_offset=offset)
        if grow_caps:
            with tracing.span("fivm.admit.rehash"):
                engine.views = {
                    name: (v.rehash(grow_caps[name]) if name in grow_caps
                           else v)
                    for name, v in engine.views.items()
                }
            # tables carry the grown capacities now, but nothing compiled
            # (or checkpointed) against them yet — the torn state the
            # post-rehash recovery path must survive
            faults.crossing("post_rehash_pre_recompile",
                            grown=sorted(grow_caps))
        deferred: list = []
        if cfg is not None and cfg.active and cfg.capacity_degrade:
            with tracing.span("fivm.admit.integrity"):
                try:
                    check_stream_capacity(engine, sub_stream)
                except StreamCapacityError as e:
                    resegmented = capacity_segments(engine, sub_stream)
                    sub_stream, extra_grow = resegmented[0]
                    deferred = resegmented[1:]
                    if extra_grow:
                        engine.views = {
                            name: (v.rehash(extra_grow[name])
                                   if name in extra_grow else v)
                            for name, v in engine.views.items()
                        }
                    cfg.degrade_log.append(dict(
                        kind="emergency_resegment",
                        segments=1 + len(deferred),
                        grow={k: int(v) for k, v in extra_grow.items()},
                        occupancy=storage_mod.occupancy_report(engine.views),
                        error=str(e)))
        return sub_stream, deferred

    def _eager_spill(self, stream, state, update_engine: bool, error):
        """Graceful degradation of an explicit-state run that failed its
        capacity audit: apply the stream per batch through the trigger
        plans with eager table growth (``grow_if_loaded``) — slower
        (host-side growth checks per batch) but it cannot overflow-drop.
        The spill still passes validated admission, and the decision is
        recorded in ``integrity.degrade_log``."""
        from repro.runtime import integrity as integrity_mod

        cfg = self.integrity
        engine = self.engine
        with tracing.span("fivm.spill") as spill:
            stream = integrity_mod.admit_stream(engine, stream, cfg,
                                                base_offset=0)
            views, base, indicators = (dict(state[0]), dict(state[1]),
                                       dict(state[2]))
            for rel, upd in stream:
                touched, _, _ = engine.plans.write_sets(engine, rel)
                views = {
                    name: (storage_mod.grow_if_loaded(
                               v, engine._insert_budget(v, rel, upd))
                           if name in touched else v)
                    for name, v in views.items()
                }
                views, base, indicators = engine.functional_update(
                    views, base, indicators, rel, upd)
            integrity_mod.flush_dead_letters(cfg)
            new_state = canonical_state((views, base, indicators))
        cfg.degrade_log.append(dict(
            kind="eager_spill", updates=len(stream), error=str(error),
            wall_s=spill.wall))
        if update_engine:
            engine.set_state(new_state)
        return new_state

    def _run_segmented(self, segments, pipeline: bool = True,
                       base_offset: int = 0):
        """Two-deep pipelined segment loop: while segment i's compiled
        program executes on device, segment i+1 is *admitted* — its
        rehash dispatched, its xs stacked and uploaded, its program
        fetched (:meth:`_admit_segment`).  Admission never blocks on a
        device result, so the host reaches segment i+1's dispatch with
        segment i still in flight; the overlap this buys is bounded by
        the device-side execution time (negligible on a shared-core CPU
        host, where admission itself is the wall — material where DMA
        and compute are separate engines).  Intermediate segments donate
        their input state (only segment 0's can alias caller-visible
        buffers), which is the measured win on this container.
        ``pipeline=False`` blocks on each segment's result before
        admitting the next — the serialized baseline the BENCH_stream
        ``segmented_pipeline`` row compares against.  Per-segment
        admit/dispatch/audit/publish/save host times (the walls of their
        ``fivm.*`` spans) and the segment's counters (``counts``, from
        :func:`repro.runtime.tracing.count`) land in
        ``last_segment_stats``.

        With a :attr:`checkpoint` attached, every segment boundary
        snapshots the engine: the save dispatches device copies of the
        fresh state *before* the next segment's program donates the
        originals, then the writer thread's device→host transfer and
        filesystem commit overlap that segment's admission + execution —
        checkpointing rides the same overlap discipline as admission.
        The final boundary save is awaited so a completed run is durable
        (and a writer failure surfaces here, not silently).  Boundary
        steps are numbered by *cumulative stream offset*
        (``base_offset`` + updates applied), which is what
        :meth:`resume` uses as its replay cursor.

        Integrity hooks (DESIGN.md §11) ride the boundaries: the audited
        Reevaluate pass runs every ``audit_interval`` segments *before*
        that boundary's snapshot dispatches, so a repaired state — not a
        drifted one — is what gets committed; an emergency
        re-segmentation during admission splices its deferred remainder
        into the segment queue.  Each segment's admit+dispatch wall also
        feeds :attr:`stragglers` (EWMA slow-segment detection), and the
        verdict lands in the segment's stats entry."""
        stats: list = []
        state = None
        ck = self.checkpoint
        cfg = self.integrity
        if cfg is not None:
            # a failed prior attempt may have left validation results
            # pending; re-admission below re-records them, so stale
            # entries would double-count
            cfg.pending_dead_letters.clear()
        offset = base_offset
        queue = list(segments)
        tracing.take_counts()  # a segment's entry holds only its own counts
        prepared, admit_s, sub, deferred = self._admit_segment(
            *queue[0], offset=offset)
        if deferred:
            queue[1:1] = deferred
        i = 0
        while i < len(queue):
            n_steps = prepared.n_steps
            with tracing.span("fivm.dispatch") as dispatch:
                # segment 0's input can alias caller-visible arrays (the
                # original database, the update_engine=False snapshot)
                # and must be copied; later segments run on exclusively
                # engine-owned outputs of the previous segment — donate
                # them instead of paying a full-state device copy
                state = self.run(prepared, update_engine=True,
                                 donate_input=i > 0)
                if not pipeline:
                    jax.block_until_ready(state)
            offset += len(sub)
            faults.crossing("mid_segment", segment=i, offset=offset)
            audit_s = 0.0
            audit_meta: dict = {}
            if cfg is not None and cfg.audit_due(i):
                from repro.runtime import integrity as integrity_mod

                with tracing.span("fivm.audit") as audit:
                    records = integrity_mod.audit_engine(self.engine, cfg,
                                                         segment=i)
                    if any(r.repaired for r in records):
                        # the repair replaced engine views; the boundary
                        # snapshot (and the next segment) must see it
                        state = self.engine.state
                    audit_meta = integrity_mod.publish_meta(records)
                audit_s = audit.wall
            publish_s = 0.0
            snap = None
            if self.registry is not None:
                # publish *after* the audit hook (readers must see a
                # repaired state, never a drifted one) and *before* the
                # next segment's admission can dispatch the program that
                # donates these buffers — jnp.copy dispatches without a
                # host sync, exactly like the async checkpoint save
                snap = self.registry.publish(self.engine.views,
                                             offset=offset, segment=i,
                                             meta=audit_meta)
                publish_s = self.registry.last_publish_seconds
            save_s = 0.0
            if ck is not None:
                with tracing.span("fivm.checkpoint") as save:
                    ck.save_boundary(self.engine, offset=offset, segment=i,
                                     blocking=not pipeline,
                                     view_copies=(snap.views
                                                  if snap is not None
                                                  else None))
                    if i + 1 == len(queue):
                        ck.wait()  # a finished run is durably checkpointed
                save_s = save.wall
            straggler = self.stragglers.observe(i, admit_s + dispatch.wall)
            stats.append(dict(segment=i, n_steps=n_steps,
                              admit_s=admit_s, dispatch_s=dispatch.wall,
                              save_s=save_s, audit_s=audit_s,
                              publish_s=publish_s,
                              generation=(self.registry.generation
                                          if self.registry is not None
                                          else None),
                              straggler=straggler,
                              straggler_baseline=self.stragglers.baseline,
                              counts=tracing.take_counts()))
            if i + 1 < len(queue):
                prepared, admit_s, sub, deferred = self._admit_segment(
                    *queue[i + 1], offset=offset)
                if deferred:
                    queue[i + 2:i + 2] = deferred
            i += 1
        if cfg is not None and cfg.pending_dead_letters:
            # every admitted segment has executed by now, so the parked
            # violation flags are ready and this sync is free
            from repro.runtime import integrity as integrity_mod

            integrity_mod.flush_dead_letters(cfg)
        self.last_segment_stats = stats
        return state

    # --------------------------------------------------------------- recovery
    def resume(self, stream, checkpoint=None, pipeline: bool = True):
        """Replay-from-offset recovery: restore the newest committed
        snapshot and continue ``stream`` from where it left off.

        ``stream`` is the *full* raw update stream of the original run
        (replay determinism: recovery re-derives everything else —
        capacities, segments, plans — from the restored state plus the
        remaining updates).  The restored snapshot's ``offset`` says how
        many leading updates are already applied; they are skipped, the
        rest runs through the normal checkpointed segmented path, so a
        crash *during recovery* recovers the same way.

        Mesh-elastic: snapshots hold logical (unsharded) arrays, so a
        mesh-aware executor re-derives its :class:`ShardPlan` against the
        *current* devices and re-places the restored state — a run killed
        on 4 devices resumes on 1 or 2 (or vice versa).  Compiled stream
        programs are dropped on replan (their GSPMD partitioning is baked
        against the old mesh and the :attr:`PreparedStream.signature`
        does not carry it).

        When no committed snapshot exists yet (first boundary never
        reached, or a kill landed before the first commit), a blocking
        offset-0 baseline snapshot is written first — establishing the
        invariant that a resumed run *always* restarts from a snapshot,
        never from a partially-advanced live engine."""
        ck = checkpoint if checkpoint is not None else self.checkpoint
        assert ck is not None, (
            "resume needs a StreamCheckpointer (pass checkpoint= or "
            "construct the executor with one)")
        self.checkpoint = ck
        # an interrupted run may have died with an async save in flight
        # (or a captured writer failure); recovery restarts from the last
        # committed step regardless
        ck.ckpt.discard_pending()
        stream = list(stream)
        meta = ck.restore_into(self.engine)
        offset = int(meta["offset"]) if meta is not None else 0
        if self.shard is not None:
            from . import shard as shard_mod

            self.shard = shard_mod.replan_shards(self.engine, self.shard)
            self._compiled.clear()
            self.engine.shard_state(self.shard)
        if meta is None:
            ck.save_boundary(self.engine, offset=0, segment=-1,
                             blocking=True)
        if self.registry is not None:
            # readers of a restarted process must see the restored
            # (committed) state, never whatever the engine held before
            # the restore; generations stay monotonic across restarts
            # within this registry's lifetime
            self.registry.publish(self.engine.views, offset=offset,
                                  segment=-1, meta=dict(restored=True))
        remaining = stream[offset:]
        assert 0 <= offset <= len(stream), (
            f"snapshot offset {offset} exceeds the replayed stream "
            f"({len(stream)} updates) — wrong stream or checkpoint dir?")
        if not remaining:
            return self.engine.state
        return self.run(remaining, update_engine=True, pipeline=pipeline,
                        _offset=offset)
