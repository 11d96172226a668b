"""Trigger-plan IR (DESIGN.md §8): delta propagation as a compiled artifact.

F-IVM's central claim is that maintenance reduces to a *fixed* hierarchy of
view updates per trigger — the key/update computation is the same for every
task, only the ring payload differs.  Historically the engine re-discovered
that fixed structure interpretively on every update: ``propagate_coo`` /
``propagate_factorized`` walked the view-tree path per call, and the three
planning decisions of higher-order IVM — densify-vs-factorized delta
carriage, dense-vs-sparse view storage, scatter kernel backend — were made
ad hoc in three different layers (``delta.py``, ``storage.py``,
``kernels/scatter_ops.py``).

This module makes the trigger an explicit compiled object:

* a small typed IR (:class:`Gather`, :class:`Lift`, :class:`JoinContract`,
  :class:`Marginalize`, :class:`ScatterAccum`, :class:`IndicatorBump`,
  :class:`BaseBump`, :class:`Reevaluate`), each op carrying schema, storage
  class, and backend annotations;
* a compiler :func:`compile_trigger` that runs **once per (relation,
  update-kind, batch, storage layout, backend override)** and is cached on
  the engine (:class:`PlanCache`, with hit/miss counters and op interning);
* one unified planning pass: the densify cost model
  (:func:`should_densify`), the storage planner's sparse-hostility
  eligibility walk (:func:`storage_hostility`), and the scatter-backend
  resolution all read the same symbolic path analysis, so they trade off
  against each other in one place;
* thin interpreters (:func:`execute_trigger`) that replay a plan with the
  exact same delta-algebra calls the old tree-walk made — eager triggers,
  jitted triggers, and the fused stream executor's scan/rounds/switch
  bodies are all generated from the same plans (``stream.prepare_stream``
  embeds them; the switch-mode mutable/const partition derives from each
  plan's write-set via :func:`state_write_mask`);
* plan-level CSE: ops are interned per engine, and
  :func:`shared_prep_ops` / :func:`build_prep_memo` let a fused rounds
  step compute sibling gather planes / densified sparse siblings once per
  step when several positions' plans read a view no trigger in the pattern
  writes.

The symbolic state tracked during compilation mirrors
``contraction.BatchedDelta`` exactly (COO schema, dense schema, effective
batch incl. collapse, pending deferred gather), so every runtime decision
the delta algebra makes is known — and recorded — at compile time.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .contraction import BatchedDelta
from .materialize import views_on_path
from .query import Query
from .relations import COOUpdate, DenseRelation, FactorizedUpdate
from .view_tree import ViewNode, evaluate_view

#: indicator dense relations are referenced by this name prefix in op
#: ``view`` fields (mirrors the host oracle's ``∃<node>`` naming)
IND_PREFIX = "∃"


# ---------------------------------------------------------------------------
# The op vocabulary.  Frozen dataclasses: hashable (interning / memo keys)
# and printable in a stable text form (golden-plan tests).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PlanOp:
    def label(self) -> str:  # pragma: no cover - overridden
        return type(self).__name__


@dataclasses.dataclass(frozen=True)
class LeafDelta(PlanOp):
    """Build the leaf delta: COO rows, or one densified delta relation."""

    rel: str
    schema: tuple
    batch: int
    densify: bool

    def label(self):
        if self.densify:
            form = f"densified[{','.join(self.schema)}]"
        elif self.batch == 0:
            form = f"factors[{','.join(self.schema)}]"
        else:
            form = f"rows[{','.join(self.schema)}; B={self.batch}]"
        return f"Leaf {form}"


@dataclasses.dataclass(frozen=True)
class Gather(PlanOp):
    """Deferred sibling gather: the join stays symbolic (pending_gather)
    and fuses into the eventual scatter / a later forced materialize."""

    view: str
    vars: tuple
    storage: str  # "dense" | "sparse"
    forces: bool = False  # materializes a previously pending gather first

    def label(self):
        f = " !force" if self.forces else ""
        return f"Gather[{self.view} {self.storage}]{f}"


@dataclasses.dataclass(frozen=True)
class JoinContract(PlanOp):
    """Eager join with a materialized sibling (einsum per bilinear term)."""

    view: str
    vars: tuple
    storage: str
    grows: tuple = ()  # fresh dense axes grown by this join
    densifies: bool = False  # sparse sibling materializes to dense first
    gathers: bool = False  # fully-bound per-row gather-multiply path
    forces: bool = False

    def label(self):
        tags = []
        if self.densifies:
            tags.append("densify")
        if self.gathers:
            tags.append("gather")
        if self.grows:
            tags.append(f"+[{','.join(self.grows)}]")
        if self.forces:
            tags.append("!force")
        t = (" " + " ".join(tags)) if tags else ""
        return f"Join[{self.view} {self.storage}]{t}"


@dataclasses.dataclass(frozen=True)
class Lift(PlanOp):
    """Gather the lift relation g_var at the delta's keys (identity lifts
    compile to *no* Lift op — the skip is a plan-time decision)."""

    var: str
    spec: tuple

    def label(self):
        return f"Lift[{self.var} {'.'.join(str(s) for s in self.spec)}]"


@dataclasses.dataclass(frozen=True)
class Marginalize(PlanOp):
    var: str
    axis: str  # "coo" | "dense"
    collapses: bool = False  # batch collapse fires after this ⊕
    forces: bool = False

    def label(self):
        tags = []
        if self.collapses:
            tags.append("collapse")
        if self.forces:
            tags.append("!force")
        t = (" " + " ".join(tags)) if tags else ""
        return f"Marg[{self.var} {self.axis}]{t}"


@dataclasses.dataclass(frozen=True)
class Emit(PlanOp):
    """Record the current delta as this view's delta (PropagationResult)."""

    view: str

    def label(self):
        return f"Emit[{self.view}]"


@dataclasses.dataclass(frozen=True)
class ScatterAccum(PlanOp):
    """view ⊎ δ into the materialized view under its storage backend."""

    view: str
    storage: str
    backend: str | None = None  # scatter kernel backend (plan-time resolved)
    fused: bool = False  # a pending gather fuses into this scatter
    mixed: bool = False  # delta carries dense axes (grid / mixed apply)

    def label(self):
        tags = [self.storage]
        if self.backend is not None:
            tags.append(self.backend)
        if self.fused:
            tags.append("fused")
        if self.mixed:
            tags.append("mixed")
        return f"Scatter[{self.view} {' '.join(tags)}]"


@dataclasses.dataclass(frozen=True)
class BaseBump(PlanOp):
    rel: str
    backend: str | None = None

    def label(self):
        b = f" {self.backend}" if self.backend is not None else ""
        return f"BaseBump[{self.rel}{b}]"


@dataclasses.dataclass(frozen=True)
class IndicatorBump(PlanOp):
    """Transition-count maintenance of ∃_proj rel; starts an indicator
    propagation section (the δ∃ becomes the current delta)."""

    node: str
    rel: str
    proj: tuple

    def label(self):
        return f"IndicatorBump[{IND_PREFIX}{self.node} ← {self.rel}]"


@dataclasses.dataclass(frozen=True)
class Reevaluate(PlanOp):
    """Evaluate the view tree bottom-up from stored base relations."""

    scope: str  # "root" (reeval) | "store" (1-IVM sibling recompute)

    def label(self):
        return f"Reevaluate[{self.scope}]"


@dataclasses.dataclass(frozen=True)
class FusedChain(PlanOp):
    """A Gather→Lift→(Marginalize)→Emit→ScatterAccum subsequence fused
    into one megakernel dispatch (``repro.kernels.ring_fused``): every
    gathered payload plane and lifted ring component stays in VMEM across
    the chain, the ring product runs as one fused flat formula, and the
    terminal ⊎ scatters with per-tile dedup — over the whole view, or,
    where the terminal ScatterAccum's hint is ``compact``, over the
    batch's ranked keys before a B-row add into the view.  Legality is
    decided at plan time (:func:`fuse_trigger_ops`); the recorded
    ``reads``/``writes`` keep the chain transparent to the
    collective-placement and CSE passes, and ``vmem_bytes`` is the tile
    model's footprint bound (golden-plan tests pin it)."""

    ops: tuple  # the fused op subsequence, in original plan order
    reads: tuple  # view names gathered inside the chain (lifts excluded)
    writes: tuple  # view names ⊎-written by the chain's terminal scatter
    vmem_bytes: int
    spec: tuple  # fused ring spec, e.g. ("degree", 2) | ("scalar",)

    def label(self):
        return (f"Fused[{len(self.ops)} ops → {','.join(self.writes)}"
                f" ring={'.'.join(str(s) for s in self.spec)}"
                f" vmem={self.vmem_bytes}B]")


def iter_flat_ops(ops):
    """Iterate an op sequence with FusedChain subsequences expanded — the
    view every structural pass (CSE, goldens) that predates fusion sees."""
    for op in ops:
        if isinstance(op, FusedChain):
            yield from op.ops
        else:
            yield op


# ---------------------------------------------------------------------------
# TriggerPlan
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TriggerPlan:
    """A compiled maintenance trigger: the fixed op sequence for one
    (relation, update-kind, batch, storage layout)."""

    rel: str
    kind: str  # "coo" | "factorized" | "first_order" | "reeval"
    strategy: str
    schema: tuple
    batch: int | None  # None for factorized updates
    densify: bool
    ops: tuple  # main delta-path section
    ind_ops: tuple  # indicator sections (each led by an IndicatorBump)
    write_views: frozenset
    write_base: frozenset
    write_indicators: frozenset
    cost: int  # modeled element count of the chosen delta walk

    def write_sets(self):
        return self.write_views, self.write_base, self.write_indicators

    def read_views(self) -> frozenset:
        """View names this plan reads *by key* through sibling
        Gather/JoinContract ops (indicator dense planes keep their
        ``∃`` prefix).  These are the cross-shard read sites of the
        multi-device placement pass (:func:`collective_placement`): a
        gather at arbitrary delta keys must see the view's whole key
        axis, so reading a sharded view lowers to a collective."""
        out = set()
        for op in iter_flat_ops(self.ops + self.ind_ops):
            if isinstance(op, (Gather, JoinContract)):
                out.add(op.view)
        return frozenset(out)

    def pretty(self) -> str:
        """Stable text form (golden-plan tests pin this)."""
        b = "-" if self.batch is None else str(self.batch)
        head = (f"trigger {self.rel} kind={self.kind} strategy={self.strategy}"
                f" schema=[{','.join(self.schema)}] batch={b}"
                f" densify={'yes' if self.densify else 'no'}"
                f" cost={self.cost}")
        lines = [head]
        for op in self.ops:
            lines.append(f"  {op.label()}")
            if isinstance(op, FusedChain):
                for inner in op.ops:
                    lines.append(f"    {inner.label()}")
        for op in self.ind_ops:
            pad = "  " if isinstance(op, IndicatorBump) else "    "
            lines.append(f"{pad}{op.label()}")
        lines.append(
            "  writes: views=[%s] base=[%s] indicators=[%s]" % (
                ",".join(sorted(self.write_views)),
                ",".join(sorted(self.write_base)),
                ",".join(sorted(self.write_indicators))))
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Unified cost model (the PR-2 densify planner, now a plan-time pass)
# ---------------------------------------------------------------------------
def path_costs(path: Sequence[ViewNode], upd_schema: Sequence[str],
               batch: int, query: Query):
    """(cost_row, cost_dense, grew_dense): modeled element counts of the two
    delta representations along the path.

    * **Row (COO) propagation** streams ``[B, D_dense...]`` slices: each
      node costs ``B_eff · ∏ dense-axis domains`` where dense axes are the
      sibling/indicator variables the update doesn't bind, and ``B_eff``
      drops to 1 once the COO schema empties (batch collapse).
    * **Dense-delta propagation** materializes one relation over the
      delta's variable set: the leaf pays the full update-schema domain
      product, each node the domain product of the current delta schema.
    """
    B = batch
    dom = query.domains
    bound = set(upd_schema)

    def extent(vars_):
        e = 1
        for v in vars_:
            e *= int(dom[v])
        return e

    coo = set(upd_schema)
    row_dense: set[str] = set()
    dense_vars = set(upd_schema)
    cost_row = B
    cost_dense = extent(upd_schema)
    grew_dense = False
    child = path[0]
    for node in path[1:]:
        sib_schemas = [set(sib.schema) for sib in node.children
                       if sib is not child]
        if node.indicator is not None:
            sib_schemas.append(set(node.indicator[1]))
        for sch in sib_schemas:
            row_dense |= sch - bound
            dense_vars |= sch
        grew_dense = grew_dense or bool(row_dense)
        b_eff = B if coo else 1
        cost_row += b_eff * extent(row_dense)
        cost_dense += extent(dense_vars)
        for v in node.marg_vars:
            coo.discard(v)
            row_dense.discard(v)
            dense_vars.discard(v)
        child = node
    return cost_row, cost_dense, grew_dense


def should_densify(path: Sequence[ViewNode], upd_schema: Sequence[str],
                   batch: int, query: Query) -> bool:
    """Densify when the dense walk is strictly cheaper.  Updates that bind
    every sibling variable never grow dense axes, so the row walk is the
    factorized fast path and wins regardless of batch size."""
    cost_row, cost_dense, grew_dense = path_costs(path, upd_schema, batch,
                                                  query)
    if not grew_dense:
        return False
    return cost_dense < cost_row


def storage_hostility(tree: ViewNode, updatable) -> set[str]:
    """Names of views whose delta interactions are *not* purely
    gather/scatter shaped — the storage planner's sparse-hostile set.

    Derived from the same symbolic path walk the trigger compiler uses:
    a sibling joined while some of its variables are not COO-bound forces
    a densify (or grows dense delta axes), and a view whose ⊎ arrives with
    dense axes takes the mixed (grid-enumerating) apply.  Sparse storage
    remains *correct* for these views — the delta-algebra fallbacks cover
    them — but the auto planner keeps them dense."""
    hostile: set[str] = set()
    for rel in updatable:
        path = views_on_path(tree, rel)
        child = path[0]
        coo = set(child.schema)
        dense: set[str] = set()
        for node in path[1:]:
            for sib in node.children:
                if sib is child:
                    continue
                sch = set(sib.schema)
                if not sch <= coo:
                    hostile.add(sib.name)
                    dense |= sch - coo
            if node.indicator is not None:
                dense |= set(node.indicator[1]) - coo
            if dense:
                hostile.add(f"W:{node.name}")
            for v in node.marg_vars:
                coo.discard(v)
                dense.discard(v)
            if dense:
                hostile.add(node.name)
            child = node
    return hostile


# ---------------------------------------------------------------------------
# Compile-time helpers
# ---------------------------------------------------------------------------
def _storage_kind(view) -> str:
    from . import storage

    return "sparse" if isinstance(view, storage.SparseRelation) else "dense"


def _payload_width(ring) -> int:
    w = 0
    for shp in ring.components.values():
        c = 1
        for s in shp:
            c *= int(s)
        w += c
    return w


def active_backend_override() -> str | None:
    """The globally forced scatter backend (``use_backend`` / env), if any —
    part of the plan-cache key so an override change can never replay a
    stale plan."""
    from repro.kernels import scatter_ops

    return scatter_ops.active_override()


# ---------------------------------------------------------------------------
# Plan-level fusion mode (DESIGN.md §13)
# ---------------------------------------------------------------------------
FUSION_ENV_VAR = "REPRO_PLAN_FUSION"

FUSION_MODES = ("on", "off", "auto")

_fusion_override: str | None = None


def set_fusion(mode: str | None) -> None:
    """Process-wide fusion-mode override (None restores env/auto)."""
    global _fusion_override
    assert mode is None or mode in FUSION_MODES, mode
    _fusion_override = mode


@contextlib.contextmanager
def use_fusion(mode: str | None):
    """Scoped fusion override — the fused-vs-unfused benches and the
    equivalence sweeps flip this per run."""
    global _fusion_override
    prev = _fusion_override
    set_fusion(mode)
    try:
        yield
    finally:
        _fusion_override = prev


def active_fusion_override() -> str | None:
    return _fusion_override or os.environ.get(FUSION_ENV_VAR) or None


def fusion_mode() -> str:
    """Resolved fusion mode: explicit override / env > auto.  Auto fuses
    only on TPU — the megakernel is a VMEM/launch-overhead play; on CPU
    the XLA fused lowering is roughly cost-neutral, so auto keeps the
    bit-exact op-by-op path (and the existing goldens) stable."""
    mode = active_fusion_override() or "auto"
    assert mode in FUSION_MODES, mode
    if mode != "auto":
        return mode
    return "on" if jax.default_backend() == "tpu" else "off"


def _resolve_scatter_backend(num_segments: int, batch: int, width: int):
    from repro.kernels import scatter_ops

    return scatter_ops.resolve_backend(num_segments, batch, width, None)


@dataclasses.dataclass
class _SymDelta:
    """Compile-time mirror of ``BatchedDelta``'s state machine: the exact
    fields its join/marginalize/apply decisions read."""

    coo: tuple
    dense: tuple
    b: int
    pending: bool
    ring: Any

    def b_eff(self) -> int:
        return self.b

    def defer_ok(self, view_vars, view_nonempty=True) -> bool:
        if self.pending or self.dense:
            return False
        if self.ring.mul_terms is None or not self.ring.commutative:
            return False
        return bool(view_vars) and all(v in self.coo for v in view_vars)


def _domain_extent(query: Query, vars_) -> int:
    e = 1
    for v in vars_:
        e *= int(query.domains[v])
    return e


def _scatter_op(query: Query, name: str, view, st: _SymDelta) -> ScatterAccum:
    """Annotate a ⊎ site: storage class + the kernel backend the dispatch
    layer will resolve for its primary scatter (the three scattered
    planners, decided together at plan time)."""
    ring = st.ring
    kind = _storage_kind(view)
    d = _payload_width(ring)
    if kind == "sparse":
        backend = _resolve_scatter_backend(view.capacity, st.b, d)
        return ScatterAccum(name, kind, backend=backend,
                            fused=st.pending, mixed=bool(st.dense))
    if st.coo and not st.dense:
        S = 1
        for v in view.schema:
            S *= int(view.domain_of(v))
        backend = _resolve_scatter_backend(S, st.b, d)
        return ScatterAccum(name, kind, backend=backend, fused=st.pending)
    if st.coo:  # mixed COO×dense apply
        S = _domain_extent(query, st.coo)
        dd = d * _domain_extent(query, st.dense)
        backend = _resolve_scatter_backend(S, st.b, dd)
        return ScatterAccum(name, kind, backend=backend, mixed=True)
    # dense-axes-only delta: plain elementwise add, no scatter involved
    return ScatterAccum(name, kind, backend=None, mixed=bool(st.dense))


# ---------------------------------------------------------------------------
# The compiler
# ---------------------------------------------------------------------------
def _emit_join(ops: list, st: _SymDelta, name: str, view, view_vars,
               intern) -> None:
    """Emit the op for ``delta.join_dense(view)`` and advance the symbolic
    state, mirroring contraction.BatchedDelta.join_dense exactly."""
    kind = _storage_kind(view)
    if st.defer_ok(view_vars):
        ops.append(intern(Gather(name, tuple(view_vars), kind)))
        st.pending = True
        return
    forces = st.pending
    st.pending = False  # join_dense forces before any eager path
    if st.defer_ok(view_vars):  # re-dispatch after force (second sibling)
        ops.append(intern(Gather(name, tuple(view_vars), kind,
                                 forces=forces)))
        st.pending = True
        return
    fully_bound = bool(view_vars) and all(v in st.coo for v in view_vars)
    if kind == "sparse":
        if fully_bound:
            ops.append(intern(JoinContract(name, tuple(view_vars), kind,
                                           gathers=True, forces=forces)))
            return
        densifies = True
    else:
        densifies = False
    shared_coo = [v for v in view_vars if v in st.coo]
    v_rest = [v for v in view_vars if v not in shared_coo]
    grows = tuple(v for v in v_rest if v not in st.dense)
    st.dense = tuple(st.dense) + grows
    ops.append(intern(JoinContract(name, tuple(view_vars), kind,
                                   grows=grows, densifies=densifies,
                                   forces=forces)))


def _emit_marginalize(ops: list, st: _SymDelta, query: Query, var: str,
                      intern) -> None:
    """Emit Lift?/Marginalize for ``delta.marginalize(var, lift_or_none)``,
    mirroring the identity-lift skip and the batch-collapse rule."""
    lifted = query.lift_spec(var) != ("one",)
    if lifted:
        ops.append(intern(Lift(var, tuple(query.lift_spec(var)))))
    if var in st.coo:
        forces = st.pending and st.b > 1 and len(st.coo) == 1
        if forces:
            st.pending = False
        st.coo = tuple(v for v in st.coo if v != var)
        collapses = (not st.coo) and st.b > 1
        if collapses:
            st.b = 1
        ops.append(intern(Marginalize(var, "coo", collapses=collapses,
                                      forces=forces)))
    else:
        st.dense = tuple(v for v in st.dense if v != var)
        ops.append(intern(Marginalize(var, "dense")))


def _compile_path_ops(tree: ViewNode, query: Query, rel: str,
                      upd_schema, batch: int, views: Mapping,
                      ind_meta: Mapping[str, tuple], densify: bool,
                      intern, apply_views: bool = True):
    """Compile the leaf-to-root delta path into ops.  ``views`` maps the
    materialized view names to their storage objects (storage classes and
    capacities are read off them); ``ind_meta`` maps indicator node names
    to (proj, dense_view).  ``apply_views=False`` skips ScatterAccum ops
    (1-IVM computes the root delta from recomputed stores and applies only
    at the root)."""
    ring = query.ring
    path = views_on_path(tree, rel)
    ops: list = []
    if densify:
        st = _SymDelta(coo=(), dense=tuple(upd_schema), b=1, pending=False,
                       ring=ring)
    else:
        st = _SymDelta(coo=tuple(upd_schema), dense=(), b=batch,
                       pending=False, ring=ring)
    ops.append(intern(LeafDelta(rel, tuple(upd_schema), batch, densify)))
    write_views: set[str] = set()

    leaf = path[0]
    ops.append(intern(Emit(leaf.name)))
    if apply_views and leaf.name in views:
        ops.append(intern(_scatter_op(query, leaf.name,
                                     views[leaf.name], st)))
        write_views.add(leaf.name)

    child = leaf
    for node in path[1:]:
        for sib in node.children:
            if sib is child:
                continue
            assert sib.name in views, (
                f"sibling {sib.name} of delta path must be materialized "
                f"(μ guarantees this for updatable {rel})")
            _emit_join(ops, st, sib.name, views[sib.name], sib.schema,
                       intern)
        if node.indicator is not None:
            assert node.name in ind_meta, (
                f"maintained indicator for {node.name} required")
            proj, ind_view = ind_meta[node.name]
            _emit_join(ops, st, IND_PREFIX + node.name, ind_view, proj,
                       intern)
        wname = f"W:{node.name}"
        if apply_views and wname in views:
            ops.append(intern(_scatter_op(query, wname,
                                         views[wname], st)))
            write_views.add(wname)
        for v in node.marg_vars:
            _emit_marginalize(ops, st, query, v, intern)
        ops.append(intern(Emit(node.name)))
        if apply_views and node.name in views:
            ops.append(intern(_scatter_op(query, node.name,
                                         views[node.name], st)))
            write_views.add(node.name)
        child = node
    return tuple(ops), write_views


def _compile_indicator_ops(tree: ViewNode, query: Query, rel: str,
                           batch: int, views: Mapping,
                           indicators: Mapping, intern):
    """Compile the indicator second pass (Sec. 6): for every maintained
    ∃-projection over ``rel``, count maintenance plus the δ∃ propagation
    path from the indicator node to the root."""
    ring = query.ring
    ops: list = []
    write_views: set[str] = set()
    write_inds: set[str] = set()
    for node_name, ind in indicators.items():
        if ind.rel_name != rel:
            continue
        write_inds.add(node_name)
        ops.append(intern(IndicatorBump(node_name, rel, tuple(ind.proj))))
        st = _SymDelta(coo=tuple(ind.proj), dense=(), b=batch,
                       pending=False, ring=ring)
        node = tree.find(node_name)
        for sib in node.children:
            assert sib.name in views, f"{sib.name} must be materialized"
            _emit_join(ops, st, sib.name, views[sib.name], sib.schema,
                       intern)
        for v in node.marg_vars:
            _emit_marginalize(ops, st, query, v, intern)
        if node.name in views:
            ops.append(intern(_scatter_op(query, node.name,
                                         views[node.name], st)))
            write_views.add(node.name)
        path = path_to_root(tree, node_name)
        child = node
        for parent in path[1:]:
            for sib in parent.children:
                if sib is child:
                    continue
                assert sib.name in views, f"{sib.name} must be materialized"
                _emit_join(ops, st, sib.name, views[sib.name], sib.schema,
                           intern)
            if parent.indicator is not None and parent.name != node_name:
                proj, ind_view = (tuple(indicators[parent.name].proj),
                                  indicators[parent.name].dense)
                _emit_join(ops, st, IND_PREFIX + parent.name, ind_view,
                           proj, intern)
            for v in parent.marg_vars:
                _emit_marginalize(ops, st, query, v, intern)
            if parent.name in views:
                ops.append(intern(_scatter_op(query, parent.name,
                                          views[parent.name], st)))
                write_views.add(parent.name)
            child = parent
    return tuple(ops), write_views, write_inds


def compile_trigger(engine, rel: str, upd_sig, intern=None,
                    views=None) -> TriggerPlan:
    """Compile the maintenance trigger for updates to ``rel``.

    ``upd_sig`` is ``("coo", schema, batch)`` or ``("factorized", schema)``.
    ``views`` defaults to the engine's materialized views; pass the state
    actually being updated when it may differ in storage layout.  The
    result is a pure metadata object: compiling never touches device
    state, so plans cache across jit traces, scan bodies, and switch
    branches (one compiler, every execution path).
    """
    intern = intern or (lambda op: op)
    kind, schema = upd_sig[0], tuple(upd_sig[1])
    batch = upd_sig[2] if kind == "coo" else None
    query, tree, strategy = engine.query, engine.tree, engine.strategy
    views = engine.views if views is None else views
    root = tree.name

    if strategy == "reeval":
        ops = (intern(BaseBump(rel, active_backend_override())),
               intern(Reevaluate("root")))
        return TriggerPlan(
            rel=rel, kind="reeval", strategy=strategy, schema=schema,
            batch=batch, densify=False, ops=ops, ind_ops=(),
            write_views=frozenset({root}), write_base=frozenset({rel}),
            write_indicators=frozenset(), cost=0)

    if strategy == "fivm_1":
        # 1-IVM: recompute sibling views from base, run the delta path over
        # the recomputed store (all views present), apply only at the root.
        if kind == "factorized":
            # the full densified delta is the point of the comparison
            batch = _domain_extent(query, schema)
        path = views_on_path(tree, rel)
        densify = should_densify(path, schema, batch, query)
        store_views = {n.name: views.get(n.name, _DenseProxy(n, query))
                       for n in tree.walk()}
        path_ops, _ = _compile_path_ops(
            tree, query, rel, schema, batch, store_views, {}, densify,
            intern, apply_views=False)
        cost_row, cost_dense, _ = path_costs(path, schema, batch, query)
        ops = (intern(Reevaluate("store")),) + path_ops + (
            _scatter_op(query, root, views[root],
                        _SymDelta(coo=(), dense=(), b=1, pending=False,
                                  ring=query.ring)),
            intern(BaseBump(rel, active_backend_override())))
        return TriggerPlan(
            rel=rel, kind="first_order", strategy=strategy, schema=schema,
            batch=batch, densify=densify, ops=ops, ind_ops=(),
            write_views=frozenset({root}), write_base=frozenset({rel}),
            write_indicators=frozenset(),
            cost=cost_dense if densify else cost_row)

    # fivm / dbt: higher-order propagation along the delta tree
    ind_meta = {name: (tuple(ind.proj), ind.dense)
                for name, ind in engine.indicators.items()}
    path = views_on_path(tree, rel)
    if kind == "coo":
        densify = should_densify(path, schema, batch, query)
    else:
        densify = False
    if kind == "factorized":
        ops, write_views = _compile_factorized_ops(
            tree, query, rel, schema, views, ind_meta, intern)
        cost = 0
    else:
        ops, write_views = _compile_path_ops(
            tree, query, rel, schema, batch, views, ind_meta, densify,
            intern)
        cost_row, cost_dense, _ = path_costs(path, schema, batch, query)
        cost = cost_dense if densify else cost_row
    write_base = frozenset({rel}) & frozenset(engine.base)
    ind_ops, ind_write_views, write_inds = _compile_indicator_ops(
        tree, query, rel, batch or 1, views, engine.indicators, intern)
    if ind_ops and kind == "factorized":
        raise AssertionError("indicator maintenance needs COO updates")
    return TriggerPlan(
        rel=rel, kind=kind, strategy=strategy, schema=schema, batch=batch,
        densify=densify, ops=ops, ind_ops=ind_ops,
        write_views=frozenset(write_views | ind_write_views),
        write_base=write_base, write_indicators=frozenset(write_inds),
        cost=cost)


class _DenseProxy:
    """Compile-time stand-in for a 1-IVM recomputed store view (always
    dense: ``evaluate_view`` materializes densely)."""

    def __init__(self, node: ViewNode, query: Query):
        self.schema = tuple(node.schema)
        self._query = query

    def domain_of(self, var: str) -> int:
        return int(self._query.domains[var])


def _compile_factorized_ops(tree: ViewNode, query: Query, rel: str,
                            upd_schema, views: Mapping, ind_meta, intern):
    """Sec. 5 Optimize: the same path, interpreted over a factor list.
    Joins absorb into touching factors, marginalization always contracts
    against the lift relation (no identity skip — mirror of the eager
    factorized walk), application is the outer-product accumulate."""
    path = views_on_path(tree, rel)
    ops: list = []
    write_views: set[str] = set()

    def scatter(name):
        view = views[name]
        ops.append(intern(ScatterAccum(name, _storage_kind(view),
                                       backend=None)))
        write_views.add(name)

    leaf = path[0]
    ops.append(intern(LeafDelta(rel, tuple(upd_schema), 0, False)))
    ops.append(intern(Emit(leaf.name)))
    if leaf.name in views:
        scatter(leaf.name)
    child = leaf
    for node in path[1:]:
        for sib in node.children:
            if sib is child:
                continue
            assert sib.name in views, f"sibling {sib.name} not materialized"
            ops.append(intern(JoinContract(
                sib.name, tuple(sib.schema), _storage_kind(views[sib.name]),
                densifies=_storage_kind(views[sib.name]) == "sparse")))
        if node.indicator is not None:
            proj, _ind = ind_meta[node.name]
            ops.append(intern(JoinContract(IND_PREFIX + node.name, proj,
                                           "dense")))
        wname = f"W:{node.name}"
        if wname in views:
            scatter(wname)
        for v in node.marg_vars:
            ops.append(intern(Lift(v, tuple(query.lift_spec(v)))))
            ops.append(intern(Marginalize(v, "factor")))
        ops.append(intern(Emit(node.name)))
        if node.name in views:
            scatter(node.name)
        child = node
    return tuple(ops), write_views


def path_to_root(tree: ViewNode, name: str) -> list[ViewNode]:
    """Node-to-root spine (indicator propagation paths)."""
    path: list[ViewNode] = []

    def rec(node: ViewNode) -> bool:
        if node.name == name:
            path.append(node)
            return True
        for c in node.children:
            if rec(c):
                path.append(node)
                return True
        return False

    assert rec(tree)
    return path


# ---------------------------------------------------------------------------
# The plan-level fusion pass (DESIGN.md §13)
# ---------------------------------------------------------------------------
def _try_fuse_chain(ops, start: int, coo: tuple, views: Mapping,
                    query: Query, written, spec, width: int):
    """Try to grow a fused chain from ``ops[start]`` to the first terminal
    ScatterAccum.  Returns ``(FusedChain, coo_after)`` or None when any op
    on the way is outside the fused vocabulary or violates the tile/VMEM
    model (the fallback matrix in DESIGN.md §13)."""
    from repro.kernels import ring_fused

    cur = list(coo)
    src_rows: list[int] = []
    reads: list[str] = []
    n_mul = 0
    collapsed = False
    j = start
    while j < len(ops):
        op = ops[j]
        if isinstance(op, Gather):
            # indicator planes and views this plan already wrote stay
            # unfused (read-after-write inside one trigger must see the
            # op-by-op ordering); source planes ride whole in VMEM, so
            # their row count is bounded
            if collapsed or op.view.startswith(IND_PREFIX) \
                    or op.view in written or op.view not in views:
                return None
            view = views[op.view]
            if _storage_kind(view) == "sparse":
                rows = int(view.capacity) + 1
            else:
                rows = _domain_extent(query, op.vars)
            if rows > ring_fused.MAX_FUSED_PLANE:
                return None
            src_rows.append(rows)
            reads.append(op.view)
            n_mul += 1
        elif isinstance(op, Lift):
            if collapsed:
                return None
            src_rows.append(int(query.domains[op.var]))
            n_mul += 1
        elif isinstance(op, Marginalize):
            # only COO marginalization stays a key-column drop (+ lift
            # source) inside the chain; dense-axis contraction falls back
            if op.axis != "coo" or op.var not in cur:
                return None
            cur.remove(op.var)
            if op.collapses:
                collapsed = True
        elif isinstance(op, Emit):
            pass
        elif isinstance(op, ScatterAccum):
            # terminal ⊎: dense or hashed-COO slot scatter fits the tile
            # model; mixed (dense-axes) applies don't.  A chain with no
            # gather/lift source is just a scatter — no fusion win.
            if op.mixed or op.view.startswith(IND_PREFIX) or n_mul == 0:
                return None
            vmem = ring_fused.chain_vmem_bytes(src_rows, width)
            if vmem > ring_fused.VMEM_BUDGET:
                return None
            fused = FusedChain(ops=tuple(ops[start:j + 1]),
                               reads=tuple(reads), writes=(op.view,),
                               vmem_bytes=vmem, spec=spec)
            return fused, tuple(cur)
        else:  # LeafDelta / JoinContract / BaseBump / ... : not fusable
            return None
        j += 1
    return None


def fuse_trigger_ops(plan: TriggerPlan, query: Query,
                     views: Mapping) -> TriggerPlan:
    """The plan-level fusion pass: collapse maximal
    Gather→Lift→(Marginalize)→Emit→ScatterAccum subsequences of a COO
    trigger plan into :class:`FusedChain` ops lowered by
    ``repro.kernels.ring_fused``.

    Legality is decided here, at plan time: commutative-bilinear f32 ring
    (``ring_fused.fused_ring_spec``), pure-COO delta state at the chain
    boundary (no dense axes, no carried pending gather), gathered source
    planes bounded by the VMEM tile model, and a terminal non-mixed
    scatter whose write set is disjoint from the chain's reads.
    Everything else falls back op-by-op — the unfused interpreter remains
    the oracle.  Indicator sections never fuse (they read views updated
    in place mid-trigger)."""
    if plan.kind != "coo" or plan.densify:
        return plan
    from repro.kernels import ring_fused

    spec = ring_fused.fused_ring_spec(query.ring)
    if spec is None:
        return plan
    width = _payload_width(query.ring)
    ops = list(plan.ops)
    out: list = []
    # symbolic mirror of the runtime delta state at each op boundary —
    # chains may only start where the delta is pure-COO with no pending
    # gather, so the flat-plane product model is exact
    coo: tuple = ()
    pending = False
    dense = False
    written: set[str] = set()
    i = 0
    while i < len(ops):
        fused = None
        if not pending and not dense and coo:
            fused = _try_fuse_chain(ops, i, coo, views, query, written,
                                    spec, width)
        if fused is not None:
            chain, coo = fused
            out.append(chain)
            written.add(chain.writes[0])
            pending = False
            i += len(chain.ops)
            continue
        op = ops[i]
        if isinstance(op, LeafDelta):
            coo = () if op.densify else tuple(op.schema)
            dense = bool(op.densify)
            pending = False
        elif isinstance(op, Gather):
            pending = True
        elif isinstance(op, JoinContract):
            pending = False
            if op.grows or op.densifies:
                dense = True
        elif isinstance(op, Marginalize):
            if op.forces:
                pending = False
            if op.axis == "coo":
                coo = tuple(v for v in coo if v != op.var)
        elif isinstance(op, ScatterAccum):
            written.add(op.view)
        out.append(op)
        i += 1
    if not any(isinstance(op, FusedChain) for op in out):
        return plan
    return dataclasses.replace(plan, ops=tuple(out))


# ---------------------------------------------------------------------------
# The plan cache
# ---------------------------------------------------------------------------
def storage_signature(views: Mapping) -> tuple:
    """Hashable storage-layout fingerprint: a plan is only valid for the
    exact (backend kind, capacity) layout it was compiled against — a
    sparse rehash between stream segments recompiles."""
    from . import storage

    sig = []
    for name in sorted(views):
        v = views[name]
        if isinstance(v, storage.SparseRelation):
            sig.append((name, "s", v.capacity))
        else:
            sig.append((name, "d", 0))
    return tuple(sig)


class PlanCache:
    """Per-engine trigger-plan cache with op interning.

    Keys: (rel, update signature, storage layout, scatter-backend
    override, fusion mode).  ``hits``/``miss_new``/``miss_invalidated``/
    ``compile_seconds`` feed the bench telemetry — ``miss_new`` counts
    first compiles of a (rel, update-signature) trigger, while
    ``miss_invalidated`` counts recompiles of a previously-seen trigger
    forced by a layout / backend-override / fusion-mode change, so the
    on/off sweeps report honest cache behavior.  Interned ops let sibling
    triggers share structurally identical subtrees (the plan-level CSE
    substrate)."""

    def __init__(self):
        self.plans: dict = {}
        self.hits = 0
        self.miss_new = 0
        self.miss_invalidated = 0
        self.compile_seconds = 0.0
        self.verify_seconds = 0.0
        self._interned: dict = {}
        self._write_sets: dict = {}
        self._seen: set = set()

    @property
    def misses(self) -> int:
        return self.miss_new + self.miss_invalidated

    def intern(self, op: PlanOp) -> PlanOp:
        return self._interned.setdefault(op, op)

    def lookup_sig(self, engine, rel: str, upd_sig,
                   views=None) -> TriggerPlan:
        views = engine.views if views is None else views
        key = (rel, upd_sig, storage_signature(views),
               active_backend_override(), fusion_mode())
        plan = self.plans.get(key)
        if plan is not None:
            self.hits += 1
            return plan
        trigger = (rel, upd_sig)
        if trigger in self._seen:
            self.miss_invalidated += 1
        else:
            self.miss_new += 1
            self._seen.add(trigger)
        t0 = time.perf_counter()
        plan = compile_trigger(engine, rel, upd_sig, intern=self.intern,
                               views=views)
        if fusion_mode() == "on":
            plan = fuse_trigger_ops(plan, engine.query, views)
        self.compile_seconds += time.perf_counter() - t0
        # static invariant verification (DESIGN.md §14) rides the compile
        # miss only: a verified plan is cached as verified, so replay —
        # every cache hit above — pays nothing
        from repro.analysis import verifier as verifier_mod

        if verifier_mod.verify_mode() == "on":
            t1 = time.perf_counter()
            verifier_mod.check_plan(engine, plan, views=views)
            self.verify_seconds += time.perf_counter() - t1
        self.plans[key] = plan
        return plan

    def lookup(self, engine, rel: str, upd, views=None) -> TriggerPlan:
        if isinstance(upd, FactorizedUpdate):
            sig = ("factorized", tuple(upd.schema))
        else:
            sig = ("coo", tuple(upd.schema), upd.batch)
        return self.lookup_sig(engine, rel, sig, views=views)

    def write_sets(self, engine, rel: str):
        """Structural write sets for ``rel`` (independent of batch size and
        storage layout): the views/base/indicator entries any trigger for
        ``rel`` may replace.  Drives eager-path growth and the stream
        executor's mutable/const state partition.

        Memoized under the same environment key as the plan cache itself
        (storage layout, backend override, fusion mode) — keying by ``rel``
        alone let a mid-session layout or fusion-mode flip serve a
        write-set derived from an invalidated plan."""
        key = (rel, storage_signature(engine.views),
               active_backend_override(), fusion_mode())
        if key not in self._write_sets:
            # representative signature: write sets do not depend on the
            # update's batch or on densification
            sig = ("coo", tuple(engine.query.relations[rel]), 1)
            plan = self.lookup_sig(engine, rel, sig)
            self._write_sets[key] = plan.write_sets()
        return self._write_sets[key]

    def stats(self) -> dict:
        total = self.hits + self.misses
        n = len(self.plans)
        return dict(
            plans=n,
            hits=self.hits,
            misses=self.misses,
            miss_new=self.miss_new,
            miss_invalidated=self.miss_invalidated,
            hit_rate=round(self.hits / total, 4) if total else 0.0,
            #: cumulative across every compile on this engine
            compile_ms_total=round(1e3 * self.compile_seconds, 3),
            #: average per compiled trigger plan
            compile_ms_per_plan=round(1e3 * self.compile_seconds / n, 3)
            if n else 0.0,
            #: compile-time static verification (DESIGN.md §14); cache
            #: hits never re-verify, so this amortizes to zero on replay
            verify_ms_total=round(1e3 * self.verify_seconds, 3),
            interned_ops=len(self._interned),
        )


# ---------------------------------------------------------------------------
# Interpreters
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class PropagationResult:
    """Deltas per affected view name (leaf-to-root order) + updated views.

    ``updated`` values carry each view's planned storage backend
    (``ViewStorage``): a dense view stays dense, a hashed-COO view stays
    sparse — the delta algebra dispatches per storage."""

    deltas: dict
    updated: dict


def _resolve_view(name: str, views: Mapping, ind_dense: Mapping):
    if name.startswith(IND_PREFIX):
        return ind_dense[name[len(IND_PREFIX):]]
    return views[name]


def run_coo_ops(ops, views: Mapping, query: Query, upd: COOUpdate,
                ind_dense: Mapping, memo: Mapping | None = None,
                delta: BatchedDelta | None = None,
                updated: dict | None = None) -> PropagationResult:
    """Replay a compiled COO path section.  Performs exactly the
    delta-algebra calls the interpretive walk made (bit-identical); the
    plan's annotations only *direct* — backend hints thread into the
    scatters, memoized sibling planes short-circuit the prepare step."""
    ring = query.ring
    deltas: dict = {}
    updated = {} if updated is None else updated
    pending_lift = None
    for op in ops:
        if isinstance(op, LeafDelta):
            delta = (densified_delta(query, op.rel, upd) if op.densify
                     else BatchedDelta.from_coo(ring, upd))
        elif isinstance(op, Gather):
            view = _resolve_view(op.view, views, ind_dense)
            plane = memo.get(("plane", op.view)) if memo else None
            with jax.named_scope("fivm.gather"):
                delta = delta.join_dense(view, src_plane=plane)
        elif isinstance(op, JoinContract):
            view = _resolve_view(op.view, views, ind_dense)
            if op.densifies and memo:
                view = memo.get(("dense", op.view), view)
            delta = delta.join_dense(view)
        elif isinstance(op, Lift):
            pending_lift = query.lift_rel(op.var)
        elif isinstance(op, Marginalize):
            delta = delta.marginalize(op.var, pending_lift)
            pending_lift = None
        elif isinstance(op, Emit):
            deltas[op.view] = delta
        elif isinstance(op, ScatterAccum):
            with jax.named_scope("fivm.scatter"):
                updated[op.view] = delta.apply_to(views[op.view],
                                                  backend=op.backend)
        elif isinstance(op, FusedChain):
            with jax.named_scope("fivm.fused_chain"):
                delta = _run_fused_chain(op, delta, views, query, ind_dense,
                                         memo, deltas, updated)
        else:  # pragma: no cover
            raise TypeError(op)
    return PropagationResult(deltas, updated)


def _run_fused_chain(chain: FusedChain, delta: BatchedDelta, views: Mapping,
                     query: Query, ind_dense: Mapping, memo, deltas: dict,
                     updated: dict) -> BatchedDelta:
    """Interpret a :class:`FusedChain`.

    Two lowerings, resolved once from the chain's terminal ScatterAccum:

    * **megakernel** (TPU real / interpret) — gather/lift sources
      accumulate as flat ``(plane [Sg, d], ids [B])`` pairs; the whole
      product + ⊎ runs through one ``ring_fused.fused_apply`` dispatch at
      the terminal scatter, source planes resident in VMEM.  The
      terminal's backend hint picks the sweep of the whole view or,
      past the onehot/compact crossover, the compact ⊎ over the
      batch's ranked keys.
    * **flat-XLA** (CPU/GPU) — sources gather as per-component payload
      dicts (``view.gather``; no flat-plane concats at all), the running
      product is one ``Ring.mul`` per hop (``ring_mul_flat`` is its flat
      mirror, term order and add association identical), and the ⊎
      scatters B rows per component — the same adds element-for-element
      as the megakernel, so results agree bit for bit on integer-valued
      payloads.

    Either way the materialized end-of-chain delta is returned (the
    op-by-op continuation state; DCE'd under jit when nothing downstream
    reads it).  Plan-time legality (:func:`fuse_trigger_ops`) guarantees
    the entry state: pure-COO delta, no pending gather, fused-ring
    payload."""
    from repro.core import storage
    from repro.kernels import ring_fused

    ring = query.ring
    spec = chain.spec
    assert delta.pending_gather is None and not delta.dense_schema, (
        "fused chain entered with non-pure-COO delta state")
    term = chain.ops[-1]
    assert isinstance(term, ScatterAccum)
    xla = ring_fused.resolve_backend(term.backend) == "fused_xla"
    coo = list(delta.coo_schema)
    keys = delta.keys
    B = delta.batch
    vals = (None if xla
            else storage.flatten_payload(ring, delta.payload, (B,)))
    sources: list = []      # megakernel path: (plane, ids) pairs
    row_factors: list = []  # flat-XLA path: gathered [B, *comp] payloads
    lift_rel = None
    collapsed = False
    join_cache: dict = {}

    def joined():
        """Running product over the sources applied so far — a flat
        ``[B, d]`` plane (megakernel) or a payload dict (flat-XLA) —
        computed once per source-list state (Emit, the continuation, and
        the flat-XLA scatter all reuse it)."""
        n = len(row_factors) if xla else len(sources)
        if n not in join_cache:
            if xla:
                cur = delta.payload
                for g in row_factors:
                    cur = ring.mul(cur, g)
            else:
                cur = vals
                for plane, ids in sources:
                    g = jnp.take(plane, ids, axis=0, mode="clip")
                    cur = ring_fused.ring_mul_flat(cur, g, spec)
            join_cache[n] = cur
        return join_cache[n]

    def materialize() -> BatchedDelta:
        cur = joined()
        k = keys if not collapsed else keys[:1]
        if xla:
            payload = ({c: jnp.sum(v, axis=0, keepdims=True)
                        for c, v in cur.items()} if collapsed else cur)
        else:
            if collapsed:
                cur = jnp.sum(cur, axis=0, keepdims=True)
            payload = storage.unflatten_payload(ring, cur, (k.shape[0],),
                                                dtype=ring.dtype)
        return BatchedDelta(coo_schema=tuple(coo), dense_schema=(),
                            keys=k, ring=ring, payload=payload,
                            dense_domains=())

    def view_keys(schema):
        return jnp.stack([keys[:, coo.index(v)] for v in schema], axis=1)

    for op in chain.ops:
        if isinstance(op, Gather):
            view = _resolve_view(op.view, views, ind_dense)
            kv = view_keys(view.schema)
            plane = memo.get(("plane", op.view)) if memo else None
            if xla and plane is None:
                # row gather: per-component takes, no flat-plane concat
                row_factors.append(view.gather(kv))
                continue
            if isinstance(view, storage.SparseRelation):
                slots, found = view.lookup(kv)
                if plane is None:
                    plane = view.gather_plane()
                ids = jnp.where(found, slots, view.capacity)
            else:
                if plane is None:
                    plane = storage.flatten_payload(ring, view.payload,
                                                    view.domains)
                ids = storage.linear_ids(kv, view.domains)
            if xla:  # memoized plane (stream-step CSE): flat row take
                rows = jnp.take(plane, ids.astype(jnp.int32), axis=0,
                                mode="clip")
                row_factors.append(storage.unflatten_payload(
                    ring, rows, (B,), dtype=ring.dtype))
            else:
                sources.append((plane, ids.astype(jnp.int32)))
        elif isinstance(op, Lift):
            lift_rel = query.lift_rel(op.var)
        elif isinstance(op, Marginalize):
            i = coo.index(op.var)
            if lift_rel is not None:
                ids = keys[:, i].astype(jnp.int32)
                if xla:
                    row_factors.append({c: lift_rel.payload[c][ids]
                                        for c in ring.components})
                else:
                    dom = int(lift_rel.payload[
                        next(iter(ring.components))].shape[0])
                    sources.append((storage.flatten_payload(
                        ring, lift_rel.payload, (dom,)), ids))
                lift_rel = None
            keys = jnp.delete(keys, i, axis=1, assume_unique_indices=True)
            coo.pop(i)
            if op.collapses:
                collapsed = True
        elif isinstance(op, Emit):
            deltas[op.view] = materialize()
        elif isinstance(op, ScatterAccum):
            view = views[op.view]
            if isinstance(view, storage.SparseRelation):
                table, target = view.fused_slot_targets(
                    view_keys(view.schema))
                if xla:  # B-row ⊎ per component, overflow rows drop
                    safe = jnp.where(target < 0, view.capacity, target)
                    cur = joined()
                    updated[op.view] = view.replace_payload(table, {
                        c: view.payload[c].at[safe].add(cur[c],
                                                        mode="drop")
                        for c in ring.components})
                else:
                    plane = storage.flatten_payload(ring, view.payload,
                                                    (view.capacity,))
                    out = ring_fused.fused_apply(plane, target, vals,
                                                 sources, spec,
                                                 backend=op.backend)
                    updated[op.view] = view.replace_plane(table, out)
            elif xla:
                # scatter the joined product per component — B rows of
                # ``.at[].add`` instead of round-tripping the whole view
                # plane through a flat copy
                cur = joined()
                if view.schema:
                    updated[op.view] = view.scatter_add(
                        view_keys(view.schema), cur, backend="jnp")
                else:  # collapsed-to-scalar view: ⊎ is the batch sum
                    updated[op.view] = DenseRelation(
                        view.schema, ring,
                        {c: view.payload[c] + jnp.sum(cur[c], axis=0)
                         for c in ring.components})
            else:
                if view.schema:
                    ids = storage.linear_ids(view_keys(view.schema),
                                             view.domains)
                else:  # collapsed-to-scalar view: every row hits slot 0
                    ids = jnp.zeros((keys.shape[0],), jnp.int32)
                plane = storage.flatten_payload(ring, view.payload,
                                                view.domains)
                out = ring_fused.fused_apply(plane, ids, vals, sources,
                                             spec, backend=op.backend)
                payload = storage.unflatten_payload(ring, out, view.domains,
                                                    dtype=ring.dtype)
                updated[op.view] = DenseRelation(view.schema, ring, payload)
        else:  # pragma: no cover
            raise TypeError(op)
    return materialize()


def run_factorized_ops(ops, views: Mapping, query: Query,
                       upd: FactorizedUpdate,
                       ind_dense: Mapping) -> PropagationResult:
    """Replay a compiled factorized (Sec. 5 Optimize) path section over a
    factor list: joins absorb, marginalization touches only the factor
    containing the variable, application is the outer-product ⊎."""
    ring = query.ring
    factors: list[DenseRelation] = list(upd.factors)
    deltas: dict = {}
    updated: dict = {}

    def current() -> FactorizedUpdate:
        sch = tuple(v for f in factors for v in f.schema)
        return FactorizedUpdate(sch, tuple(factors))

    for op in ops:
        if isinstance(op, LeafDelta):
            pass  # the factor list IS the leaf delta
        elif isinstance(op, JoinContract):
            view = _resolve_view(op.view, views, ind_dense)
            absorb_factor(factors, view, ring)
        elif isinstance(op, Lift):
            pass  # factorized marginalization always contracts the lift
        elif isinstance(op, Marginalize):
            marginalize_factor(factors, op.var, query)
        elif isinstance(op, Emit):
            deltas[op.view] = current()
        elif isinstance(op, ScatterAccum):
            updated[op.view] = apply_factorized(views[op.view], factors,
                                                ring)
        else:  # pragma: no cover
            raise TypeError(op)
    return PropagationResult(deltas, updated)


def run_indicator_ops(ops, views: dict, indicators: dict, query: Query,
                      upd: COOUpdate, old_base) -> None:
    """Replay indicator sections *in place*: each IndicatorBump computes
    the transition-count delta δ∃ and the following ops propagate it to
    the root, reading (and immediately writing) the already-updated
    views."""
    ring = query.ring
    delta = None
    pending_lift = None
    for op in ops:
        if isinstance(op, IndicatorBump):
            st = indicators[op.node]
            assert isinstance(upd, COOUpdate), (
                "indicator maintenance needs COO updates")
            assert old_base is not None, (
                "indicator relations must be stored")
            new_state, dind = st.delta_for_update(query, upd, old_base)
            indicators[op.node] = new_state
            delta = BatchedDelta.from_coo(ring, dind)
        elif isinstance(op, (Gather, JoinContract)):
            ind_dense = {n: s.dense for n, s in indicators.items()}
            view = _resolve_view(op.view, views, ind_dense)
            delta = delta.join_dense(view)
        elif isinstance(op, Lift):
            pending_lift = query.lift_rel(op.var)
        elif isinstance(op, Marginalize):
            delta = delta.marginalize(op.var, pending_lift)
            pending_lift = None
        elif isinstance(op, ScatterAccum):
            views[op.view] = delta.apply_to(views[op.view],
                                            backend=op.backend)
        else:  # pragma: no cover
            raise TypeError(op)


def reevaluate_store(engine, base) -> dict:
    """The ``Reevaluate`` op's interpretation: evaluate the view tree
    bottom-up from ``base`` relations, returning every node's view.

    Shared by ``execute_trigger``'s reeval / first-order kinds and by the
    integrity layer's audited reconciliation (repro.runtime.integrity,
    DESIGN.md §11) — one interpreter, whether Reevaluate runs as a
    maintenance strategy or as the self-healing ground truth.  Premarg
    ``W:`` views are recomputed when the engine maintains them."""
    store: dict = {}
    premarg = any(name.startswith("W:") for name in engine.views)
    evaluate_view(engine.tree, base, engine.query, store=store,
                  premarg=premarg)
    return store


def execute_trigger(engine, plan: TriggerPlan, views, base, indicators,
                    upd, memo: Mapping | None = None):
    """Run a compiled trigger: the single execution entry shared by eager
    ``apply_update``, jitted per-call triggers, and every fused-stream
    dispatch mode.  Returns new ``(views, base, indicators)``."""
    query = engine.query
    views = dict(views)
    base = dict(base)
    indicators = dict(indicators)

    if plan.kind == "reeval":
        base[plan.rel] = engine._bump_base(base[plan.rel], upd)
        store = reevaluate_store(engine, base)
        views[engine.tree.name] = store[engine.tree.name]
        return views, base, indicators

    if plan.kind == "first_order":
        if isinstance(upd, FactorizedUpdate):
            upd = densify_update_to_coo(query, upd)
        store = reevaluate_store(engine, base)
        from .indicators import indicator_of

        ind_dense = {
            name: indicator_of(base[st.rel_name], st.proj, query)
            for name, st in indicators.items()
        }
        path_ops = tuple(op for op in plan.ops
                         if not isinstance(op, (Reevaluate, BaseBump,
                                                ScatterAccum)))
        res = run_coo_ops(path_ops, store, query, upd, ind_dense)
        root = engine.tree.name
        delta = res.deltas[root]
        assert isinstance(delta, BatchedDelta)
        views[root] = delta.apply_to(views[root])
        base[plan.rel] = engine._bump_base(base[plan.rel], upd)
        return views, base, indicators

    # fivm / dbt
    old_base = base.get(plan.rel)
    ind_dense = {name: st.dense for name, st in indicators.items()}
    if plan.kind == "factorized":
        res = run_factorized_ops(plan.ops, views, query, upd, ind_dense)
    else:
        res = run_coo_ops(plan.ops, views, query, upd, ind_dense, memo=memo)
    views.update(res.updated)
    if plan.write_base:
        with jax.named_scope("fivm.base_bump"):
            base[plan.rel] = engine._bump_base(base[plan.rel], upd)
    if plan.ind_ops:
        with jax.named_scope("fivm.indicator"):
            run_indicator_ops(plan.ind_ops, views, indicators, query, upd,
                              old_base)
    return views, base, indicators


# ---------------------------------------------------------------------------
# Delta-construction helpers (shared with the eager wrappers in delta.py)
# ---------------------------------------------------------------------------
def densified_delta(query: Query, rel: str, upd: COOUpdate) -> BatchedDelta:
    """Scatter the COO batch into a dense delta relation over the update
    schema, carried as a BatchedDelta with batch=1 and no COO vars."""
    ring = query.ring
    doms = tuple(query.domains[v] for v in upd.schema)
    dense = DenseRelation.from_coo(upd.schema, ring, doms, upd.keys,
                                   upd.payload)
    payload = {c: dense.payload[c][None] for c in ring.components}
    return BatchedDelta(
        coo_schema=(),
        dense_schema=tuple(upd.schema),
        keys=jnp.zeros((1, 0), jnp.int32),
        ring=ring,
        payload=payload,
        dense_domains=doms,
    )


def densify_update_to_coo(query: Query, upd: FactorizedUpdate) -> COOUpdate:
    """1-IVM takes the full (densified) delta — that is the point of the
    comparison in Sec. 8.3."""
    ring = query.ring
    dense = upd.densify(ring)
    b = int(np.prod([dense.domain_of(v) for v in dense.schema]))
    doms = [dense.domain_of(v) for v in dense.schema]
    grids = np.meshgrid(*[np.arange(d) for d in doms], indexing="ij")
    keys = jnp.asarray(np.stack([g.ravel() for g in grids],
                                axis=1).astype(np.int32))
    payload = {
        c: dense.payload[c].reshape((b, *ring.components[c]))
        for c in ring.components
    }
    return COOUpdate(dense.schema, keys, payload)


def lift_or_none(query: Query, var: str):
    """None for identity lifts: g(x)=1 multiplies by ring one, so the
    marginalization is a plain sum — skipping the gather+einsum halves the
    op count of unlifted variables (most join variables)."""
    if query.lift_spec(var) == ("one",):
        return None
    return query.lift_rel(var)


def absorb_factor(factors: list, view, ring) -> None:
    """Join a materialized sibling view into the factor list.  Factors
    whose variables intersect the view's schema merge first; disjoint
    factors stay independent (this is what preserves the factorized
    complexity).  Sparse siblings materialize first (the planner keeps
    factor-joined views dense)."""
    from .contraction import contract_dense

    if not isinstance(view, DenseRelation):
        view = view.to_dense()
    touching = [f for f in factors if set(f.schema) & set(view.schema)]
    if not touching:
        factors.append(view)  # cartesian sibling: keep as its own factor
        return
    for f in touching:
        factors.remove(f)
    acc = touching[0]
    for f in touching[1:]:
        acc = contract_dense(acc, f, marg=())
    acc = contract_dense(acc, view, marg=())
    factors.append(acc)


def marginalize_factor(factors: list, var: str, query: Query) -> None:
    from .contraction import contract_dense

    for i, f in enumerate(factors):
        if var in f.schema:
            factors[i] = contract_dense(f, query.lift_rel(var), marg=(var,))
            return
    raise KeyError(f"variable {var} not found in any factor")


def apply_factorized(view, factors: list, ring):
    """view ⊎ (⊗ factors): outer-product accumulate.  Cost is the size of
    the materialized view (O(p²) for matrix views), not of any larger
    product.  Scalar factors (fully-marginalized groups, e.g. ⊕_E δS_E in
    Example 5.2) scale the product.  A sparse view absorbs the product by
    *per-factor active-key enumeration* + slot scatter — the key grid never
    materializes over the full domain (eager path only; the active sets
    are read host-side)."""
    from .contraction import contract_dense

    covered = {v for f in factors for v in f.schema}
    assert covered == set(view.schema), (covered, view.schema)
    if not isinstance(view, DenseRelation):
        return apply_factorized_sparse(view, factors, ring)
    acc = factors[0]
    for f in factors[1:]:
        acc = contract_dense(acc, f, marg=())
    acc = acc.transpose(view.schema)
    return view.add(acc)


def apply_factorized_sparse(view, factors: list, ring):
    """Lower a FactorizedUpdate onto a hashed-COO view without densifying:
    enumerate each keyed factor's *active* (non-ring-zero) keys host-side,
    form the cartesian product of active rows, compute each row's payload
    as the ordered ring product of its factor values (the same multiply
    order as the dense outer product — bit-identical), and slot-scatter.
    Inserts ∏ active_i keys instead of the full domain product."""
    keyed = [f for f in factors if f.schema]
    actives = []
    for f in keyed:
        nz = np.argwhere(np.asarray(ring.is_zero(f.payload)) == False)  # noqa: E712
        if nz.shape[0] == 0:
            return view  # a ring-zero factor annihilates the product
        actives.append(nz.astype(np.int32))
    counts = [a.shape[0] for a in actives]
    B = 1
    for c in counts:
        B *= c
    grids = (np.meshgrid(*[np.arange(c) for c in counts], indexing="ij")
             if counts else [])
    rows = [jnp.asarray(g.ravel().astype(np.int32)) for g in grids]
    # per-row payload: multiply factor values in factor-list order (the
    # order the dense path's contract_dense chain uses)
    payload = None
    ki = 0
    for f in factors:
        if f.schema:
            idx = tuple(jnp.asarray(actives[ki][:, j])[rows[ki]]
                        for j in range(len(f.schema)))
            vals = {c: f.payload[c][idx] for c in ring.components}
            ki += 1
        else:
            vals = {c: jnp.broadcast_to(
                f.payload[c], (max(B, 1), *ring.components[c]))
                for c in ring.components}
        payload = vals if payload is None else ring.mul(payload, vals)
    # assemble key columns in the view's schema order
    cols = []
    for v in view.schema:
        for ki2, f in enumerate(keyed):
            if v in f.schema:
                j = f.schema.index(v)
                cols.append(jnp.asarray(actives[ki2][:, j])[rows[ki2]])
                break
    keys = jnp.stack(cols, axis=1) if cols else jnp.zeros((B, 0), jnp.int32)
    return view.scatter_add(keys, payload)


# ---------------------------------------------------------------------------
# Write-set → state-leaf mask (the switch-mode mutable/const partition)
# ---------------------------------------------------------------------------
def state_write_mask(state, write_views, write_base,
                     write_indicators) -> tuple:
    """Per-state-leaf mask (tree_flatten order): True iff the leaf belongs
    to an entry some plan's write-set names.  Replaces the old
    identity-diffing of representative trigger applications — the plan
    *is* the authority on what a trigger replaces."""
    views, base, indicators = state
    mask_tree = (
        {n: jax.tree.map(lambda _: n in write_views, v)
         for n, v in views.items()},
        {n: jax.tree.map(lambda _: n in write_base, v)
         for n, v in base.items()},
        {n: jax.tree.map(lambda _: n in write_indicators, v)
         for n, v in indicators.items()},
    )
    return tuple(jax.tree_util.tree_leaves(mask_tree))


# ---------------------------------------------------------------------------
# Collective placement (the multi-device sharding pass, DESIGN.md §9)
# ---------------------------------------------------------------------------
def read_sets(plans: Sequence[TriggerPlan]) -> frozenset:
    """Union of :meth:`TriggerPlan.read_views` across plans."""
    out: set = set()
    for p in plans:
        out |= p.read_views()
    return frozenset(out)


def collective_placement(plans: Sequence[TriggerPlan],
                         shardable) -> dict:
    """Decide, per view named by any plan, how it participates in a
    sharded carry — the plan-time collective pass consumed by
    ``repro.core.shard.plan_shards``.

    ``shardable`` maps view names to whether their storage layout *can*
    split along its key/slot axis (leading extent divisible by the mesh).
    The placement derives entirely from the compiled plans' op graph:

    * ``"scatter"``  — written via ScatterAccum and never read by key:
      the ⊎ routes each row to the shard owning its key/slot range; no
      read collective ever materializes the full axis.
    * ``"all_gather"`` — written *and* read by key (a sibling gather at
      arbitrary delta keys): the view shards for its writes, and each
      read lowers to gather-then-all-gather chosen here, at plan time.
    * ``"replicate"`` — read-only views, layouts that cannot split, and
      indicator planes: reads stay local, writes (if any) broadcast.
    """
    write_v: set = set()
    for p in plans:
        write_v |= set(p.write_views)
    read_v = read_sets(plans)
    placement: dict = {}
    for name in sorted(write_v | set(read_v)):
        if not shardable.get(name, False) or name not in write_v:
            placement[name] = "replicate"
        elif name in read_v:
            placement[name] = "all_gather"
        else:
            placement[name] = "scatter"
    return placement


# ---------------------------------------------------------------------------
# Plan-level CSE across a fused stream step
# ---------------------------------------------------------------------------
def shared_prep_ops(plans: Sequence[TriggerPlan]) -> tuple:
    """Sibling-view prepare steps shared by ≥ 2 plans of one fused stream
    step whose source view no plan in the step writes: their gather planes
    / densified forms are loop-computed once per step instead of once per
    position (the common gather/lift prefix of sibling triggers)."""
    # only fivm/dbt COO plans read carried views in their gather ops —
    # first_order/reeval plans gather from trigger-internal recomputed
    # stores, which never ride the carry
    plans = [p for p in plans if p.kind == "coo"]
    write_union: set[str] = set()
    for p in plans:
        write_union |= set(p.write_views)
    counts: dict = {}
    for p in plans:
        seen = set()
        # FusedChain subsequences expand: a fused gather still consumes
        # the memoized plane, so it participates in CSE like its unfused
        # form (the memo keys are identical)
        for op in iter_flat_ops(p.ops):
            key = None
            if isinstance(op, Gather) and not op.view.startswith(IND_PREFIX):
                key = ("plane", op.view)
            elif isinstance(op, JoinContract) and op.densifies \
                    and not op.view.startswith(IND_PREFIX):
                key = ("dense", op.view)
            if key is not None and key not in seen:
                seen.add(key)
                counts[key] = counts.get(key, 0) + 1
    return tuple(sorted(k for k, n in counts.items()
                        if n >= 2 and k[1] not in write_union))


def build_prep_memo(shared: tuple, views: Mapping) -> dict:
    """Materialize the shared prepare steps against the current state."""
    from . import storage

    memo: dict = {}
    for form, name in shared:
        v = views[name]
        if form == "plane":
            if isinstance(v, storage.SparseRelation):
                memo[(form, name)] = v.gather_plane()
            else:
                memo[(form, name)] = storage.flatten_payload(
                    v.ring, v.payload, v.domains)
        else:  # "dense"
            memo[(form, name)] = storage.as_dense(v)
    return memo
