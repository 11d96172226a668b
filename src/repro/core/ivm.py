"""The IVM engine: triggers + maintenance strategies (Sec. 4, Sec. 8).

Strategies:
  * ``fivm``    — F-IVM: one view tree, μ-chosen materialization, factorized
                  delta propagation (the paper's contribution).
  * ``fivm_1``  — first-order F-IVM: only the root is materialized; deltas
                  recompute sibling subtrees from base relations on the fly.
  * ``dbt``     — DBToaster-like fully-recursive higher-order IVM: every
                  view in the tree is materialized regardless of μ (models
                  DBT-RING's extra views; the scalar-payload DBT baseline is
                  built by running one engine per scalar aggregate, see
                  apps/regression.py).
  * ``reeval``  — full recomputation from stored base relations per update.

The DBToaster runtime role (codegen of triggers) is played in two stages
(DESIGN.md §8): ``repro.core.plan.compile_trigger`` compiles each
(relation, update-kind, storage layout) into a cached :class:`TriggerPlan`
— the fixed hierarchy of view updates the paper proves is task-independent
— and jax.jit lowers the plan's replay into one XLA program.  Eager
per-call updates, jitted triggers, and the fused stream executor all
execute the same plans.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import tracing

from . import plan as plan_mod
from . import storage as storage_mod
from .indicators import IndicatorState, add_indicators
from .materialize import choose_materialized
from .query import Query
from .relations import COOUpdate, DenseRelation, FactorizedUpdate
from .variable_orders import VariableOrder, heuristic_order
from .view_tree import ViewNode, build_view_tree, evaluate_view


@dataclasses.dataclass
class IVMEngine:
    query: Query
    tree: ViewNode
    materialized_names: set[str]
    views: dict[str, object]  # name -> ViewStorage (dense or sparse)
    base: dict[str, DenseRelation]
    indicators: dict[str, IndicatorState]  # keyed by node name carrying it
    strategy: str
    updatable: tuple[str, ...]
    store_base: bool
    #: per-view storage decisions (repro.core.storage.plan_storage)
    storage_plan: dict = dataclasses.field(default_factory=dict)
    #: compiled trigger plans (repro.core.plan), keyed per (relation,
    #: update signature, storage layout, backend override)
    plans: plan_mod.PlanCache = dataclasses.field(
        default_factory=plan_mod.PlanCache)

    # ------------------------------------------------------------------ build
    @classmethod
    def build(
        cls,
        query: Query,
        database: Mapping[str, DenseRelation],
        updatable: tuple[str, ...] | None = None,
        var_order: VariableOrder | None = None,
        strategy: str = "fivm",
        use_indicators: bool = False,
        fuse_chains: bool = True,
        premarg: bool = False,
        storage: str | None = None,
        storage_overrides: Mapping[str, str] | None = None,
        storage_opts: Mapping | None = None,
        store_base: bool | None = None,
    ) -> "IVMEngine":
        """Build an engine; ``storage`` selects the view-storage mode
        ("auto" | "dense" | "sparse"; default: ``REPRO_VIEW_STORAGE`` env
        var, else auto — the planner picks dense vs sparse per view from
        modeled domain product × fill).  ``storage_overrides`` forces a
        backend per view name; ``storage_opts`` are extra
        :func:`repro.core.storage.plan_storage` keywords (headroom,
        thresholds, capacities).

        ``store_base=True`` stores (and maintains, via each plan's
        ``write_base``) *every* base relation even under fivm / dbt —
        the prerequisite for the integrity layer's audited Reevaluate
        reconciliation and ``reevaluate_from_base`` self-healing
        (repro.runtime.integrity): views can only be recomputed from
        base relations that are actually kept.  Default (``None``)
        derives it from the strategy as before."""
        with tracing.span("fivm.build"):
            updatable = tuple(updatable if updatable is not None else query.relations)
            vo = var_order or heuristic_order(query)
            tree = build_view_tree(query, vo, fuse_chains=fuse_chains)
            if use_indicators:
                assert strategy in ("fivm", "dbt", "reeval"), (
                    "1-IVM has no intermediate views; indicator projections do not apply"
                )
                tree = add_indicators(tree, query)

            if strategy == "fivm":
                mat = choose_materialized(tree, updatable)
            elif strategy == "dbt":
                mat = {n.name for n in tree.walk()}
            elif strategy in ("fivm_1", "reeval"):
                mat = {tree.name} | {n.name for n in tree.walk() if n.is_leaf}
            else:  # pragma: no cover
                raise ValueError(strategy)

            store_base = strategy in ("fivm_1", "reeval") or bool(store_base)
            # indicator-bearing nodes need their base relation stored and all
            # children materialized when the indicator's relation is updatable
            indicators: dict[str, IndicatorState] = {}
            for n in tree.walk():
                if n.indicator is not None:
                    r, proj = n.indicator
                    indicators[n.name] = IndicatorState.init(r, database[r], proj, query)
                    if r in updatable:
                        mat |= {c.name for c in n.children}
                        mat |= {ln.name for ln in tree.walk() if ln.is_leaf and ln.relation == r}

            views: dict[str, DenseRelation] = {}
            store: dict[str, DenseRelation] = {}
            evaluate_view(tree, database, query, store=store, premarg=premarg)
            if premarg:
                # the factorized result representation: every pre-marginalization
                # view is part of the maintained output (Sec. 7.3)
                mat |= {k for k in store if k.startswith("W:")}
            for name in mat:
                views[name] = store[name]
            # storage planning: convert each materialized view to its planned
            # backend (dense small views, hashed-COO sparse large/low-fill ones)
            plan = storage_mod.plan_storage(
                views, tree=tree, updatable=updatable, strategy=strategy,
                mode=storage, overrides=storage_overrides,
                **dict(storage_opts or {}))
            views = storage_mod.apply_storage_plan(views, plan)
            # base relations are stored (as copies: leaf views alias the caller's
            # database arrays, and state donation requires every buffer in the
            # state pytree to appear exactly once) only where maintenance reads
            # them back: 1-IVM / reevaluation recompute from base, and indicator
            # transition counting needs the pre-update relation.  fivm / dbt
            # never read other base relations — storing them would just add a
            # dead scatter per update and inflate the stream executor's carry.
            need_base = set(query.relations) if store_base else {
                n.indicator[0] for n in tree.walk() if n.indicator is not None
            }
            base = {
                r: DenseRelation(rel.schema, rel.ring,
                                 {c: jnp.array(v) for c, v in rel.payload.items()})
                for r, rel in database.items() if r in need_base
            }
            return cls(
                query=query,
                tree=tree,
                materialized_names=mat,
                views=views,
                base=base,
                indicators=indicators,
                strategy=strategy,
                updatable=updatable,
                store_base=store_base,
                storage_plan=plan,
            )

    # ---------------------------------------------------------------- result
    def result(self) -> DenseRelation:
        """The root view, densely materialized (reporting API: callers
        index payload tensors positionally; a sparse root densifies here)."""
        return storage_mod.as_dense(self.views[self.tree.name])

    def result_storage(self):
        """The root view under its planned storage backend."""
        return self.views[self.tree.name]

    def num_materialized(self) -> int:
        return len(self.materialized_names)

    def memory_bytes(self) -> int:
        """View-state bytes under the actual storage backends (a sparse
        view counts its key table + payload plane, not the dense extent)."""
        total = 0
        for v in self.views.values():
            total += storage_mod.view_nbytes(v)
        for ind in self.indicators.values():
            total += ind.counts.size * 4
            total += storage_mod.view_nbytes(ind.dense)
        return total

    # ----------------------------------------------------------------- plans
    def trigger_plan(self, rel: str, upd) -> plan_mod.TriggerPlan:
        """The cached maintenance plan for an update like ``upd``."""
        return self.plans.lookup(self, rel, upd)

    def precompile(self, batch: int = 1) -> dict[str, plan_mod.TriggerPlan]:
        """Compile (and cache) the COO trigger plan of every updatable
        relation at the given batch size; returns them by relation."""
        return {
            rel: self.plans.lookup_sig(
                self, rel, ("coo", tuple(self.query.relations[rel]), batch))
            for rel in self.updatable
        }

    # ---------------------------------------------------------------- update
    def apply_update(self, rel: str, upd: COOUpdate | FactorizedUpdate) -> None:
        """Eager (per-call) update.  Sparse views in the trigger plan's
        write-set rehash to 2× capacity when this batch could cross the
        load-factor bound — growth needs a host sync, so it lives only on
        this path; jitted triggers and the stream executor keep capacities
        static (the planner's headroom covers them, and prepared streams
        grow between segments, see stream.StreamExecutor.run)."""
        assert rel in self.updatable, f"{rel} not declared updatable"
        touched, _, _ = self.plans.write_sets(self, rel)
        self.views = {
            name: (storage_mod.grow_if_loaded(
                       v, self._insert_budget(v, rel, upd))
                   if name in touched else v)
            for name, v in self.views.items()
        }
        views, base, indicators = self.functional_update(
            self.views, self.base, self.indicators, rel, upd
        )
        self.views, self.base, self.indicators = views, base, indicators

    def _insert_budget(self, view, rel: str, upd) -> int:
        """Worst-case distinct keys one update can insert into ``view``:
        B rows × the domain product of view variables the update does not
        bind (a mixed COO×dense apply enumerates that grid).  Factorized
        updates enumerate the cartesian product of per-factor *active* key
        sets (the sparse lowering never touches the full grid), so their
        budget is that product — bounded per variable by the factor's
        non-zero count.  ``grow_if_loaded`` clamps to the view's domain
        product."""
        if not isinstance(view, storage_mod.SparseRelation):
            return 0
        if not isinstance(upd, COOUpdate):
            ring = self.query.ring
            budget, seen = 1, set()
            for v in view.schema:
                if v in upd.schema:
                    f = upd.factor_for(v)
                    if id(f) in seen:
                        continue
                    seen.add(id(f))
                    active = int(np.asarray(
                        jnp.sum(~ring.is_zero(f.payload))))
                    budget *= active
                else:
                    budget *= int(self.query.domains[v])
            return budget
        extra = 1
        for v in view.schema:
            if v not in upd.schema:
                extra *= int(self.query.domains[v])
        return upd.batch * extra

    def trigger_body(self, rel: str, plan: plan_mod.TriggerPlan | None = None):
        """The pure (uncompiled) maintenance trigger for updates to ``rel``:
            body(state, upd) -> state
        with ``state = (views, base, indicators)``.  The output is
        canonicalized (see :func:`canonical_state`) so that every relation's
        trigger shares one stable state-pytree signature — the invariant the
        stream executor relies on to thread the state through ``lax.scan``
        carries and across ``lax.switch`` branches.  ``plan`` pins the
        compiled trigger plan (the stream executor embeds per-position
        plans); without it the engine's plan cache resolves per update
        signature.  ``memo`` carries per-step CSE results (shared sibling
        gather planes) inside fused rounds bodies."""

        def body(state, upd, memo=None):
            views, base, indicators = state
            return canonical_state(
                self.functional_update(views, base, indicators, rel, upd,
                                       plan=plan, memo=memo)
            )

        return body

    def make_trigger(self, rel: str):
        """Compile the maintenance trigger for updates to ``rel`` (the role
        DBToaster's code generator plays; here the backend is XLA and the
        source is the cached TriggerPlan).

        Returns a jitted pure function
            trigger(state, upd) -> state
        where ``state = (views, base, indicators)`` is a pytree.  Batch size
        of the update is static per compilation (pipeline pads batches).
        """
        # donate the state: views not touched by this trigger alias through,
        # and updated views are modified in place (no full-state copy)
        return jax.jit(self.trigger_body(rel), donate_argnums=(0,))

    @property
    def state(self):
        return (self.views, self.base, self.indicators)

    def canonical_state(self):
        """The engine state with every leaf coerced to a canonical (strong)
        dtype — the fixed point of every trigger's output signature."""
        return canonical_state(self.state)

    def set_state(self, state) -> None:
        self.views, self.base, self.indicators = state

    def shard_state(self, shard_plan) -> None:
        """Place the canonical state under a :class:`repro.core.shard.
        ShardPlan` — every leaf device_put to its planned NamedSharding
        (sharded views split their key/slot axis across the mesh, the
        rest replicate).  The sharded analogue of :meth:`canonical_state`:
        triggers and the stream executor run on the placed state
        unchanged, with GSPMD inserting the plan's collectives."""
        self.set_state(shard_plan.place(self.canonical_state()))

    def functional_update(self, views, base, indicators, rel: str, upd,
                          plan: plan_mod.TriggerPlan | None = None,
                          memo=None):
        """Pure update: returns new (views, base, indicators).  Fetches the
        cached :class:`TriggerPlan` for ``(rel, upd signature, storage
        layout)`` and replays it — the single execution path behind eager
        updates, jitted triggers, and every fused-stream dispatch mode."""
        assert rel in self.updatable, f"{rel} not declared updatable"
        if plan is None:
            plan = self.plans.lookup(self, rel, upd, views=views)
        return plan_mod.execute_trigger(self, plan, views, base, indicators,
                                        upd, memo=memo)

    def _bump_base(self, rel: DenseRelation, upd) -> DenseRelation:
        """Base-relation ⊎: COO batches go through the ring scatter
        dispatch layer (``DenseRelation.scatter_add``), which resolves the
        kernel backend at trace time — the choice is baked into the
        compiled trigger / stream program, so scan and switch bodies stay
        branch-free and donation-compatible."""
        if isinstance(upd, FactorizedUpdate):
            dense = upd.densify(self.query.ring).transpose(rel.schema)
            return rel.add(dense)
        return rel.scatter_add(upd.keys, upd.payload)


def canonical_state(state):
    """Strip weak types: coerce every leaf to its own (strong) dtype.

    Trigger traces mix host-literal arithmetic into the state, which can
    flip JAX weak-type flags between input and output.  Per-call jit absorbs
    that as a one-off retrace; ``lax.scan``/``lax.switch`` instead require
    bit-stable carry/branch signatures, so both the initial state and every
    trigger output pass through this normalization."""
    return jax.tree.map(
        lambda x: jax.lax.convert_element_type(x, jnp.asarray(x).dtype), state
    )
