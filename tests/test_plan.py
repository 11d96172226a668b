"""Trigger-plan IR (DESIGN.md §8): golden plans, cache behavior, and the
plan-only execution paths.

* **Golden plans** — compiled plans for the three apps (regression
  cofactor, matrix chain, conjunctive) pinned in their stable text form:
  any change to op emission, storage/backend annotation, densify decision,
  or write-set derivation shows up as a golden diff.
* **Plan-cache hit counter** — a second ``apply_update`` with the same
  update signature compiles nothing.
* **Sparse factorized lowering** — FactorizedUpdate onto a hashed-COO view
  via per-factor active-key enumeration + slot scatter, bit-identical to
  the dense oracle and never touching the full key grid.
* **Segment growth** — a raw stream whose worst-case insert budget crosses
  the 0.7 load factor mid-run splits into segments, rehashes between them,
  and recompiles (plans are keyed on the storage layout).
* **Plan-level CSE** — a fused rounds step computes sibling gather planes
  shared across positions (and written by none) once per step.
"""
import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (COOUpdate, DenseRelation, IVMEngine, Query,
                        SparseRelation, StreamExecutor, chain,
                        prepare_stream, sum_ring)
from repro.core import plan as plan_mod
from repro.core.apps import conjunctive, matrix_chain, regression


@pytest.fixture
def plain_env(monkeypatch):
    """Golden plans bake storage kinds and resolved scatter backends in;
    pin the environment the goldens were generated under (CPU auto
    resolution, auto storage) so the matrix CI legs that force sparse
    storage / kernel backends still compare against one text.  Scoped to
    the golden tests only — every other test in this file must run under
    whatever lowering the CI matrix forces."""
    monkeypatch.delenv("REPRO_VIEW_STORAGE", raising=False)
    monkeypatch.delenv("REPRO_SCATTER_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_PLAN_FUSION", raising=False)


# ---------------------------------------------------------------------------
# golden plans
# ---------------------------------------------------------------------------
def _regression_engine():
    rng = np.random.default_rng(0)
    rels = {"R": ("A", "B"), "S": ("A", "C")}
    doms = dict(A=3, B=4, C=5)
    mult = {n: jnp.asarray(rng.integers(0, 2, size=tuple(doms[v] for v in sch))
                           .astype(np.float32))
            for n, sch in rels.items()}
    return regression.build_cofactor_engine(
        rels, doms, mult, var_order=chain(["A"], {"A": [["B"], ["C"]]}))


GOLDEN_REGRESSION_R = """\
trigger R kind=coo strategy=fivm schema=[A,B] batch=4 densify=no cost=12
  Leaf rows[A,B; B=4]
  Emit[R]
  Lift[B degree.1]
  Marg[B coo]
  Emit[V0@B]
  Scatter[V0@B dense jnp]
  Gather[V1@C dense]
  Lift[A degree.0]
  Marg[A coo] collapse !force
  Emit[V2@A]
  Scatter[V2@A dense]
  writes: views=[V0@B,V2@A] base=[] indicators=[]"""

GOLDEN_REGRESSION_S = """\
trigger S kind=coo strategy=fivm schema=[A,C] batch=1 densify=no cost=3
  Leaf rows[A,C; B=1]
  Emit[S]
  Lift[C degree.2]
  Marg[C coo]
  Emit[V1@C]
  Scatter[V1@C dense jnp]
  Gather[V0@B dense]
  Lift[A degree.0]
  Marg[A coo]
  Emit[V2@A]
  Scatter[V2@A dense]
  writes: views=[V1@C,V2@A] base=[] indicators=[]"""

GOLDEN_CHAIN_A2 = """\
trigger A2 kind=factorized strategy=fivm schema=[X2,X3] batch=- densify=no cost=0
  Leaf factors[X2,X3]
  Emit[A2]
  Scatter[A2 dense]
  Join[A3 dense]
  Lift[X3 one]
  Marg[X3 factor]
  Emit[V0@X3]
  Scatter[V0@X3 dense]
  Join[A1 dense]
  Lift[X2 one]
  Marg[X2 factor]
  Emit[V3@X1]
  Scatter[V3@X1 dense]
  writes: views=[A2,V0@X3,V3@X1] base=[] indicators=[]"""

GOLDEN_CONJUNCTIVE_R = """\
trigger R kind=coo strategy=fivm schema=[A,B] batch=2 densify=no cost=6
  Leaf rows[A,B; B=2]
  Emit[R]
  Scatter[R dense jnp]
  Gather[V0@C dense]
  Scatter[W:V1@B dense jnp fused]
  Marg[B coo]
  Emit[V1@B]
  Scatter[W:V2@A dense jnp fused]
  Marg[A coo] collapse !force
  Emit[V2@A]
  Scatter[V2@A dense]
  writes: views=[R,V2@A,W:V1@B,W:V2@A] base=[] indicators=[]"""


GOLDEN_REGRESSION_R_FUSED = """\
trigger R kind=coo strategy=fivm schema=[A,B] batch=4 densify=no cost=12
  Leaf rows[A,B; B=4]
  Fused[5 ops → V0@B ring=degree.3 vmem=929792B]
    Emit[R]
    Lift[B degree.1]
    Marg[B coo]
    Emit[V0@B]
    Scatter[V0@B dense jnp]
  Fused[5 ops → V2@A ring=degree.3 vmem=1073152B]
    Gather[V1@C dense]
    Lift[A degree.0]
    Marg[A coo] collapse !force
    Emit[V2@A]
    Scatter[V2@A dense]
  writes: views=[V0@B,V2@A] base=[] indicators=[]"""

GOLDEN_CONJUNCTIVE_R_FUSED = """\
trigger R kind=coo strategy=fivm schema=[A,B] batch=2 densify=no cost=6
  Leaf rows[A,B; B=2]
  Emit[R]
  Scatter[R dense jnp]
  Fused[2 ops → W:V1@B ring=scalar vmem=929792B]
    Gather[V0@C dense]
    Scatter[W:V1@B dense jnp fused]
  Marg[B coo]
  Emit[V1@B]
  Scatter[W:V2@A dense jnp fused]
  Marg[A coo] collapse !force
  Emit[V2@A]
  Scatter[V2@A dense]
  writes: views=[R,V2@A,W:V1@B,W:V2@A] base=[] indicators=[]"""


def test_golden_plan_regression_cofactor(plain_env):
    eng = _regression_engine()
    assert eng.plans.lookup_sig(
        eng, "R", ("coo", ("A", "B"), 4)).pretty() == GOLDEN_REGRESSION_R
    assert eng.plans.lookup_sig(
        eng, "S", ("coo", ("A", "C"), 1)).pretty() == GOLDEN_REGRESSION_S


def test_golden_plan_matrix_chain_factorized(plain_env):
    rng = np.random.default_rng(0)
    mats = [jnp.asarray(rng.random((4, 3)).astype(np.float32)),
            jnp.asarray(rng.random((3, 5)).astype(np.float32)),
            jnp.asarray(rng.random((5, 2)).astype(np.float32))]
    eng = matrix_chain.build_chain_engine(mats)
    assert eng.plans.lookup_sig(
        eng, "A2", ("factorized", ("X2", "X3"))).pretty() == GOLDEN_CHAIN_A2


def test_golden_plan_conjunctive_factorized_representation(plain_env):
    rng = np.random.default_rng(0)
    rels = {"R": ("A", "B"), "S": ("B", "C")}
    doms = dict(A=3, B=3, C=3)
    mult = {n: rng.integers(0, 2, size=tuple(doms[v] for v in sch))
            .astype(np.float32) for n, sch in rels.items()}
    eng, _ = conjunctive.make_factorized_engine(
        rels, mult, chain(["A", "B", "C"]), doms)
    assert eng.plans.lookup_sig(
        eng, "R", ("coo", ("A", "B"), 2)).pretty() == GOLDEN_CONJUNCTIVE_R


# ---------------------------------------------------------------------------
# golden fused plans (DESIGN.md §13)
# ---------------------------------------------------------------------------
def test_golden_fused_plan_regression_cofactor(plain_env):
    """Fusion on: both maintenance chains collapse to FusedChain ops with
    pinned boundaries, write sets, ring specs, and VMEM estimates; the
    Leaf stays a fallback op (it constructs the delta, not a hop)."""
    with plan_mod.use_fusion("on"):
        eng = _regression_engine()
        p = eng.plans.lookup_sig(eng, "R", ("coo", ("A", "B"), 4))
    assert p.pretty() == GOLDEN_REGRESSION_R_FUSED
    from repro.kernels import ring_fused
    chains = [op for op in p.ops if isinstance(op, plan_mod.FusedChain)]
    assert len(chains) == 2
    assert all(c.vmem_bytes <= ring_fused.VMEM_BUDGET for c in chains)
    # fused plans report the same structural read/write sets as unfused
    assert p.read_views() == frozenset({"V1@C"})
    assert set(p.write_views) == {"V0@B", "V2@A"}


def test_golden_fused_plan_conjunctive_partial_chain(plain_env):
    """Conjunctive app: only the Gather→premarg-Scatter hop is fusible
    (the base-relation scatter and post-collapse tail stay op-by-op) —
    the fallback matrix in one golden."""
    rng = np.random.default_rng(0)
    rels = {"R": ("A", "B"), "S": ("B", "C")}
    doms = dict(A=3, B=3, C=3)
    mult = {n: rng.integers(0, 2, size=tuple(doms[v] for v in sch))
            .astype(np.float32) for n, sch in rels.items()}
    with plan_mod.use_fusion("on"):
        eng, _ = conjunctive.make_factorized_engine(
            rels, mult, chain(["A", "B", "C"]), doms)
        p = eng.plans.lookup_sig(eng, "R", ("coo", ("A", "B"), 2))
    assert p.pretty() == GOLDEN_CONJUNCTIVE_R_FUSED


def test_fusion_skips_factorized_and_int_ring_plans(plain_env):
    """Factorized plans and non-f32 rings are outside the fused algebra:
    fusion on must leave their plans byte-identical to fusion off."""
    rng = np.random.default_rng(0)
    mats = [jnp.asarray(rng.random((4, 3)).astype(np.float32)),
            jnp.asarray(rng.random((3, 5)).astype(np.float32)),
            jnp.asarray(rng.random((5, 2)).astype(np.float32))]
    with plan_mod.use_fusion("on"):
        eng = matrix_chain.build_chain_engine(mats)
        p = eng.plans.lookup_sig(eng, "A2", ("factorized", ("X2", "X3")))
    assert p.pretty() == GOLDEN_CHAIN_A2


def test_fusion_mode_resolution(monkeypatch):
    monkeypatch.delenv(plan_mod.FUSION_ENV_VAR, raising=False)
    if jax.default_backend() != "tpu":
        assert plan_mod.fusion_mode() == "off"  # auto keeps CPU unfused
    with plan_mod.use_fusion("on"):
        assert plan_mod.fusion_mode() == "on"
    monkeypatch.setenv(plan_mod.FUSION_ENV_VAR, "on")
    assert plan_mod.fusion_mode() == "on"
    with plan_mod.use_fusion("off"):  # explicit override beats env
        assert plan_mod.fusion_mode() == "off"


# ---------------------------------------------------------------------------
# fused ≡ unfused across dispatch modes × storage backends
# ---------------------------------------------------------------------------
def _regression_stream(q, schedule, b=4, seed=42):
    rng = np.random.default_rng(seed)
    ring = q.ring
    out = []
    for r in schedule:
        sch = q.relations[r]
        keys = np.stack([rng.integers(0, q.domains[v], size=b)
                         for v in sch], 1).astype(np.int32)
        payload = {**ring.zeros((b,)),
                   "c": jnp.asarray(rng.integers(-2, 3, b)
                                    .astype(np.float32))}
        out.append((r, COOUpdate(sch, jnp.asarray(keys), payload)))
    return out


@pytest.mark.parametrize("storage", ["dense", "sparse"])
@pytest.mark.parametrize("schedule,mode", [
    (["R"] * 6, "scan"),
    (["R", "S"] * 3, "rounds"),
    (["R", "S", "R", "R", "S"], "switch"),
])
def test_fused_stream_matches_unfused_oracle(schedule, mode, storage):
    """Every fused-stream dispatch mode must replay fused plans
    bit-identically to the unfused sequential oracle, dense and sparse
    (integer-valued f32 payloads ⇒ bitwise equality)."""
    def build():
        rng = np.random.default_rng(0)
        rels = {"R": ("A", "B"), "S": ("A", "C")}
        doms = dict(A=3, B=4, C=5)
        mult = {n: jnp.asarray(
            rng.integers(0, 2, size=tuple(doms[v] for v in sch))
            .astype(np.float32)) for n, sch in rels.items()}
        return regression.build_cofactor_engine(
            rels, doms, mult, var_order=chain(["A"], {"A": [["B"], ["C"]]}),
            storage=storage)

    with plan_mod.use_fusion("off"):
        oracle = build()
        stream = _regression_stream(oracle.query, schedule)
        for r, u in stream:
            oracle.apply_update(r, u)

    with plan_mod.use_fusion("on"):
        fused = build()
        prepared = prepare_stream(fused, stream)
        assert prepared.mode == mode
        assert prepared.fusion_sig == "on"
        assert any(isinstance(op, plan_mod.FusedChain)
                   for p in prepared.plans for op in p.ops)
        StreamExecutor(fused).run(prepared)

    for name in oracle.views:
        a, b = oracle.views[name], fused.views[name]
        da = a.to_dense() if isinstance(a, SparseRelation) else a
        db = b.to_dense() if isinstance(b, SparseRelation) else b
        for comp in da.payload:
            np.testing.assert_array_equal(
                np.asarray(da.payload[comp]), np.asarray(db.payload[comp]),
                err_msg=f"{name}/{comp} [{mode} {storage}]")


def test_fused_eager_interpreter_matches_unfused():
    """The eager per-update path replays FusedChain ops too."""
    with plan_mod.use_fusion("off"):
        oracle = _regression_engine()
        stream = _regression_stream(oracle.query, ["R", "S"] * 2, b=3)
        for r, u in stream:
            oracle.apply_update(r, u)
    with plan_mod.use_fusion("on"):
        fused = _regression_engine()
        for r, u in stream:
            fused.apply_update(r, u)
        assert any(isinstance(op, plan_mod.FusedChain)
                   for p in fused.plans.plans.values() for op in p.ops)
    for name in oracle.views:
        for comp in oracle.views[name].payload:
            np.testing.assert_array_equal(
                np.asarray(oracle.views[name].payload[comp]),
                np.asarray(fused.views[name].payload[comp]))


@pytest.mark.parametrize("pc,lowering", [(8192, "fused_compact"),
                                          (64, "fused_pallas")])
def test_fused_chain_lowering_follows_the_crossover(monkeypatch, plain_env,
                                                    pc, lowering):
    """On the chip a fused chain's ⊎ follows the hint its terminal
    ScatterAccum resolved: past the onehot/compact crossover
    (``max(4096, 8·B)``) the compact ⊎, at or below it the sweep.  The
    plan itself is the one every other ⊎ gets; only its lowering moves."""
    from repro.kernels import ring_fused

    ring = sum_ring()
    doms = dict(A=pc, B=4, C=4)
    rels = {"R": ("B", "A"), "S": ("C", "A")}
    q = Query(relations=rels, free_vars=(), ring=ring, domains=doms,
              lifts={"B": ("value",)})
    db = {n: DenseRelation(sch, ring, {"v": jnp.ones(
              tuple(doms[v] for v in sch), jnp.float32)})
          for n, sch in rels.items()}
    eng = IVMEngine.build(q, db, var_order=chain(["A"], {"A": [["B"], ["C"]]}),
                          storage="dense")
    upd = COOUpdate(("B", "A"), jnp.zeros((16, 2), jnp.int32),
                    {"v": jnp.ones((16,), jnp.float32)})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = eng.trigger_plan("R", upd)
    # the first chain lifts B into the view over A (at pc = 64 a second
    # one gathers the S side's view into the root)
    chains = [op for op in plan.ops if isinstance(op, plan_mod.FusedChain)]
    term = chains[0].ops[-1]
    assert term.backend == ("compact" if pc > 4096 else "onehot")
    assert ring_fused.resolve_backend(term.backend) == lowering


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------
def test_plan_cache_second_update_compiles_nothing():
    eng = _regression_engine()
    ring = eng.query.ring

    def upd(b):
        keys = np.stack([np.arange(b) % 3, np.arange(b) % 4], 1)
        payload = {**ring.zeros((b,)),
                   "c": jnp.asarray(np.ones(b, np.float32))}
        return COOUpdate(("A", "B"), jnp.asarray(keys.astype(np.int32)),
                         payload)

    eng.apply_update("R", upd(4))
    misses = eng.plans.misses
    assert misses >= 1 and eng.plans.plans
    eng.apply_update("R", upd(4))  # same signature: pure cache hit
    assert eng.plans.misses == misses
    assert eng.plans.hits >= 1
    eng.apply_update("R", upd(7))  # new batch size: one new plan
    assert eng.plans.misses == misses + 1
    stats = eng.plans.stats()
    assert stats["plans"] == len(eng.plans.plans)
    assert 0.0 <= stats["hit_rate"] <= 1.0
    assert stats["compile_ms_total"] >= stats["compile_ms_per_plan"] >= 0.0


def test_plan_cache_splits_new_vs_invalidated_misses():
    """A first-ever (rel, signature) is a ``miss_new``; recompiling the
    same trigger under a different plan environment (here: a fusion-mode
    flip, same as a storage rehash or backend override) is a
    ``miss_invalidated`` — the fusion on/off sweeps read these to tell
    fresh compiles from honest invalidations."""
    eng = _regression_engine()
    ring = eng.query.ring

    def upd(b):
        keys = np.stack([np.arange(b) % 3, np.arange(b) % 4], 1)
        payload = {**ring.zeros((b,)),
                   "c": jnp.asarray(np.ones(b, np.float32))}
        return COOUpdate(("A", "B"), jnp.asarray(keys.astype(np.int32)),
                         payload)

    with plan_mod.use_fusion("off"):
        eng.apply_update("R", upd(4))
    new0, inv0 = eng.plans.miss_new, eng.plans.miss_invalidated
    assert new0 >= 1 and inv0 == 0
    with plan_mod.use_fusion("off"):  # same key: pure hit
        eng.apply_update("R", upd(4))
    assert (eng.plans.miss_new, eng.plans.miss_invalidated) == (new0, 0)
    with plan_mod.use_fusion("on"):  # same triggers, new plan environment
        eng.apply_update("R", upd(4))
    assert eng.plans.miss_new == new0
    assert eng.plans.miss_invalidated >= 1
    assert eng.plans.misses == eng.plans.miss_new + eng.plans.miss_invalidated
    stats = eng.plans.stats()
    assert stats["miss_new"] == eng.plans.miss_new
    assert stats["miss_invalidated"] == eng.plans.miss_invalidated


def test_write_sets_track_fusion_mode_flip(plain_env):
    """Regression: ``PlanCache.write_sets`` used to memoize by rel alone,
    so a mid-session ``REPRO_PLAN_FUSION`` flip kept serving write sets
    derived from an invalidated plan.  The memo now shares the plan
    cache's environment key: the flip must force a fresh derivation
    (visible as a new plan-cache miss), and — since fusion preserves the
    op multiset — the re-derived sets must come out equal."""
    eng = _regression_engine()
    with plan_mod.use_fusion("off"):
        off_sets = eng.plans.write_sets(eng, "R")
        misses0 = eng.plans.misses
        # memoized: a repeat under the same environment is free
        assert eng.plans.write_sets(eng, "R") == off_sets
        assert eng.plans.misses == misses0
    with plan_mod.use_fusion("on"):
        on_sets = eng.plans.write_sets(eng, "R")
        assert eng.plans.misses == misses0 + 1  # fresh derivation
        assert eng.plans.write_sets(eng, "R") == on_sets
        assert eng.plans.misses == misses0 + 1
    assert on_sets == off_sets


def test_stream_prepare_embeds_cached_plans():
    rng = np.random.default_rng(3)
    q = Query(relations={"R": ("A", "B"), "S": ("A", "C")},
              free_vars=("A",), ring=sum_ring(),
              domains=dict(A=4, B=5, C=3),
              lifts={"B": ("value",), "C": ("value",)})
    vo = chain(["A"], {"A": [["B"], ["C"]]})

    def rel(schema):
        shape = tuple(dict(A=4, B=5, C=3)[v] for v in schema)
        return DenseRelation(tuple(schema), q.ring, {"v": jnp.asarray(
            rng.integers(0, 2, size=shape).astype(np.float32))})

    eng = IVMEngine.build(q, {"R": rel("AB"), "S": rel("AC")}, var_order=vo)

    def stream_of(schedule, b):
        out = []
        for r in schedule:
            sch = q.relations[r]
            keys = np.stack([rng.integers(0, eng.query.domains[v], size=b)
                             for v in sch], 1).astype(np.int32)
            out.append((r, COOUpdate(sch, jnp.asarray(keys),
                                     {"v": jnp.asarray(
                                         np.ones(b, np.float32))})))
        return out

    prepared = prepare_stream(eng, stream_of(["R", "S"] * 3, 4))
    assert prepared.mode == "rounds" and len(prepared.plans) == 2
    assert all(isinstance(p, plan_mod.TriggerPlan) for p in prepared.plans)
    misses = eng.plans.misses
    # a replayed same-shape stream fetches every plan from the cache
    prepare_stream(eng, stream_of(["R", "S"] * 3, 4))
    assert eng.plans.misses == misses
    # the eager path and the fused path share the same compiled plans
    rel_, upd = stream_of(["R"], 4)[0]
    assert eng.trigger_plan(rel_, upd) is prepared.plans[0]


def test_write_mask_matches_identity_diff():
    """The plan-derived switch partition must mark every leaf a trigger
    actually replaces (identity-diff of a representative application)."""
    eng = _regression_engine()
    ring = eng.query.ring
    state = eng.state
    in_leaves = jax.tree_util.tree_leaves(state)
    keys = jnp.zeros((1, 2), jnp.int32)
    payload = {**ring.zeros((1,)), "c": jnp.asarray(np.ones(1, np.float32))}
    out = eng.functional_update(*state, "R", COOUpdate(("A", "B"), keys,
                                                       payload))
    out_leaves = jax.tree_util.tree_leaves(out)
    wv, wb, wi = eng.plans.write_sets(eng, "R")
    mask = plan_mod.state_write_mask(state, wv, wb, wi)
    for i, (a, b) in enumerate(zip(in_leaves, out_leaves)):
        if a is not b:
            assert mask[i], f"leaf {i} replaced but not in the write mask"


# ---------------------------------------------------------------------------
# sparse factorized-update lowering (no densify)
# ---------------------------------------------------------------------------
def test_sparse_factorized_apply_bit_identical_and_sparse():
    rng = np.random.default_rng(1)
    ring = sum_ring()
    keys = np.stack([rng.integers(0, 6, 8), rng.integers(0, 5, 8)],
                    1).astype(np.int32)
    dense = DenseRelation.from_coo(
        ("X", "Y"), ring, (6, 5), jnp.asarray(keys),
        {"v": jnp.asarray(rng.integers(-2, 3, 8).astype(np.float32))})
    sparse = SparseRelation.from_dense(dense, capacity=64)
    u = np.zeros(6, np.float32)
    u[[1, 4]] = [2.0, -3.0]
    v = np.zeros(5, np.float32)
    v[[0, 2, 3]] = [1.0, 5.0, -1.0]
    factors = [DenseRelation(("X",), ring, {"v": jnp.asarray(u)}),
               DenseRelation((), ring, {"v": jnp.asarray(np.float32(2.5))}),
               DenseRelation(("Y",), ring, {"v": jnp.asarray(v)})]
    before = sparse.num_slots_used_sync()
    got = plan_mod.apply_factorized(sparse, factors, ring)
    ref = plan_mod.apply_factorized(dense, factors, ring)
    np.testing.assert_array_equal(np.asarray(got.to_dense().payload["v"]),
                                  np.asarray(ref.payload["v"]))
    # per-factor active-key enumeration: at most 2×3 fresh keys, never the
    # 30-key dense grid (the pre-refactor fallback enumerated the grid)
    assert got.num_slots_used_sync() <= before + 2 * 3


def test_sparse_chain_engine_rank1_updates_match_dense():
    rng = np.random.default_rng(7)
    mats = [jnp.asarray(rng.random((6, 5)).astype(np.float32)),
            jnp.asarray(rng.random((5, 4)).astype(np.float32))]
    eng_d = matrix_chain.build_chain_engine(mats, storage="dense")
    eng_s = matrix_chain.build_chain_engine(mats, storage="sparse")
    assert any(s.kind == "sparse" for s in eng_s.storage_plan.values())
    ring = eng_d.query.ring
    for k, p in ((1, 6), (2, 5)):
        u = np.zeros(p, np.float32)
        u[rng.integers(0, p)] = float(rng.integers(1, 4))
        w = np.zeros(mats[k - 1].shape[1], np.float32)
        w[rng.integers(0, w.size)] = float(rng.integers(1, 4))
        upd = matrix_chain.rank1_update(k, jnp.asarray(u), jnp.asarray(w),
                                        ring)
        eng_d.apply_update(f"A{k}", upd)
        eng_s.apply_update(f"A{k}", upd)
    np.testing.assert_array_equal(
        np.asarray(matrix_chain.result_matrix(eng_d)),
        np.asarray(matrix_chain.result_matrix(eng_s)))


def test_zero_factor_annihilates_without_inserts():
    ring = sum_ring()
    sparse = SparseRelation.zeros(("X", "Y"), ring, (8, 8), capacity=16)
    factors = [DenseRelation(("X",), ring,
                             {"v": jnp.zeros((8,), jnp.float32)}),
               DenseRelation(("Y",), ring,
                             {"v": jnp.ones((8,), jnp.float32)})]
    out = plan_mod.apply_factorized(sparse, factors, ring)
    assert out.num_slots_used_sync() == 0


# ---------------------------------------------------------------------------
# segment growth across a prepared stream
# ---------------------------------------------------------------------------
def test_stream_grows_sparse_tables_between_segments():
    """A stream whose inserts cross the 0.7 load factor mid-run must split,
    rehash between segments, recompile, and stay bit-identical to the
    dense oracle (regression for the old silent-drop behavior)."""
    rng = np.random.default_rng(5)
    doms = dict(A=16, B=4, C=12, D=4)
    q = Query(relations={"R": ("A", "B"), "S": ("A", "C"), "T": ("C", "D")},
              free_vars=("A", "C"), ring=sum_ring(), domains=doms,
              lifts={"B": ("value",), "D": ("value",)})
    vo = chain(["A", "C"], {"A": [["B"]], "C": [["D"]]})

    def rel(schema):
        shape = tuple(doms[v] for v in schema)
        mult = (rng.random(size=shape) < 0.03).astype(np.float32)
        return DenseRelation(tuple(schema), q.ring,
                             {"v": jnp.asarray(mult)})

    db = {"R": rel("AB"), "S": rel("AC"), "T": rel("CD")}
    stream = []
    for _ in range(6):
        b = 12
        keys = np.stack([rng.integers(0, doms[v], size=b)
                         for v in ("A", "C")], 1).astype(np.int32)
        vals = rng.integers(1, 3, size=b).astype(np.float32)
        stream.append(("S", COOUpdate(("A", "C"), jnp.asarray(keys),
                                      {"v": jnp.asarray(vals)})))

    opts = dict(storage="sparse", storage_opts=dict(headroom=1.0,
                                                    min_capacity=8))
    fused = IVMEngine.build(q, db, var_order=vo, **opts)
    caps0 = {n: v.capacity for n, v in fused.views.items()
             if isinstance(v, SparseRelation)}
    ex = StreamExecutor(fused)
    segments = ex._capacity_segments(stream)
    assert len(segments) >= 2, "stream must cross the load factor mid-run"
    ex.run(stream)
    caps1 = {n: v.capacity for n, v in fused.views.items()
             if isinstance(v, SparseRelation)}
    assert any(caps1[n] > caps0[n] for n in caps0), (caps0, caps1)

    oracle = IVMEngine.build(q, db, var_order=vo, storage="dense")
    for r, u in stream:
        oracle.apply_update(r, u)
    np.testing.assert_array_equal(
        np.asarray(fused.result().transpose(("A", "C")).payload["v"]),
        np.asarray(oracle.result().transpose(("A", "C")).payload["v"]))


# ---------------------------------------------------------------------------
# plan-level CSE inside a fused rounds step
# ---------------------------------------------------------------------------
def test_rounds_step_shares_stream_constant_sibling_planes():
    """R and S both gather the T-subtree view at the root join; T never
    updates in the stream, so the plan-level CSE computes that plane once
    per round instead of once per position — and results stay exact."""
    rng = np.random.default_rng(9)
    doms = dict(A=6, B=4, C=5, D=3)
    q = Query(relations={"R": ("A", "B"), "S": ("A", "C"), "T": ("A", "D")},
              free_vars=(), ring=sum_ring(), domains=doms,
              lifts={"B": ("value",), "C": ("value",), "D": ("value",)})
    vo = chain(["A"], {"A": [["B"], ["C"], ["D"]]})

    def rel(schema):
        shape = tuple(doms[v] for v in schema)
        return DenseRelation(tuple(schema), q.ring, {"v": jnp.asarray(
            rng.integers(0, 3, size=shape).astype(np.float32))})

    db = {"R": rel("AB"), "S": rel("AC"), "T": rel("AD")}
    stream = []
    for r in ["R", "S"] * 3:
        sch = q.relations[r]
        keys = np.stack([rng.integers(0, doms[v], size=4)
                         for v in sch], 1).astype(np.int32)
        vals = rng.integers(-2, 3, size=4).astype(np.float32)
        stream.append((r, COOUpdate(sch, jnp.asarray(keys),
                                    {"v": jnp.asarray(vals)})))

    fused = IVMEngine.build(q, db, var_order=vo)
    ex = StreamExecutor(fused)
    prepared = prepare_stream(fused, stream)
    assert prepared.mode == "rounds"
    ex.run(prepared)
    # the T-subtree view is read by both plans and written by neither
    assert ex.last_shared_ops, "expected a shared sibling prepare op"
    assert all(name not in {"R", "S"} for _, name in ex.last_shared_ops)

    seq = IVMEngine.build(q, db, var_order=vo)
    for r, u in stream:
        seq.apply_update(r, u)
    np.testing.assert_array_equal(np.asarray(fused.result().payload["v"]),
                                  np.asarray(seq.result().payload["v"]))
