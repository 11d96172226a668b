"""Fused stream executor ≡ per-call triggers ≡ host oracle.

The executor compiles a whole update stream into one XLA program (scan /
rounds / switch dispatch, see repro.core.stream).  These tests pin its
results to the sequential ``apply_update`` path (bit-identical: the fused
program traces the very same trigger bodies) and to the exact host oracle
``PyIVM`` — across all four maintenance strategies, heterogeneous batch
sizes (exercising bucket padding), aperiodic schedules (exercising the
switch fallback), and indicator-bearing cyclic queries.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import (COOUpdate, DenseRelation, IVMEngine, PyRelation,
                        Query, SparseRelation, StreamCapacityError,
                        StreamExecutor, build_view_tree, capacity_segments,
                        chain, prepare_stream, sum_ring)
from repro.core import storage as storage_mod
from repro.core.py_engine import PyEngineSpec, PyIVM
from repro.core.rings import PyNumberRing

DOMS = dict(A=4, B=5, C=3, D=6, E=4)


def example_query():
    return Query(
        relations={"R": ("A", "B"), "S": ("A", "C", "E"), "T": ("C", "D")},
        free_vars=("A", "C"),
        ring=sum_ring(),
        domains=DOMS,
        lifts={"B": ("value",), "D": ("value",), "E": ("value",)},
    )


def example_vo():
    return chain(["A", "C"], {"A": [["B"]], "C": [["D"], ["E"]]})


def random_db(rng, ring):
    def rel(schema):
        shape = tuple(DOMS[v] for v in schema)
        mult = rng.integers(0, 3, size=shape).astype(np.float32)
        return DenseRelation(tuple(schema), ring, {"v": jnp.asarray(mult)})

    return {"R": rel("AB"), "S": rel("ACE"), "T": rel("CD")}


def random_stream(rng, q, schedule, batches):
    out = []
    for rel, B in zip(schedule, batches):
        sch = q.relations[rel]
        keys = np.stack([rng.integers(0, DOMS[v], size=B) for v in sch],
                        axis=1).astype(np.int32)
        vals = rng.integers(-2, 3, size=B).astype(np.float32)
        out.append((rel, COOUpdate(sch, jnp.asarray(keys),
                                   {"v": jnp.asarray(vals)})))
    return out


def py_oracle_result(q, db, stream):
    """Exact host-side F-IVM over the same tree and stream."""
    ring = PyNumberRing()
    lifts = {v: (lambda x, s=spec: float(x)) for v, spec in q.lifts.items()}
    spec = PyEngineSpec(ring=ring, lifts=lifts)
    tree = build_view_tree(q, example_vo())
    py_db = {}
    for name, rel in db.items():
        pr = PyRelation(rel.schema, ring)
        arr = np.asarray(rel.payload["v"])
        for key in np.argwhere(arr != 0):
            pr.data[tuple(int(k) for k in key)] = float(arr[tuple(key)])
        py_db[name] = pr
    eng = PyIVM(tree, py_db, spec)
    for rel, upd in stream:
        d = PyRelation(upd.schema, ring)
        keys = np.asarray(upd.keys)
        vals = np.asarray(upd.payload["v"])
        for i in range(keys.shape[0]):
            d.insert(tuple(int(k) for k in keys[i]), float(vals[i]))
        eng.apply_update(rel, d)
    res = eng.result()
    out = np.zeros((DOMS["A"], DOMS["C"]), np.float64)
    perm = [res.schema.index(v) for v in ("A", "C")]
    for k, p in res.data.items():
        out[k[perm[0]], k[perm[1]]] = p
    return out


@pytest.mark.parametrize("strategy", ["fivm", "dbt", "fivm_1", "reeval"])
@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=4, deadline=None)
def test_fused_stream_matches_sequential_and_oracle(strategy, seed):
    rng = np.random.default_rng(seed)
    q = example_query()
    db = random_db(rng, q.ring)
    # heterogeneous batches: exercises bucket padding inside the executor
    schedule = ["R", "S", "T"] * 3
    batches = [int(rng.integers(1, 8)) for _ in schedule]
    stream = random_stream(rng, q, schedule, batches)

    fused = IVMEngine.build(q, db, var_order=example_vo(), strategy=strategy)
    StreamExecutor(fused).run(stream)

    seq = IVMEngine.build(q, db, var_order=example_vo(), strategy=strategy)
    for rel, upd in stream:
        seq.apply_update(rel, upd)

    got = np.asarray(fused.result().transpose(("A", "C")).payload["v"])
    ref = np.asarray(seq.result().transpose(("A", "C")).payload["v"])
    np.testing.assert_array_equal(got, ref)  # same trigger traces: exact
    np.testing.assert_allclose(got, py_oracle_result(q, db, stream),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("strategy", ["fivm", "dbt", "fivm_1", "reeval"])
def test_fused_aperiodic_switch_matches_sequential(strategy):
    """Aperiodic schedule: prepare_stream must pick switch dispatch."""
    rng = np.random.default_rng(3)
    q = example_query()
    db = random_db(rng, q.ring)
    schedule = ["R", "S", "T", "S", "R", "R", "T"]  # no period
    stream = random_stream(rng, q, schedule, [4] * len(schedule))

    fused = IVMEngine.build(q, db, var_order=example_vo(), strategy=strategy)
    prepared = prepare_stream(fused, stream)
    assert prepared.mode == "switch"
    StreamExecutor(fused).run(prepared)

    seq = IVMEngine.build(q, db, var_order=example_vo(), strategy=strategy)
    for rel, upd in stream:
        seq.apply_update(rel, upd)

    got = np.asarray(fused.result().transpose(("A", "C")).payload["v"])
    ref = np.asarray(seq.result().transpose(("A", "C")).payload["v"])
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_allclose(got, py_oracle_result(q, db, stream),
                               rtol=1e-4, atol=1e-4)


def test_prepare_stream_modes_and_bucketing():
    rng = np.random.default_rng(0)
    q = example_query()
    eng = IVMEngine.build(q, random_db(rng, q.ring), var_order=example_vo())
    single = random_stream(rng, q, ["S"] * 4, [3, 7, 2, 7])
    p = prepare_stream(eng, single)
    assert p.mode == "scan" and p.buckets == (7,)
    assert p.n_tuples == 3 + 7 + 2 + 7

    rounds = random_stream(rng, q, ["R", "S"] * 3, [2, 5] * 3)
    p = prepare_stream(eng, rounds)
    assert p.mode == "rounds" and p.pattern == ("R", "S")
    assert p.buckets == (2, 5)  # per-position buckets
    assert p.tail_len == 0

    aper = random_stream(rng, q, ["R", "S", "R", "R"], [2, 2, 2, 2])
    p = prepare_stream(eng, aper)
    assert p.mode == "switch"

    # near-periodic: trailing partial round canonicalizes to rounds + tail
    near = random_stream(rng, q, ["R", "S", "T"] * 2 + ["R"], [3] * 7)
    p = prepare_stream(eng, near)
    assert p.mode == "rounds" and p.pattern == ("R", "S", "T")
    assert p.n_steps == 2 and p.tail_len == 1

    # a rotated round-robin stream is periodic under shift-matching
    rot = random_stream(rng, q, ["S", "R"] * 3 + ["S"], [2] * 7)
    p = prepare_stream(eng, rot)
    assert p.mode == "rounds" and p.pattern == ("S", "R") and p.tail_len == 1


@pytest.mark.parametrize("strategy", ["fivm", "dbt", "fivm_1", "reeval"])
def test_fused_near_periodic_rounds_matches_sequential(strategy):
    """Near-periodic schedule (trailing partial round): the canonicalized
    rounds program — scan + tail — must match per-call triggers exactly."""
    rng = np.random.default_rng(17)
    q = example_query()
    db = random_db(rng, q.ring)
    schedule = ["R", "S", "T"] * 3 + ["R", "S"]
    batches = [int(rng.integers(1, 8)) for _ in schedule]
    stream = random_stream(rng, q, schedule, batches)

    fused = IVMEngine.build(q, db, var_order=example_vo(), strategy=strategy)
    prepared = prepare_stream(fused, stream)
    assert prepared.mode == "rounds" and prepared.tail_len == 2
    StreamExecutor(fused).run(prepared)

    seq = IVMEngine.build(q, db, var_order=example_vo(), strategy=strategy)
    for rel, upd in stream:
        seq.apply_update(rel, upd)

    got = np.asarray(fused.result().transpose(("A", "C")).payload["v"])
    ref = np.asarray(seq.result().transpose(("A", "C")).payload["v"])
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_allclose(got, py_oracle_result(q, db, stream),
                               rtol=1e-4, atol=1e-4)


def test_fused_stream_with_kernel_scatter_backend():
    """The fused executor with a kernel scatter backend (compact/XLA inner)
    stays bit-identical to the kernel-off per-call path — integer-valued
    payloads make every accumulation order exact."""
    from repro.kernels import scatter_ops

    rng = np.random.default_rng(23)
    q = example_query()
    db = random_db(rng, q.ring)
    stream = random_stream(rng, q, ["R", "S", "T"] * 3,
                           [int(rng.integers(1, 8)) for _ in range(9)])

    seq = IVMEngine.build(q, db, var_order=example_vo(), strategy="fivm")
    for rel, upd in stream:
        seq.apply_update(rel, upd)

    with scatter_ops.use_backend("compact_xla"):
        fused = IVMEngine.build(q, db, var_order=example_vo(), strategy="fivm")
        StreamExecutor(fused).run(stream)

    got = np.asarray(fused.result().transpose(("A", "C")).payload["v"])
    ref = np.asarray(seq.result().transpose(("A", "C")).payload["v"])
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("strategy", ["fivm", "dbt"])
def test_fused_stream_with_indicators(strategy):
    """Cyclic triangle query with maintained ∃-projections through the
    fused executor; padding rows must not perturb indicator counts."""
    rng = np.random.default_rng(11)
    n = 6
    ring = sum_ring()
    doms = dict(A=n, B=n, C=n)
    q = Query(relations={"R": ("A", "B"), "S": ("B", "C"), "T": ("C", "A")},
              free_vars=(), ring=ring, domains=doms, lifts={})

    def mk(schema):
        shape = tuple(doms[v] for v in schema)
        return DenseRelation(tuple(schema), ring, {"v": jnp.asarray(
            rng.integers(0, 2, size=shape).astype(np.float32))})

    db = {"R": mk("AB"), "S": mk("BC"), "T": mk("CA")}
    state = {k: np.asarray(v.payload["v"]).copy() for k, v in db.items()}
    stream = []
    for step in range(9):
        rel = ["R", "S", "T"][step % 3]
        sch = q.relations[rel]
        b = 3 + step % 2  # heterogeneous: forces padded indicator updates
        flat = rng.choice(n * n, size=b, replace=False)
        keys = np.stack([flat // n, flat % n], axis=1).astype(np.int32)
        vals = rng.integers(-1, 2, size=b).astype(np.float32)
        stream.append((rel, COOUpdate(sch, jnp.asarray(keys),
                                      {"v": jnp.asarray(vals)})))
        np.add.at(state[rel], (keys[:, 0], keys[:, 1]), vals)

    kwargs = dict(var_order=chain(["A", "B", "C"]), strategy=strategy,
                  use_indicators=True, fuse_chains=False)
    fused = IVMEngine.build(q, db, **kwargs)
    StreamExecutor(fused).run(stream)
    seq = IVMEngine.build(q, db, **kwargs)
    for rel, upd in stream:
        seq.apply_update(rel, upd)

    got = float(np.asarray(fused.result().payload["v"]))
    ref = float(np.asarray(seq.result().payload["v"]))
    exp = float(np.einsum("ab,bc,ca->", state["R"], state["S"], state["T"]))
    assert got == ref
    assert np.allclose(got, exp)


# ---------------------------------------------------------------------------
# capacity segmentation: restore, prepare-time audit, zombie budgeting,
# and the sync-free replay path (ISSUE 5 satellites)
# ---------------------------------------------------------------------------
SEG_DOMS = dict(A=64, B=64, C=3)


def _seg_query():
    return Query(relations={"R": ("A", "B"), "T": ("B", "C")},
                 free_vars=("A",), ring=sum_ring(), domains=SEG_DOMS,
                 lifts={"C": ("value",)})


def _seg_db(rng):
    ring = sum_ring()

    def rel(schema):
        shape = tuple(SEG_DOMS[v] for v in schema)
        mult = np.zeros(shape, np.float32)
        idx = tuple(rng.integers(0, d, size=8) for d in shape)
        np.add.at(mult, idx, 1.0)
        return DenseRelation(tuple(schema), ring, {"v": jnp.asarray(mult)})

    return {"R": rel("AB"), "T": rel("BC")}


def _seg_engine(rng):
    return IVMEngine.build(_seg_query(), _seg_db(rng),
                           var_order=chain(["A", "B"], {"B": [["C"]]}),
                           storage="sparse")


def _seg_upd(q, rel, B, seed, vals=None):
    rng = np.random.default_rng(seed)
    sch = q.relations[rel]
    keys = np.stack([rng.integers(0, SEG_DOMS[v], size=B) for v in sch],
                    axis=1).astype(np.int32)
    if vals is None:
        vals = np.ones(B, np.float32)
    return (rel, COOUpdate(sch, jnp.asarray(keys),
                           {"v": jnp.asarray(np.asarray(vals, np.float32))}))


def _sparse_caps(engine):
    return {n: v.capacity for n, v in engine.views.items()
            if isinstance(v, SparseRelation)}


def test_segmented_run_restores_engine_views_with_update_engine_false():
    """Regression (ISSUE 5): a segmented raw run with update_engine=False
    must leave the engine's views dict — capacities included — exactly as
    it found them; only the returned state carries the rehash-grown
    tables.  The restore snapshots the container dicts, so it holds even
    against in-place mutation of engine.views between segments."""
    q = _seg_query()
    eng = _seg_engine(np.random.default_rng(0))
    stream = [_seg_upd(q, "R", 32, 100 + i) for i in range(12)]
    ex = StreamExecutor(eng)
    segments = capacity_segments(eng, stream)
    assert len(segments) > 1 or segments[0][1], "stream must segment"
    caps_before = _sparse_caps(eng)
    views_before = dict(eng.views)
    result_before = np.asarray(eng.result().payload["v"]).copy()

    state = ex.run(stream, update_engine=False)

    assert _sparse_caps(eng) == caps_before
    assert eng.views == views_before  # the very same storage objects
    np.testing.assert_array_equal(np.asarray(eng.result().payload["v"]),
                                  result_before)
    grown = {n: v.capacity for n, v in state[0].items()
             if isinstance(v, SparseRelation)}
    assert any(grown[n] > caps_before[n] for n in grown)
    assert ex.last_segment_stats and all(
        s["dispatch_s"] >= 0 and s["admit_s"] >= 0
        for s in ex.last_segment_stats)


def test_segmented_run_restores_engine_when_a_segment_raises(monkeypatch):
    """The restore must also run when a mid-segment admit blows up —
    the engine cannot be left holding half the segments' growth."""
    q = _seg_query()
    eng = _seg_engine(np.random.default_rng(1))
    stream = [_seg_upd(q, "R", 32, 200 + i) for i in range(12)]
    ex = StreamExecutor(eng)
    caps_before = _sparse_caps(eng)
    calls = dict(n=0)
    orig = StreamExecutor._admit_segment

    def failing_admit(self, sub_stream, grow_caps, offset=0):
        calls["n"] += 1
        if calls["n"] >= 2:
            raise RuntimeError("boom mid-segment")
        return orig(self, sub_stream, grow_caps, offset)

    monkeypatch.setattr(StreamExecutor, "_admit_segment", failing_admit)
    with pytest.raises(RuntimeError, match="boom"):
        ex.run(stream, update_engine=False)
    assert _sparse_caps(eng) == caps_before


def test_prepare_stream_refuses_overflowing_stream():
    """Regression (ISSUE 5): a directly-prepared stream bypasses
    segmentation, so prepare_stream must run the worst-case budget audit
    and raise — silently overflow-dropping rows is the failure the
    segmentation machinery exists to prevent."""
    q = _seg_query()
    eng = _seg_engine(np.random.default_rng(2))
    flood = [_seg_upd(q, "R", 32, 300 + i) for i in range(12)]
    with pytest.raises(StreamCapacityError, match="raw stream"):
        prepare_stream(eng, flood)
    # the audit is skippable for budgeted callers (the segmented runner)
    prepared = prepare_stream(eng, flood, check_capacity=False)
    assert prepared.n_steps > 0
    # and the raw-stream run path the error points to handles the flood
    ex = StreamExecutor(eng)
    ex.run(flood)
    seq = _seg_engine(np.random.default_rng(2))
    for rel, upd in flood:
        seq.apply_update(rel, upd)
    np.testing.assert_array_equal(np.asarray(eng.result().payload["v"]),
                                  np.asarray(seq.result().payload["v"]))


def test_explicit_state_run_audits_the_caller_state():
    """An explicit-state raw run must audit the state it will actually
    mutate: the engine's own occupancy says nothing about the caller's
    tables (they may be much fuller, and a compiled stream silently
    drops overflowing inserts)."""
    q = _seg_query()
    eng = _seg_engine(np.random.default_rng(6))
    ex = StreamExecutor(eng)
    # advance a state without touching the engine: its R table fills
    # while the engine stays near-empty (and nothing segments)
    fill = [_seg_upd(q, "R", 24, 600)]
    assert len(capacity_segments(eng, fill)) == 1
    state = ex.run(fill, update_engine=False)
    occ_state = state[0]["R"].num_slots_used_sync()
    occ_engine = eng.views["R"].num_slots_used_sync()
    assert occ_state > occ_engine
    # a top-up that fits next to the engine's occupancy but not the
    # caller state's must be refused, not silently overflow-dropped
    top_up = [_seg_upd(q, "R", 16, 601)]
    assert len(capacity_segments(eng, top_up)) == 1  # engine would pass
    with pytest.raises(StreamCapacityError):
        ex.run(top_up, state=state)
    # ... while the same stream against the engine's own state runs fine
    ex.run(top_up)


def test_prepare_stream_audit_counts_distinct_keys_not_rows():
    """The audit's budget is distinct projected keys × unbound extent —
    a stream hammering one key must prepare fine however long it is."""
    q = _seg_query()
    eng = _seg_engine(np.random.default_rng(3))
    sch = q.relations["R"]
    one_key = np.zeros((32, len(sch)), np.int32)
    stream = [("R", COOUpdate(sch, jnp.asarray(one_key),
                              {"v": jnp.ones((32,), jnp.float32)}))
              for _ in range(20)]
    prepared = prepare_stream(eng, stream)  # must not raise
    assert prepared.n_steps == 20


def test_capacity_segments_count_zombie_slots():
    """Occupancy is num_slots_used (zombies included): ring-zero keys
    keep their slot until a rehash compacts them, and a compiled segment
    never rehashes — so a zombie-heavy table must trigger growth earlier
    than its live-key count alone would."""
    ring = sum_ring()
    q = _seg_query()
    eng = _seg_engine(np.random.default_rng(4))
    # grow zombies in the leaf view R: insert a batch, then delete it
    ins = _seg_upd(q, "R", 24, 400)
    dele = ("R", COOUpdate(ins[1].schema, ins[1].keys,
                           ring.neg(ins[1].payload)))
    eng.apply_update(*ins)
    eng.apply_update(*dele)
    view = eng.views["R"]
    assert isinstance(view, SparseRelation)
    zombies = view.num_slots_used_sync() - view.num_keys_sync()
    assert zombies > 0
    # a stream whose budget fits next to the LIVE keys but not next to
    # the zombie-inflated occupancy must still be segmented for growth
    cap = view.capacity
    headroom = int(storage_mod.LOAD_FACTOR * cap) - view.num_keys_sync()
    budget = headroom - zombies // 2
    assert 0 < budget <= headroom
    stream = [_seg_upd(q, "R", budget, 401)]
    segments = capacity_segments(eng, stream)
    assert segments[0][1].get("R", cap) > cap  # growth decision fired
    # ... and the pre-segment rehash compacts the zombies away
    ex = StreamExecutor(eng)
    ex.run(stream, pipeline=False)  # exercise the blocking baseline too
    grown = eng.views["R"]
    assert grown.capacity > cap
    seq = _seg_engine(np.random.default_rng(4))
    for u in (ins, dele, stream[0]):
        seq.apply_update(*u)
    np.testing.assert_array_equal(np.asarray(eng.result().payload["v"]),
                                  np.asarray(seq.result().payload["v"]))


def test_stream_replay_path_is_sync_free(monkeypatch):
    """Regression (ISSUE 5): the replay hot path — running an
    already-prepared stream against an explicit state — must never block
    on a device→host payload read.  All sanctioned host syncs route
    through the explicit helpers (relations.host_payload / payload_sync,
    num_keys_sync, num_slots_used_sync — admission and reporting paths
    only); the test arms every one of them to raise during the replay,
    under a device→host transfer guard for good measure (the guard is
    inert on the CPU backend, where device buffers are host memory, but
    bites on accelerators)."""
    rng = np.random.default_rng(5)
    q = example_query()
    db = random_db(rng, q.ring)
    eng = IVMEngine.build(q, db, var_order=example_vo(), strategy="fivm")
    stream = random_stream(rng, q, ["R", "S", "T"] * 2, [4] * 6)
    ex = StreamExecutor(eng)
    prepared = prepare_stream(eng, stream)
    state = ex.run(prepared, update_engine=False)  # warm + compile
    jax.block_until_ready(state)

    from repro.core import relations as relations_mod

    def boom(*a, **k):
        raise AssertionError("host sync on the stream replay path")

    monkeypatch.setattr(relations_mod, "host_payload", boom)
    monkeypatch.setattr(DenseRelation, "payload_sync", boom)
    monkeypatch.setattr(DenseRelation, "num_keys_sync", boom)
    monkeypatch.setattr(SparseRelation, "num_keys_sync", boom)
    monkeypatch.setattr(SparseRelation, "num_slots_used_sync", boom)
    with jax.transfer_guard_device_to_host("disallow"):
        state = ex.run(prepared, state=state, update_engine=False,
                       donate_input=True)
        state = ex.run(prepared, state=state, update_engine=False,
                       donate_input=True)
    jax.block_until_ready(state)
    # ... while stream admission legitimately uses the sync helpers
    with pytest.raises(AssertionError, match="host sync"):
        eng.views[eng.tree.name].num_keys_sync()


def test_executor_does_not_clobber_engine_or_db():
    """Donation safety: run() must copy before donating — the engine's leaf
    views alias the caller's database arrays."""
    rng = np.random.default_rng(1)
    q = example_query()
    db = random_db(rng, q.ring)
    eng = IVMEngine.build(q, db, var_order=example_vo(), strategy="fivm")
    before = np.asarray(db["S"].payload["v"]).copy()
    stream = random_stream(rng, q, ["S", "R", "T"] * 2, [4] * 6)
    StreamExecutor(eng).run(stream)
    # the caller's database buffers are untouched and still readable
    np.testing.assert_array_equal(np.asarray(db["S"].payload["v"]), before)
    # and the engine state advanced (result differs from a fresh build)
    fresh = IVMEngine.build(q, db, var_order=example_vo(), strategy="fivm")
    assert not np.array_equal(
        np.asarray(eng.result().payload["v"]),
        np.asarray(fresh.result().payload["v"]))


# ---------------------------------------------------------------------------
# fused chains into a view past the onehot/compact crossover
# ---------------------------------------------------------------------------
STAR_DOMS = dict(A=8192, B=4, C=4)


def _star_engine(storage):
    """A two-relation star on ``A`` whose R trigger fuses Lift(B) →
    Marginalize(B) → ⊎ into an 8,192-row view: past the crossover
    ``max(4096, 8·B)`` for B = 16, so the plan's hint there is compact."""
    rng = np.random.default_rng(5)
    ring = sum_ring()
    rels = {"R": ("B", "A"), "S": ("C", "A")}
    q = Query(relations=rels, free_vars=(), ring=ring, domains=STAR_DOMS,
              lifts={"B": ("value",)})
    db = {n: DenseRelation(sch, ring, {"v": jnp.asarray(
              rng.integers(0, 2, size=tuple(STAR_DOMS[v] for v in sch))
              .astype(np.float32))}) for n, sch in rels.items()}
    return IVMEngine.build(q, db, var_order=chain(["A"], {"A": [["B"], ["C"]]}),
                           storage=storage)


def _star_stream(q, n, B=16, seed=8):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        rel = "RS"[i % 2]
        sch = q.relations[rel]
        # a few hot keys so the batch carries duplicate out ids
        keys = np.stack([rng.integers(0, 4 if v == "A" and i % 3 == 0
                                      else STAR_DOMS[v], size=B)
                         for v in sch], 1).astype(np.int32)
        out.append((rel, COOUpdate(sch, jnp.asarray(keys), {"v": jnp.asarray(
            rng.integers(-2, 3, B).astype(np.float32))})))
    return out


@pytest.mark.parametrize("backend,share", [("compact_interpret", 1.0),
                                           ("onehot_interpret", 0.0)])
@pytest.mark.parametrize("storage", ["dense", "sparse"])
def test_compact_fused_chain_replays_unfused(storage, backend, share):
    """Fusion on, terminal ⊎ through the compact lowering
    (``compact_interpret``) or the sweep (``onehot_interpret``): the
    segmented stream replays bit-identically to fusion off, and the
    segments count their fused chains and the compact ones among them
    (what ``fused_compact_share`` reads)."""
    from benchmarks.chip.metrics import fused_compact_share
    from repro.core import plan as plan_mod
    from repro.kernels import scatter_ops
    from repro.serve import SnapshotRegistry

    with plan_mod.use_fusion("off"):
        oracle = _star_engine(storage)
        stream = _star_stream(oracle.query, 8)
        ex_off = StreamExecutor(
            oracle, registry=SnapshotRegistry(segment_updates=4))
        ex_off.run(stream)
    with plan_mod.use_fusion("on"), \
            scatter_ops.use_backend(backend):
        fused = _star_engine(storage)
        ex = StreamExecutor(fused,
                            registry=SnapshotRegistry(segment_updates=4))
        ex.run(stream)
    segs = ex.last_segment_stats
    assert len(segs) == 2
    for s in segs:
        # four batches a segment, two of them R's one fused chain each
        assert s["counts"]["fused_chains"] == 2
        assert s["counts"]["fused_chains_compact"] == 2 * share
    assert fused_compact_share.read(_WindowRun(segs)) == share
    assert fused_compact_share.read(
        _WindowRun(ex_off.last_segment_stats)) is None
    for name in oracle.views:
        a, b = oracle.views[name], fused.views[name]
        da = a.to_dense() if isinstance(a, SparseRelation) else a
        db = b.to_dense() if isinstance(b, SparseRelation) else b
        for comp in da.payload:
            np.testing.assert_array_equal(
                np.asarray(da.payload[comp]), np.asarray(db.payload[comp]),
                err_msg=f"{name}/{comp} [{storage}]")


class _WindowRun:
    """The slice of the chip benchmark's ``Run`` a segment metric reads."""

    def __init__(self, segments):
        self._segments = list(segments)

    def window_segments(self):
        return self._segments
