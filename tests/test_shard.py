"""Multi-device sharding of the scan carry (DESIGN.md §9).

The plan-driven placement layer (``repro.core.shard``) must (a) classify
every state entry from the trigger plans alone — scatter-written views
shard, sibling-gathered views shard with an all-gather read lowering,
everything else replicates — and (b) produce results equivalent to the
single-device executor: exact for integer-valued payloads (every
accumulation order is exact), ≤1e-6 relative for general floats
(reduction order may differ across shards).

The placement/classification tests run on any device count (a 1-device
mesh is a degenerate but valid partition).  The equivalence tests need a
real multi-device mesh: they run under the CI ``multi-device`` leg
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``) and skip on
single-device hosts — except one subprocess-backed smoke test that forces
a 4-device host platform regardless of the parent's device count, so the
tier-1 suite always exercises a genuinely sharded run.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (COOUpdate, DenseRelation, IVMEngine, Query,
                        SparseRelation, StreamExecutor, chain, make_mesh,
                        plan_shards, prepare_stream, shard_executor,
                        sum_ring)
from repro.core import plan as plan_mod

DOMS = dict(A=4, B=8, C=4, D=8, E=4)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_device_count)")


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


def random_db(rng, ring, float_vals=False):
    def rel(schema):
        shape = tuple(DOMS[v] for v in schema)
        if float_vals:
            mult = (rng.random(size=shape) *
                    (rng.random(size=shape) < 0.4)).astype(np.float32)
        else:
            mult = rng.integers(0, 3, size=shape).astype(np.float32)
        return DenseRelation(tuple(schema), ring, {"v": jnp.asarray(mult)})

    return {"R": rel("AB"), "S": rel("ACE"), "T": rel("CD")}


def random_stream(rng, q, schedule, batches, float_vals=False):
    out = []
    for rel, B in zip(schedule, batches):
        sch = q.relations[rel]
        keys = np.stack([rng.integers(0, DOMS[v], size=B) for v in sch],
                        axis=1).astype(np.int32)
        if float_vals:
            vals = (rng.random(size=B) * 4 - 2).astype(np.float32)
        else:
            vals = rng.integers(-2, 3, size=B).astype(np.float32)
        out.append((rel, COOUpdate(sch, jnp.asarray(keys),
                                   {"v": jnp.asarray(vals)})))
    return out


def mixed_engine(q, db, **kwargs):
    """Sparse storage with one view forced dense: the sharded carry must
    mix slot-axis and lead-axis partitions in one state pytree."""
    probe = IVMEngine.build(q, db, var_order=example_vo(), storage="sparse",
                            **kwargs)
    sparse = [n for n, s in probe.storage_plan.items() if s.kind == "sparse"]
    assert sparse, "expected at least one sparse-eligible view"
    return IVMEngine.build(q, db, var_order=example_vo(), storage="sparse",
                           storage_overrides={sparse[0]: "dense"}, **kwargs)


# ---------------------------------------------------------------------------
# placement pass (device-count independent: a 1-device mesh is valid)
# ---------------------------------------------------------------------------
def test_collective_placement_classification():
    """The plan-time pass: written+gathered → all_gather, written-only →
    scatter, unshardable/read-only → replicate."""
    rng = np.random.default_rng(0)
    q = example_query()
    eng = IVMEngine.build(q, random_db(rng, q.ring), var_order=example_vo(),
                          storage="sparse")
    plans = [eng.plans.lookup_sig(
        eng, rel, ("coo", tuple(q.relations[rel]), 1))
        for rel in eng.updatable]
    write_union = set()
    for p in plans:
        write_union |= set(p.write_views)
    read_union = set(plan_mod.read_sets(plans))
    placement = plan_mod.collective_placement(
        plans, {n: True for n in eng.views})
    for name, place in placement.items():
        if name.startswith(plan_mod.IND_PREFIX):
            continue
        if name not in write_union:
            assert place == "replicate", (name, place)
        elif name in read_union:
            assert place == "all_gather", (name, place)
        else:
            assert place == "scatter", (name, place)
    # sibling views of some delta path are genuinely gathered: the pass
    # must place at least one all_gather and route the root's scatter
    assert "all_gather" in placement.values()
    # an unshardable layout always replicates, even when scatter-written
    forced = plan_mod.collective_placement(plans, {n: False
                                                   for n in eng.views})
    assert set(forced.values()) == {"replicate"}


def test_plan_shards_specs_and_reasons():
    rng = np.random.default_rng(1)
    q = example_query()
    eng = mixed_engine(q, random_db(rng, q.ring))
    sp = plan_shards(eng, devices=jax.devices())
    n = sp.n_devices
    for name, v in eng.views.items():
        spec = sp.specs[name]
        if spec.kind == "shard":
            assert spec.extent % n == 0
            if isinstance(v, SparseRelation):
                assert spec.axis == "slot" and spec.extent == v.capacity
            else:
                assert spec.axis == "lead" and spec.extent == v.domains[0]
            assert spec.collective in ("scatter", "all_gather")
        else:
            assert spec.collective is None and spec.extent == 0
    assert sp.pretty().startswith(f"mesh[view={n}]")
    # every sharded view's leaves carry the mesh axis on dim 0, the rest
    # replicate — and the sharding tree matches the state's structure
    shardings = sp.state_shardings(eng.state)
    jax.tree.map(lambda leaf, s: None, eng.state, shardings)


def test_storage_shard_surface():
    ring = sum_ring()
    mesh = make_mesh(jax.devices())
    dense = DenseRelation.zeros(("A", "B"), ring, (8, 4))
    sparse = SparseRelation.zeros(("A",), ring, (64,), capacity=16)
    scalar = DenseRelation.zeros((), ring, ())
    assert dense.shard_axis() == 0 and dense.shard_extent() == 8
    assert sparse.shard_axis() == 0 and sparse.shard_extent() == 16
    assert scalar.shard_axis() is None and scalar.shard_extent() == 0
    for rel, shard in ((dense, True), (sparse, True), (dense, False)):
        tree = rel.leaf_shardings(mesh, "view", shard)
        specs = jax.tree.leaves(tree)
        assert len(specs) == len(jax.tree.leaves(rel))
        for s in specs:
            parts = tuple(s.spec)
            assert (("view" in parts) == shard) or not rel.schema


# ---------------------------------------------------------------------------
# multi-device equivalence (CI multi-device leg; skips on 1 device)
# ---------------------------------------------------------------------------
@multi_device
@pytest.mark.parametrize("schedule,mode", [
    (["S"] * 5, "scan"),
    (["R", "S", "T"] * 3, "rounds"),
    (["R", "S", "T", "S", "R", "R", "T"], "switch"),
])
def test_sharded_matches_single_device(schedule, mode):
    rng = np.random.default_rng(7)
    q = example_query()
    db = random_db(rng, q.ring)
    stream = random_stream(rng, q, schedule,
                           [int(rng.integers(1, 8)) for _ in schedule])

    single = mixed_engine(q, db)
    ex_s = StreamExecutor(single)
    prepared = prepare_stream(single, stream)
    assert prepared.mode == mode
    ex_s.run(prepared)

    sharded = mixed_engine(q, db)
    ex = shard_executor(sharded)
    assert len(ex.shard.sharded_views()) >= 1
    ex.run(stream)

    got = np.asarray(sharded.result().transpose(("A", "C")).payload["v"])
    ref = np.asarray(single.result().transpose(("A", "C")).payload["v"])
    # integer-valued payloads: every accumulation order is exact
    np.testing.assert_array_equal(got, ref)


@multi_device
def test_sharded_float_payloads_within_tolerance():
    """Non-integer float payloads: cross-shard reduction order may differ
    from the single-device program — ≤1e-6 relative, per the acceptance
    bound."""
    rng = np.random.default_rng(23)
    q = example_query()
    db = random_db(rng, q.ring, float_vals=True)
    stream = random_stream(rng, q, ["R", "S", "T"] * 3, [6] * 9,
                           float_vals=True)

    single = mixed_engine(q, db)
    StreamExecutor(single).run(stream)
    sharded = mixed_engine(q, db)
    shard_executor(sharded).run(stream)

    got = np.asarray(sharded.result().transpose(("A", "C")).payload["v"])
    ref = np.asarray(single.result().transpose(("A", "C")).payload["v"])
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)


@multi_device
def test_sharded_segmented_stream_grows_and_matches():
    """Capacity segmentation under a shard plan: rehash keeps power-of-two
    capacities divisible by the mesh, so placements survive growth."""
    rng = np.random.default_rng(3)
    q = example_query()
    db = random_db(rng, q.ring)

    def fresh():
        return IVMEngine.build(
            q, db, var_order=example_vo(), storage="sparse",
            storage_opts=dict(min_capacity=16))

    stream = random_stream(rng, q, ["S"] * 12, [16] * 12)
    single = fresh()
    StreamExecutor(single).run(stream)
    sharded = fresh()
    ex = shard_executor(sharded)
    ex.run(stream)
    got = np.asarray(sharded.result().transpose(("A", "C")).payload["v"])
    ref = np.asarray(single.result().transpose(("A", "C")).payload["v"])
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# always-on multi-device smoke: forces a 4-device host platform in a
# subprocess so the tier-1 run exercises a real sharded program
# ---------------------------------------------------------------------------
_CHILD = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from repro.core import (COOUpdate, DenseRelation, IVMEngine, Query,
                        StreamExecutor, chain, shard_executor, sum_ring)

assert len(jax.devices()) == 4, jax.devices()
DOMS = dict(A=4, B=8, C=4, D=8, E=4)
q = Query(relations={"R": ("A", "B"), "S": ("A", "C", "E"), "T": ("C", "D")},
          free_vars=("A", "C"), ring=sum_ring(), domains=DOMS,
          lifts={"B": ("value",), "D": ("value",), "E": ("value",)})
vo = chain(["A", "C"], {"A": [["B"]], "C": [["D"], ["E"]]})
rng = np.random.default_rng(5)
def rel(schema):
    shape = tuple(DOMS[v] for v in schema)
    return DenseRelation(tuple(schema), q.ring, {"v": jnp.asarray(
        rng.integers(0, 3, size=shape).astype(np.float32))})
db = {"R": rel("AB"), "S": rel("ACE"), "T": rel("CD")}
stream = []
for i, r in enumerate(["R", "S", "T"] * 3):
    sch = q.relations[r]
    keys = np.stack([rng.integers(0, DOMS[v], size=5) for v in sch],
                    axis=1).astype(np.int32)
    vals = rng.integers(-2, 3, size=5).astype(np.float32)
    stream.append((r, COOUpdate(sch, jnp.asarray(keys),
                                {"v": jnp.asarray(vals)})))
single = IVMEngine.build(q, db, var_order=vo, storage="sparse")
StreamExecutor(single).run(stream)
sharded = IVMEngine.build(q, db, var_order=vo, storage="sparse")
ex = shard_executor(sharded)
ex.run(stream)
got = np.asarray(sharded.result().transpose(("A", "C")).payload["v"])
ref = np.asarray(single.result().transpose(("A", "C")).payload["v"])
from repro.core.plan import FusedChain
print(json.dumps(dict(match=bool(np.array_equal(got, ref)),
                      sharded_views=list(ex.shard.sharded_views()),
                      fused_chains=sum(isinstance(op, FusedChain)
                                       for p in sharded.plans.plans.values()
                                       for op in p.ops),
                      devices=len(jax.devices()))))
"""

#: the same child with the Pallas kernels (interpret mode) and plan fusion
#: forced on: the sharded program runs them per device
#: (``ring_scatter.per_device``), as it must on TPU chips
_KERNEL_CHILD = _CHILD.replace("""
assert len(jax.devices()) == 4""", """
from repro.core import plan as plan_mod
from repro.kernels import scatter_ops
scatter_ops.set_backend("onehot_interpret")
plan_mod.set_fusion("on")
assert len(jax.devices()) == 4""")


def _run_child(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["devices"] == 4
    assert report["match"], report
    assert report["sharded_views"], "nothing sharded on a 4-device mesh"
    return report


def test_sharded_equivalence_forced_host_devices():
    _run_child(_CHILD)


def test_sharded_kernels_per_device_forced_host_devices():
    report = _run_child(_KERNEL_CHILD)
    assert report["fused_chains"], "no fused chain reached the kernels"
