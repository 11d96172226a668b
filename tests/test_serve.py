"""Serving plane (DESIGN.md §12): snapshot registry, batched lookup
kernels, ViewServer front end, and the snapshot-consistency acceptance
criteria.

Layers under test:

* lookup kernels — point / range_sum / range_scan / top_k against numpy
  references on both storage backends (payloads are integer-valued f32,
  so every comparison is bit-for-bit), including zombie transparency and
  padding-row semantics;
* ``SnapshotRegistry`` — retention, pin-protects-eviction, generation
  monotonicity;
* ``ViewServer`` — request padding/slicing, staleness telemetry
  (stats schema is pinned here), checkpoint/publish copy sharing;
* acceptance criteria — pinned-generation lookups are bit-identical to
  an *offline recomputation* at that generation (replay ``stream[:snap.
  offset]`` on a fresh engine), on dense and hashed-COO storage, on a
  single device (in-process, including a reader thread concurrent with
  fault-injected segment runs) and on 4 devices (subprocess).
"""
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.stream_state import StreamCheckpointer
from repro.core import (DenseRelation, SparseRelation, StreamExecutor,
                        sum_ring)
from repro.runtime import faults
from repro.serve import SnapshotRegistry, ViewServer
from repro.serve import lookup as lookup_mod
from test_recovery import (CH_DOMS, chaos_engine, chaos_query,
                           chaos_reference, chaos_result, chaos_stream)


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# lookup kernels vs numpy references (both backends, bit-for-bit)
# ---------------------------------------------------------------------------
DOMS = (5, 4, 3)
SCHEMA = ("A", "B", "C")


def _views(seed=0, n=40):
    """A dense view, a value-identical sparse view (with zombies: some
    keys net to exactly ring zero), and the numpy ground truth."""
    rng = np.random.default_rng(seed)
    ring = sum_ring()
    keys = np.stack([rng.integers(0, d, size=n) for d in DOMS],
                    axis=1).astype(np.int32)
    vals = rng.integers(-3, 4, size=n).astype(np.float32)
    mult = np.zeros(DOMS, np.float32)
    np.add.at(mult, tuple(keys.T), vals)
    dense = DenseRelation(SCHEMA, ring, {"v": jnp.asarray(mult)})
    sparse = SparseRelation.from_coo(SCHEMA, ring, DOMS, jnp.asarray(keys),
                                     {"v": jnp.asarray(vals)}, capacity=128)
    return dense, sparse, mult


@pytest.mark.parametrize("backend", ["dense", "sparse"])
def test_point_kernel_matches_numpy(backend):
    dense, sparse, mult = _views()
    view = dense if backend == "dense" else sparse
    rng = np.random.default_rng(1)
    q = np.stack([rng.integers(0, d, size=16) for d in DOMS],
                 axis=1).astype(np.int32)
    q = np.concatenate([q, np.full((2, 3), -1, np.int32)])  # padding rows
    out = lookup_mod.point(view, jnp.asarray(q))
    ref = np.concatenate([mult[tuple(q[:16].T)], np.zeros(2, np.float32)])
    np.testing.assert_array_equal(np.asarray(out["v"]), ref)


@pytest.mark.parametrize("backend", ["dense", "sparse"])
def test_range_sum_kernel_matches_numpy(backend):
    dense, sparse, mult = _views()
    view = dense if backend == "dense" else sparse
    flat = mult.reshape(-1)
    for lo, hi in [(0, flat.size), (7, 41), (13, 13), (50, 9)]:
        out = lookup_mod.range_sum(view, jnp.int32(lo), jnp.int32(hi))
        np.testing.assert_array_equal(np.asarray(out["v"]),
                                      flat[lo:max(lo, hi)].sum())


@pytest.mark.parametrize("backend", ["dense", "sparse"])
def test_range_scan_kernel_matches_numpy(backend):
    dense, sparse, mult = _views()
    view = dense if backend == "dense" else sparse
    flat = mult.reshape(-1)
    lo, hi, k = 5, 50, 6
    ids = np.flatnonzero(flat != 0)
    sel = ids[(ids >= lo) & (ids < hi)][:k]
    keys, payload, valid = lookup_mod.range_scan(view, jnp.int32(lo),
                                                 jnp.int32(hi), k)
    nv = int(np.asarray(valid).sum())
    assert nv == len(sel)
    np.testing.assert_array_equal(np.asarray(keys)[:nv],
                                  np.stack(np.unravel_index(sel, DOMS), 1))
    np.testing.assert_array_equal(np.asarray(payload["v"])[:nv], flat[sel])
    assert not np.asarray(payload["v"])[nv:].any()  # ring zero past the end


@pytest.mark.parametrize("backend", ["dense", "sparse"])
def test_top_k_kernel_matches_numpy(backend):
    # distinct positive values on distinct keys -> a unique descending order
    rng = np.random.default_rng(2)
    ring = sum_ring()
    S = int(np.prod(DOMS))
    ids = rng.choice(S, size=12, replace=False)
    vals = rng.permutation(np.arange(1, 13)).astype(np.float32)
    keys = np.stack(np.unravel_index(ids, DOMS), 1).astype(np.int32)
    mult = np.zeros(DOMS, np.float32)
    mult[tuple(keys.T)] = vals
    dense = DenseRelation(SCHEMA, ring, {"v": jnp.asarray(mult)})
    sparse = SparseRelation.from_coo(SCHEMA, ring, DOMS, jnp.asarray(keys),
                                     {"v": jnp.asarray(vals)}, capacity=64)
    view = dense if backend == "dense" else sparse
    got_keys, got_vals, valid = lookup_mod.top_k(view, 5)
    order = np.argsort(-vals)[:5]
    assert np.asarray(valid).all()
    np.testing.assert_array_equal(np.asarray(got_vals), vals[order])
    np.testing.assert_array_equal(np.asarray(got_keys), keys[order])
    # k beyond the live population: the overhang is invalid + ring zero
    _, v2, valid2 = lookup_mod.top_k(view, 16)
    assert int(np.asarray(valid2).sum()) == 12
    assert not np.asarray(v2)[12:].any()


def test_lookup_kernels_are_zombie_transparent():
    """Keys deleted down to exact ring zero keep their slot but never
    surface through any serving kernel."""
    ring = sum_ring()
    sparse = SparseRelation.zeros(("A",), ring, (32,), capacity=16)
    keys = jnp.asarray([[3], [11], [20]], jnp.int32)
    sparse = sparse.scatter_add(keys, {"v": jnp.asarray([4.0, 6.0, 9.0],
                                                        jnp.float32)})
    sparse = sparse.scatter_add(keys[1:2], {"v": jnp.asarray([-6.0],
                                                             jnp.float32)})
    assert sparse.num_slots_used_sync() == 3  # zombie holds its slot
    np.testing.assert_array_equal(
        np.asarray(lookup_mod.point(sparse, keys)["v"]), [4.0, 0.0, 9.0])
    np.testing.assert_array_equal(
        np.asarray(lookup_mod.range_sum(sparse, jnp.int32(0),
                                        jnp.int32(32))["v"]), 13.0)
    skeys, _, valid = lookup_mod.range_scan(sparse, jnp.int32(0),
                                            jnp.int32(32), 4)
    assert int(np.asarray(valid).sum()) == 2
    np.testing.assert_array_equal(np.asarray(skeys)[:2], [[3], [20]])
    tkeys, tvals, tvalid = lookup_mod.top_k(sparse, 3)
    assert int(np.asarray(tvalid).sum()) == 2
    np.testing.assert_array_equal(np.asarray(tvals)[:2], [9.0, 4.0])
    np.testing.assert_array_equal(np.asarray(tkeys)[:2], [[20], [3]])


# ---------------------------------------------------------------------------
# SnapshotRegistry: retention, pinning, monotonicity
# ---------------------------------------------------------------------------
def test_registry_retention_and_pin_protects_eviction():
    reg = SnapshotRegistry(retain=2)
    for g in range(4):
        reg.publish({"x": jnp.full((3,), g, jnp.int32)})
    assert reg.generation == 3 and reg.publishes == 4
    with pytest.raises(LookupError):
        reg.get(0)  # evicted by double-buffered retention
    reg.pin()   # newest (3)
    reg.pin(2)
    for g in range(4, 8):
        reg.publish({"x": jnp.full((3,), g, jnp.int32)})
    # pinned generations survive arbitrarily many publishes, values intact
    np.testing.assert_array_equal(np.asarray(reg.get(2).views["x"]), [2] * 3)
    np.testing.assert_array_equal(np.asarray(reg.get(3).views["x"]), [3] * 3)
    reg.release(2)
    reg.release(3)
    with pytest.raises(LookupError):
        reg.get(2)  # release of an out-of-window pin evicts immediately
    assert reg.stats()["retained"] == 2


def test_registry_rejects_bad_args():
    with pytest.raises(ValueError):
        SnapshotRegistry(retain=0)
    with pytest.raises(ValueError):
        SnapshotRegistry(segment_updates=0)
    reg = SnapshotRegistry()
    with pytest.raises(LookupError):
        reg.latest()  # nothing published yet
    reg.publish({"x": jnp.zeros(2)})
    with pytest.raises(LookupError):
        reg.pin(7)


# ---------------------------------------------------------------------------
# ViewServer: padding, telemetry schema, copy sharing with the checkpointer
# ---------------------------------------------------------------------------
def test_viewserver_pads_and_slices_batches():
    q = chaos_query()
    eng = chaos_engine("sparse")
    StreamExecutor(eng).run(chaos_stream(q, "scan", 11))
    server = ViewServer(StreamExecutor(eng))
    name = sorted(server.registry.latest().views)[0]
    view = eng.views[name]
    rng = np.random.default_rng(5)
    keys = np.stack([rng.integers(0, int(view.domain_of(v)), size=5)
                     for v in view.schema], axis=1).astype(np.int32)
    res = server.point(name, keys)
    assert res.kind == "point" and res.generation == 0
    got = res.host()
    ref = lookup_mod.point(view, jnp.asarray(keys))
    for c in ref:
        assert got[c].shape[0] == 5  # pad rows (to MIN_BATCH=8) sliced off
        np.testing.assert_array_equal(got[c], np.asarray(ref[c]))


def test_viewserver_stats_schema():
    """The stats surface other tooling keys off — schema-pinned."""
    q = chaos_query()
    eng = chaos_engine("dense")
    ex = StreamExecutor(eng)
    server = ViewServer(ex, segment_updates=3)
    ex.run(chaos_stream(q, "scan", 11))
    st = server.stats()
    assert set(st) == {"generation", "publishes", "retained", "pinned",
                       "publish_s", "publish_to_first_read_s",
                       "generation_lag", "last_segment_stats",
                       "straggler_baseline"}
    # bootstrap + one boundary per 3-update segment of the 8-update stream
    assert st["generation"] == 3 and st["publishes"] == 4
    assert st["generation_lag"] == 3  # nothing read since the bootstrap
    seg = st["last_segment_stats"]
    assert [e["generation"] for e in seg] == [1, 2, 3]
    assert all(set(e) == {"segment", "n_steps", "admit_s", "dispatch_s",
                          "save_s", "audit_s", "publish_s", "generation",
                          "straggler", "straggler_baseline", "counts"}
               for e in seg)
    name = sorted(server.registry.latest().views)[0]
    server.point(name, np.zeros((2, len(eng.views[name].schema)), np.int32))
    assert server.stats()["generation_lag"] == 0
    assert server.stats()["publish_to_first_read_s"] is not None


def test_boundary_publish_and_checkpoint_share_copies(tmp_path):
    """A boundary that both publishes and checkpoints hands the registry's
    stamped copies to the checkpointer (no double copy) — the restored
    snapshot must still be bit-identical to the live engine."""
    q = chaos_query()
    stream = chaos_stream(q, "rounds", 11)
    eng = chaos_engine("sparse")
    ck = StreamCheckpointer(str(tmp_path), segment_updates=2)
    ex = StreamExecutor(eng, checkpoint=ck)
    server = ViewServer(ex, segment_updates=2)
    ex.run(stream)
    assert server.registry.generation >= 4
    eng2 = chaos_engine("sparse")
    meta = ck.restore_into(eng2)
    assert meta["offset"] == len(stream)
    np.testing.assert_array_equal(chaos_result(eng2), chaos_result(eng))
    for n in eng.views:
        for a, b in zip(jax.tree.leaves(eng.views[n]),
                        jax.tree.leaves(eng2.views[n])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# acceptance: pinned generations == offline recomputation at that offset
# ---------------------------------------------------------------------------
def _probe_keys(view, n=6):
    if not view.schema:
        return np.zeros((n, 0), np.int32)
    return np.stack([np.arange(n) % int(view.domain_of(v))
                     for v in view.schema], axis=1).astype(np.int32)


def _offline_reads(storage, offset, stream, probe_keys):
    """Replay ``stream[:offset]`` on a fresh engine and read every view
    through the same serving kernels."""
    eng = chaos_engine(storage)
    if offset:
        StreamExecutor(eng).run(stream[:offset])
    srv = ViewServer(StreamExecutor(eng))
    out = {}
    for n in sorted(srv.registry.latest().views):
        out[n] = (srv.point(n, probe_keys[n]).host(),
                  srv.range_sum(n, 0, 1 << 30).host())
    return eng, out


@pytest.mark.parametrize("storage", ["dense", "sparse"])
def test_every_generation_matches_offline_recompute(storage):
    """Each published generation's views (all of them — the atomicity
    contract) are bit-identical to a fresh engine that replayed exactly
    ``snap.offset`` leading stream updates."""
    q = chaos_query()
    stream = chaos_stream(q, "rounds", 11)
    eng = chaos_engine(storage)
    ex = StreamExecutor(eng)
    server = ViewServer(ex, retain=32, segment_updates=2)
    ex.run(stream)
    reg = server.registry
    assert reg.generation >= 4  # bootstrap + >= one boundary per 2 updates
    names = sorted(reg.latest().views)
    probe = {n: _probe_keys(eng.views[n]) for n in names}
    for g in range(reg.generation + 1):
        with server.pin(g) as p:
            snap = reg.get(g)
            assert p.offset == snap.offset
            ref_eng, ref_reads = _offline_reads(storage, snap.offset,
                                                stream, probe)
            for n in names:
                for a, b in zip(jax.tree.leaves(snap.views[n]),
                                jax.tree.leaves(ref_eng.views[n])):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
                got_pt = jax.device_get(p.point(n, probe[n]).data)
                got_rs = jax.device_get(p.range_sum(n, 0, 1 << 30).data)
                ref_pt, ref_rs = ref_reads[n]
                for c in got_pt:
                    np.testing.assert_array_equal(got_pt[c], ref_pt[c])
                    np.testing.assert_array_equal(got_rs[c], ref_rs[c])
    assert reg.latest().offset == len(stream)


@pytest.mark.parametrize("storage", ["dense", "sparse"])
def test_reader_thread_never_sees_torn_generation(tmp_path, storage):
    """The chaos criterion: a reader thread issuing pinned multi-view
    lookups *while* segments execute under fault injection (kill +
    in-process resume) observes only whole generations — every observed
    (generation, offset, values) triple matches an offline recomputation
    at that offset; no torn or mixed-generation read, before or after
    the fault."""
    q = chaos_query()
    stream = chaos_stream(q, "rounds", 11)
    eng = chaos_engine(storage)
    ex = StreamExecutor(eng, checkpoint=StreamCheckpointer(
        str(tmp_path), segment_updates=2))
    server = ViewServer(ex, segment_updates=2)
    names = sorted(server.registry.latest().views)
    probe = {n: _probe_keys(eng.views[n]) for n in names}
    for n in names:  # pre-warm the lookup kernels on the current layouts
        server.point(n, probe[n])
        server.range_sum(n, 0, 1 << 30)

    seen: dict = {}
    errors: list = []
    stop = threading.Event()

    def reader():
        try:
            while not stop.is_set():
                with server.pin() as p:
                    if p.generation not in seen:
                        vals = {
                            n: (jax.device_get(p.point(n, probe[n]).data),
                                jax.device_get(
                                    p.range_sum(n, 0, 1 << 30).data))
                            for n in names
                        }
                        seen[p.generation] = (p.offset, vals)
                time.sleep(0.001)
        except Exception as e:  # noqa: BLE001 — surfaced by the assert below
            errors.append(e)

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    try:
        with faults.inject("mid_segment", at=1):
            with pytest.raises(faults.InjectedFault):
                ex.resume(stream)
        ex.resume(stream)  # in-process restart; registry stays attached
        # let the reader observe the final generation
        deadline = time.time() + 10
        while server.registry.generation not in seen and time.time() < deadline:
            time.sleep(0.005)
    finally:
        stop.set()
        t.join(timeout=30)
    assert not errors, errors
    assert len(seen) >= 2
    assert max(off for off, _ in seen.values()) == len(stream)
    np.testing.assert_array_equal(chaos_result(eng),
                                  chaos_reference(storage, "rounds"))
    offline: dict = {}
    for g, (offset, vals) in sorted(seen.items()):
        if offset not in offline:
            _, offline[offset] = _offline_reads(storage, offset, stream,
                                                probe)
        for n in names:
            got_pt, got_rs = vals[n]
            ref_pt, ref_rs = offline[offset][n]
            for c in got_pt:
                np.testing.assert_array_equal(got_pt[c], ref_pt[c])
                np.testing.assert_array_equal(got_rs[c], ref_rs[c])


# ---------------------------------------------------------------------------
# 4-device serving (subprocess: forced host device count)
# ---------------------------------------------------------------------------
_SERVE_CHILD = r"""
import sys
import numpy as np, jax, jax.numpy as jnp
from repro.core import (COOUpdate, DenseRelation, IVMEngine, Query,
                        StreamExecutor, chain, shard_executor, sum_ring)
from repro.serve import ViewServer

assert len(jax.devices()) == 4, jax.devices()
CH_DOMS = dict(A=64, B=64, C=3)
q = Query(relations={"R": ("A", "B"), "T": ("B", "C")}, free_vars=("A",),
          ring=sum_ring(), domains=CH_DOMS, lifts={"C": ("value",)})

def build_db():
    rng = np.random.default_rng(3)
    def rel(schema):
        shape = tuple(CH_DOMS[v] for v in schema)
        mult = np.zeros(shape, np.float32)
        idx = tuple(rng.integers(0, d, size=8) for d in shape)
        np.add.at(mult, idx, 1.0)
        return DenseRelation(tuple(schema), q.ring, {"v": jnp.asarray(mult)})
    return {"R": rel("AB"), "T": rel("BC")}

def engine(storage):
    return IVMEngine.build(q, build_db(),
                           var_order=chain(["A", "B"], {"B": [["C"]]}),
                           storage=storage)

srng = np.random.default_rng(11)
stream = []
for r in ["R", "T"] * 4:
    sch = q.relations[r]
    keys = np.stack([srng.integers(0, CH_DOMS[v], size=24) for v in sch],
                    axis=1).astype(np.int32)
    vals = srng.integers(-2, 3, size=24).astype(np.float32)
    stream.append((r, COOUpdate(sch, jnp.asarray(keys),
                                {"v": jnp.asarray(vals)})))

for storage in ("dense", "sparse"):
    eng = engine(storage)
    ex = shard_executor(eng)
    server = ViewServer(ex, retain=64, segment_updates=2)
    ex.run(stream)
    reg = server.registry
    assert reg.generation >= 4, reg.generation
    names = sorted(reg.latest().views)
    for g in range(reg.generation + 1):
        with server.pin(g) as p:
            snap = reg.get(g)
            ref = engine(storage)
            if snap.offset:
                shard_executor(ref).run(stream[:snap.offset])
            rsrv = ViewServer(StreamExecutor(ref))
            for n in names:
                for a, b in zip(jax.tree.leaves(snap.views[n]),
                                jax.tree.leaves(ref.views[n])):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
                view = ref.views[n]
                if not view.schema:
                    continue
                keys = np.stack([np.arange(6) % int(view.domain_of(v))
                                 for v in view.schema],
                                axis=1).astype(np.int32)
                got = p.point(n, keys).host()
                want = rsrv.point(n, keys).host()
                for c in got:
                    np.testing.assert_array_equal(got[c], want[c])
    assert reg.latest().offset == len(stream)
    print(storage, "OK")
print("SERVE-4DEV OK")
"""


def test_four_device_pinned_reads_match_offline_recompute():
    """Acceptance on 4 (forced host) devices: a sharded executor serving
    through a ViewServer publishes generations whose pinned lookups are
    bit-identical to offline recomputation at each generation's offset,
    for dense and hashed-COO storage."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _SERVE_CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.returncode, out.stdout[-500:],
                                 out.stderr[-2000:])
    assert "SERVE-4DEV OK" in out.stdout
