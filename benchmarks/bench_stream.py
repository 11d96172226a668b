"""Fused stream executor vs per-call trigger dispatch (ISSUE 1 / ISSUE 2).

Three fused-stream sweeps, all written to ``BENCH_stream.json``:

* **retailer_sum_aggregate** — strategy × batch size, fused vs per-call
  (the PR-1 trajectory rows, kernel-off so numbers stay comparable).
* **housing_sum_aggregate** — the star schema's wide postcode dictionary
  (``pc=4096``), fivm, kernel-on vs kernel-off scatter backends.
* **retailer_cofactor_degree_m** — degree-m cofactor-ring payloads
  (the (c, s, Q) triple flattens to a ``1+m+m²`` feature plane in the
  scatter shim), fivm, kernel-on vs kernel-off.
* **housing_sparse_pc65536** — the full-width postcode dictionary at
  sub-percent fill: dense vs hashed-COO view storage (the ViewStorage
  planner), reporting fused throughput, *peak view bytes* under each
  backend, and a bit-identity check of the final result.
* **sharded sweep** — the housing ``pc=65536`` sparse stream and the
  degree-m cofactor stream on a plan-sharded scan carry (DESIGN.md §9),
  one subprocess per device count under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``: per-device-count
  fused throughput plus an exact-equality check against the unsharded
  executor in the same process (integer-valued payloads: every
  accumulation order is exact).
* **segmented_pipeline** — a capacity-segmented raw stream with the
  two-deep admit/run pipeline on vs off (blocking between stages): both
  walls plus the admit / device-wait split.  The pipeline hides the
  device waits behind admission; their size (and hence the wall delta)
  is a few percent on this shared-core CPU host.
* **checkpointing** — the same segmented workload with segment-boundary
  engine snapshots on vs off (DESIGN.md §10): both walls, the writer
  thread's save wall, the pipeline stall attributable to checkpointing
  (the save *dispatch* — device copies + thread handoff — as distinct
  from the PR-5 admit/wait split), and the restore-to-first-segment
  latency of a resume.  Asserts checkpoint-on throughput ≥ 0.9× off.
* **integrity** — admission validation and the audited Reevaluate pass
  (DESIGN.md §11) on the housing ``pc=65536`` sparse stream and the
  degree-m cofactor stream: validation-on vs -off walls under identical
  segmentation, plus the audit-every-2-segments wall and per-pass audit
  seconds.  Asserts validation-on throughput ≥ 0.9× off.

Kernel-on on this CPU container means the ``compact_xla`` dispatch path
(key-dedup compaction; the Pallas kernels themselves target TPU and are
pinned bit-identical by tests/test_ring_scatter.py in interpret mode);
kernel-off is the legacy ``.at[].add`` scatter.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from repro.core import IVMEngine, Query, sum_ring
from repro.core.apps import regression
from repro.kernels import scatter_ops

from .common import (HOUSING_DOMS, HOUSING_DOMS_BIG, HOUSING_RELATIONS,
                     RETAILER_DOMS, RETAILER_RELATIONS, emit, housing_vo,
                     retailer_vo, run_engine_stream, synth_db,
                     synth_low_fill_db, update_stream)

JSON_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_stream.json")

#: device counts of the sharded sweep (one forced-host-platform subprocess
#: each); override with REPRO_BENCH_DEVICE_COUNTS="1,4"
DEVICE_COUNTS = (1, 2, 4)

_CHILD_MARKER = "SHARDED_RESULT:"


def _measure(q, db, vo, strategy, stream, repeats, backend=None):
    """(fused tps, per-call tps, plan stats) under an optional
    scatter-backend override.  Plan stats come from the fused engine's
    plan cache: total and per-plan trigger compile time plus the lookup
    hit rate across prepare + replay (DESIGN.md §8 telemetry)."""
    with scatter_ops.use_backend(backend):
        eng_f = IVMEngine.build(q, db, var_order=vo, strategy=strategy)
        tps_fused, _ = run_engine_stream(eng_f, stream, fused=True,
                                         repeats=repeats)
        eng_p = IVMEngine.build(q, db, var_order=vo, strategy=strategy)
        tps_percall, _ = run_engine_stream(eng_p, stream, fused=False,
                                           repeats=repeats)
    return tps_fused, tps_percall, eng_f.plans.stats()


def _load_baseline(json_path):
    """Prior BENCH_stream.json rows keyed for the regression guard."""
    if json_path is None or not os.path.exists(json_path):
        return {}
    try:
        with open(json_path) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        return {}
    out = {}
    for r in prev.get("results", []):
        key = (r.get("dataset"), r.get("strategy"), r.get("batch"),
               r.get("scatter_backend", r.get("storage", "auto")))
        if "fused_tuples_per_s" in r:
            out[key] = r["fused_tuples_per_s"]
    return out


def _sharded_child(seed: int = 0, repeats: int = 2) -> list[dict]:
    """Child-process body of the sharded sweep: runs in a fresh
    interpreter whose XLA_FLAGS forced the host device count.  For each
    dataset, measures the unsharded fused executor and the plan-sharded
    one on the same state, and checks exact result equality (payloads are
    integer-valued, so reduction order cannot blur the comparison)."""
    import jax

    from repro.core import plan_shards

    n_dev = len(jax.devices())
    rows: list[dict] = []

    def leg(dataset, q, db, vo, stream, expect_exact):
        """``expect_exact``: integer-valued scalar payloads accumulate
        exactly in any order; general float rings (degree-m cofactor
        einsums) may reorder cross-shard reductions — ≤1e-6 relative is
        the ISSUE 5 acceptance bound for those."""
        single = IVMEngine.build(q, db, var_order=vo, strategy="fivm")
        tps_single, _ = run_engine_stream(single, stream, fused=True,
                                          repeats=repeats)
        sharded = IVMEngine.build(q, db, var_order=vo, strategy="fivm")
        sp = plan_shards(sharded)
        tps_sharded, _ = run_engine_stream(sharded, stream, fused=True,
                                           repeats=repeats, shard=sp)
        ref = single.result().payload_sync()
        got = sharded.result().payload_sync()
        exact = all(np.array_equal(ref[c], got[c]) for c in ref)
        # relative error per ring component: payload planes differ in
        # scale by orders of magnitude (count vs cofactor planes), and a
        # divergence in a small plane must not hide under a large one's
        # denominator
        max_rel = float(max(
            np.abs(ref[c] - got[c]).max()
            / max(float(np.abs(ref[c]).max()), 1e-30)
            for c in ref))
        rows.append(dict(
            dataset=dataset + "_sharded", strategy="fivm", devices=n_dev,
            batch=stream[0][1].batch, n_batches=len(stream),
            fused_tuples_per_s=round(tps_sharded),
            single_placement_tuples_per_s=round(tps_single),
            sharded_views=len(sp.sharded_views()),
            exact_match=bool(exact), max_rel_diff=max_rel,
            matches_single=bool(exact if expect_exact
                                else max_rel <= 1e-6)))

    rng = np.random.default_rng(seed)
    ring = sum_ring()
    # housing pc=65536 sparse stream (the ViewStorage planner goes sparse)
    big = dict(HOUSING_DOMS_BIG)
    sq = Query(relations=HOUSING_RELATIONS, free_vars=(), ring=ring,
               domains=big, lifts={"h2": ("value",)})
    sdb, active = synth_low_fill_db(HOUSING_RELATIONS, big, ring,
                                    np.random.default_rng(seed), "pc",
                                    n_active=512)
    stream = update_stream(HOUSING_RELATIONS, big, ring,
                           np.random.default_rng(seed + 1), 64, 10,
                           key_pools={"pc": active})
    leg("housing_sparse_pc65536", sq, sdb, housing_vo(), stream,
        expect_exact=True)  # ±1 multiplicities: int-valued, exact ⊕ order
    # degree-m cofactor ring (wide payload planes across the mesh)
    cq = regression.cofactor_query(RETAILER_RELATIONS, RETAILER_DOMS)
    cdb = synth_db(RETAILER_RELATIONS, RETAILER_DOMS, cq.ring, rng)
    cstream = update_stream(RETAILER_RELATIONS, RETAILER_DOMS, cq.ring,
                            rng, 16, 6)
    leg("retailer_cofactor_degree_m", cq, cdb, retailer_vo(), cstream,
        expect_exact=False)  # float einsum reductions: ≤1e-6 rel
    return rows


def _sharded_sweep(results, rows, device_counts, seed: int = 0):
    """Spawn one forced-host-platform subprocess per device count and
    merge its rows; asserts the multi-device runs match single-placement
    exactly (the bound for int-valued payloads).

    CPU hosts only (``run`` skips it elsewhere): the children force the
    host platform, and on an accelerator host the parent already holds
    the chip, so they would record CPU rows under the sweep's names.
    There the sharded comparison is ``chip_smoke.py --chips 4``."""
    env_counts = os.environ.get("REPRO_BENCH_DEVICE_COUNTS")
    if env_counts:
        device_counts = tuple(int(x) for x in env_counts.split(","))
    for n_dev in device_counts:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count="
                              f"{n_dev}").strip()
        env.setdefault("JAX_PLATFORMS", "cpu")
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_stream",
             "--sharded-child", str(seed)],
            env=env, capture_output=True, text=True, timeout=1800,
            cwd=os.path.join(os.path.dirname(__file__), ".."))
        assert out.returncode == 0, out.stderr[-4000:]
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith(_CHILD_MARKER)][-1]
        for row in json.loads(line[len(_CHILD_MARKER):]):
            assert row["matches_single"], (
                f"sharded run diverged at devices={row['devices']}: {row}")
            results.append(row)
            rows.append((
                f"stream/{row['dataset']}/devices={row['devices']}"
                f"/b={row['batch']}",
                round(1e6 * row["batch"] / row["fused_tuples_per_s"], 1),
                f"fused_tps={row['fused_tuples_per_s']};"
                f"single_tps={row['single_placement_tuples_per_s']};"
                f"sharded_views={row['sharded_views']};"
                f"exact={row['exact_match']};"
                f"max_rel_diff={row['max_rel_diff']:.1e}"))


def _segmented_pipeline_leg(results, rows, seed: int = 0):
    """Capacity-segmented raw stream, two-deep pipeline on vs off.  The
    row records the honest split: admit (host-side stacking/prepare),
    the blocking mode's per-segment device waits (the additive part the
    pipeline hides), and both walls.  On this shared-core CPU host the
    device waits are a few percent of the wall, so the walls land within
    noise of each other — the overlap bound is min(admit, execute), and
    it only pays off where DMA and compute are separate engines."""
    import jax
    import jax.numpy as jnp

    from repro.core import (COOUpdate, DenseRelation, StreamExecutor,
                            capacity_segments, chain)

    doms = dict(A=512, B=512, C=4)
    q = Query(relations={"R": ("A", "B"), "T": ("B", "C")},
              free_vars=("A",), ring=sum_ring(), domains=doms,
              lifts={"C": ("value",)})
    rng = np.random.default_rng(seed)

    def rel(schema):
        shape = tuple(doms[v] for v in schema)
        mult = np.zeros(shape, np.float32)
        idx = tuple(rng.integers(0, d, size=32) for d in shape)
        np.add.at(mult, idx, 1.0)
        return DenseRelation(tuple(schema), q.ring, {"v": jnp.asarray(mult)})

    db = {"R": rel("AB"), "T": rel("BC")}
    vo = chain(["A", "B"], {"B": [["C"]]})

    def fresh_engine():
        return IVMEngine.build(q, db, var_order=vo, strategy="fivm",
                               storage="sparse",
                               storage_opts=dict(min_capacity=64))

    def mk_stream():
        out = []
        r2 = np.random.default_rng(seed + 7)
        for i in range(24):
            sch = q.relations["R"]
            keys = np.stack([r2.integers(0, doms[v], size=128)
                             for v in sch], 1).astype(np.int32)
            out.append(("R", COOUpdate(sch, jnp.asarray(keys),
                                       {"v": jnp.asarray(
                                           np.ones(128, np.float32))})))
        return out

    stream = mk_stream()
    n_segments = len(capacity_segments(fresh_engine(), stream))
    assert n_segments > 2, f"stream must segment, got {n_segments}"
    # one executor per mode; update_engine=False restores the engine, so
    # every timed pass replays the identical segment trajectory with
    # every program already in the compile cache (warm pass below) — the
    # A/B then isolates the admit/run overlap, not compile time.  The
    # modes are measured *interleaved*, best-of-5 each: on a 2-core CPU
    # host the "device" work and the host-side stacking share cores, so
    # a contended stretch must hit both modes rather than skew one
    # (real accelerators separate the DMA and compute engines; there
    # the overlap is structural)
    modes = {"blocking": False, "pipelined": True}
    execs = {}
    for mode, pipelined in modes.items():
        execs[mode] = StreamExecutor(fresh_engine())
        execs[mode].run(stream, update_engine=False, pipeline=pipelined)
    walls = {m: float("inf") for m in modes}
    admits, dispatches = {}, {}
    for _ in range(5):
        for mode, pipelined in modes.items():
            ex = execs[mode]
            t0 = time.perf_counter()
            state = ex.run(stream, update_engine=False, pipeline=pipelined)
            jax.block_until_ready(state)
            wall = time.perf_counter() - t0
            if wall < walls[mode]:
                walls[mode] = wall
                admits[mode] = sum(s["admit_s"]
                                   for s in ex.last_segment_stats)
                dispatches[mode] = sum(s["dispatch_s"]
                                       for s in ex.last_segment_stats)
    # blocking mode serializes: wall ≈ admit + per-segment device waits
    # (its dispatch_s includes the block).  The pipelined wall beats the
    # additive estimate exactly when uploads overlapped execution.
    additive = admits["pipelined"] + dispatches["blocking"]
    overlap = additive / max(walls["pipelined"], 1e-12)
    row = dict(dataset="segmented_pipeline", strategy="fivm", batch=128,
               n_batches=len(stream), n_segments=n_segments,
               wall_pipelined_s=round(walls["pipelined"], 4),
               wall_blocking_s=round(walls["blocking"], 4),
               admit_s_pipelined=round(admits["pipelined"], 4),
               segment_wait_s_blocking=round(dispatches["blocking"], 4),
               additive_over_pipelined=round(overlap, 3))
    results.append(row)
    rows.append((f"stream/segmented_pipeline/segs={n_segments}/b=128",
                 round(1e6 * walls["pipelined"] / (128 * len(stream)), 1),
                 f"wall_pipelined={walls['pipelined']:.3f}s;"
                 f"wall_blocking={walls['blocking']:.3f}s;"
                 f"admit_s={admits['pipelined']:.3f};"
                 f"additive_over_pipelined={overlap:.2f}x"))


def _checkpointing_leg(results, rows, seed: int = 0):
    """Segment-boundary checkpointing on vs off, on the segmented
    workload of ``_segmented_pipeline_leg`` (both pipelined).

    The checkpoint-on executor snapshots the engine at every boundary
    (``segment_updates=4`` on a 24-batch stream → ≥6 snapshots/pass) with
    async saves: the timed wall *includes* the final durable commit
    (``wait()``), so the ratio is honest end-to-end durability cost.
    Per-pass telemetry splits it into the pipeline stall the save
    dispatch costs (device copies + writer handoff, ``save_s``) and the
    writer thread's own wall (device→host copy + npy write + fsync +
    rename), which overlaps the next segment's admission/execution the
    same way admission overlaps dispatch.  Engine state is container-
    snapshot-restored between passes so every pass replays the identical
    segment trajectory against warm compile caches.  The acceptance
    gate: checkpoint-on throughput ≥ 0.9× checkpoint-off."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.checkpoint.stream_state import StreamCheckpointer
    from repro.core import (COOUpdate, DenseRelation, StreamExecutor,
                            capacity_segments, chain)

    doms = dict(A=512, B=512, C=4)
    q = Query(relations={"R": ("A", "B"), "T": ("B", "C")},
              free_vars=("A",), ring=sum_ring(), domains=doms,
              lifts={"C": ("value",)})
    rng = np.random.default_rng(seed)

    def rel(schema):
        shape = tuple(doms[v] for v in schema)
        mult = np.zeros(shape, np.float32)
        idx = tuple(rng.integers(0, d, size=32) for d in shape)
        np.add.at(mult, idx, 1.0)
        return DenseRelation(tuple(schema), q.ring, {"v": jnp.asarray(mult)})

    db = {"R": rel("AB"), "T": rel("BC")}
    vo = chain(["A", "B"], {"B": [["C"]]})

    def fresh_engine():
        return IVMEngine.build(q, db, var_order=vo, strategy="fivm",
                               storage="sparse",
                               storage_opts=dict(min_capacity=64))

    stream = []
    r2 = np.random.default_rng(seed + 7)
    for _ in range(24):
        sch = q.relations["R"]
        keys = np.stack([r2.integers(0, doms[v], size=128)
                         for v in sch], 1).astype(np.int32)
        stream.append(("R", COOUpdate(sch, jnp.asarray(keys),
                                      {"v": jnp.asarray(
                                          np.ones(128, np.float32))})))

    ckdir = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        ck = StreamCheckpointer(ckdir, keep=3, segment_updates=4)
        execs = {
            "off": StreamExecutor(fresh_engine()),
            "on": StreamExecutor(fresh_engine(), checkpoint=ck),
        }

        def one_pass(mode):
            ex = execs[mode]
            eng = ex.engine
            saved = (dict(eng.views), dict(eng.base), dict(eng.indicators))
            w0 = ck.write_seconds
            t0 = time.perf_counter()
            state = ex.run(stream, pipeline=True)
            jax.block_until_ready(state)
            wall = time.perf_counter() - t0
            eng.set_state(saved)
            stall = sum(s.get("save_s", 0.0)
                        for s in ex.last_segment_stats)
            return wall, stall, ck.write_seconds - w0

        for mode in execs:
            one_pass(mode)  # warm: compile every segment program
        walls = {m: float("inf") for m in execs}
        stalls, writes, boundaries = {}, {}, 0
        for _ in range(5):  # interleaved best-of-5 (see pipeline leg)
            for mode in execs:
                wall, stall, write_s = one_pass(mode)
                if wall < walls[mode]:
                    walls[mode] = wall
                    stalls[mode] = stall
                    writes[mode] = write_s
                    if mode == "on":
                        boundaries = len(
                            execs["on"].last_segment_stats)

        # restore-to-first-segment: a "restarted process" restores the
        # newest readable snapshot and re-admits the remaining stream.
        # The newest step is torn first so the restore lands mid-stream
        # (and the corrupt-fallback path gets exercised at bench scale).
        steps = ck.ckpt.all_steps()
        shutil.rmtree(os.path.join(ckdir, f"step_{steps[-1]:08d}"))
        eng2 = fresh_engine()
        ex2 = StreamExecutor(eng2, checkpoint=StreamCheckpointer(
            ckdir, keep=3, segment_updates=4))
        t0 = time.perf_counter()
        meta = ex2.checkpoint.restore_into(eng2)
        rest = stream[meta["offset"]:]
        segs = capacity_segments(eng2, rest)
        ex2._admit_segment(*segs[0])
        restore_s = time.perf_counter() - t0

        ratio = walls["off"] / walls["on"]
        row = dict(dataset="checkpointing", strategy="fivm", batch=128,
                   n_batches=len(stream), n_boundaries=boundaries,
                   wall_ckpt_on_s=round(walls["on"], 4),
                   wall_ckpt_off_s=round(walls["off"], 4),
                   ckpt_on_over_off_throughput=round(ratio, 3),
                   save_stall_s=round(stalls["on"], 4),
                   save_write_s=round(writes["on"], 4),
                   restore_to_first_segment_s=round(restore_s, 4),
                   restored_offset=int(meta["offset"]))
        results.append(row)
        rows.append((f"stream/checkpointing/bnds={boundaries}/b=128",
                     round(1e6 * walls["on"] / (128 * len(stream)), 1),
                     f"wall_on={walls['on']:.3f}s;"
                     f"wall_off={walls['off']:.3f}s;"
                     f"tput_ratio={ratio:.2f};"
                     f"save_stall={stalls['on']:.3f}s;"
                     f"save_write={writes['on']:.3f}s;"
                     f"restore={restore_s:.3f}s"))
        assert ratio >= 0.9, (
            f"segment-boundary checkpointing costs more than 10% "
            f"throughput: on={walls['on']:.3f}s off={walls['off']:.3f}s "
            f"({ratio:.2f}x)")
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)


def _integrity_leg(results, rows, seed: int = 0):
    """Admission-validation and audit-interval overhead (DESIGN.md §11)
    on the housing ``pc=65536`` sparse stream and the degree-m cofactor
    stream.

    Three executors per dataset share the same segment structure
    (``segment_updates=4``, so the comparison isolates integrity work
    from segmentation): ``off`` — ``policy="permissive"``, no checks;
    ``validate`` — ``policy="quarantine"``, the jit row validator + one
    host sync per segment; ``audit`` — validation plus the audited
    Reevaluate every 2 segments on a ``store_base=True`` engine (the
    from-base recompute is the priced item; its engine also maintains
    base relations, which is part of the honest audit cost).  Engine
    state is container-snapshot-restored between passes so every pass
    replays the identical trajectory against warm compile caches.
    Acceptance gate: validation-on throughput ≥ 0.9× off."""
    import jax

    from repro.core import StreamExecutor
    from repro.runtime.integrity import IntegrityConfig

    ring = sum_ring()
    big = dict(HOUSING_DOMS_BIG)
    sq = Query(relations=HOUSING_RELATIONS, free_vars=(), ring=ring,
               domains=big, lifts={"h2": ("value",)})
    sdb, active = synth_low_fill_db(HOUSING_RELATIONS, big, ring,
                                    np.random.default_rng(seed), "pc",
                                    n_active=512)
    sstream = update_stream(HOUSING_RELATIONS, big, ring,
                            np.random.default_rng(seed + 1), 512, 12,
                            key_pools={"pc": active})
    cq = regression.cofactor_query(RETAILER_RELATIONS, RETAILER_DOMS)
    cdb = synth_db(RETAILER_RELATIONS, RETAILER_DOMS, cq.ring,
                   np.random.default_rng(seed))
    cstream = update_stream(RETAILER_RELATIONS, RETAILER_DOMS, cq.ring,
                            np.random.default_rng(seed + 2), 64, 12)
    datasets = (("housing_sparse_pc65536", sq, sdb, housing_vo(), sstream),
                ("retailer_cofactor_degree_m", cq, cdb, retailer_vo(),
                 cstream))

    for dataset, q, db, vo, stream in datasets:
        n_tuples = sum(upd.batch for _, upd in stream)

        def fresh(**kw):
            return IVMEngine.build(q, db, var_order=vo, strategy="fivm",
                                   **kw)

        cfgs = {
            "off": IntegrityConfig(policy="permissive", segment_updates=4),
            "validate": IntegrityConfig(policy="quarantine",
                                        segment_updates=4),
            "audit": IntegrityConfig(policy="quarantine",
                                     audit_interval=2, segment_updates=4),
        }
        execs = {
            mode: StreamExecutor(fresh(store_base=(mode == "audit")),
                                 integrity=cfg)
            for mode, cfg in cfgs.items()
        }

        def one_pass(mode):
            ex = execs[mode]
            eng = ex.engine
            saved = (dict(eng.views), dict(eng.base), dict(eng.indicators))
            t0 = time.perf_counter()
            state = ex.run(stream, pipeline=True)
            jax.block_until_ready(state)
            wall = time.perf_counter() - t0
            eng.set_state(saved)
            audit_s = sum(s.get("audit_s", 0.0)
                          for s in ex.last_segment_stats)
            admit_s = sum(s.get("admit_s", 0.0)
                          for s in ex.last_segment_stats)
            return wall, admit_s, audit_s

        for mode in execs:
            one_pass(mode)  # warm: compile segment programs + validator
        walls = {m: float("inf") for m in execs}
        admits, audits = {}, {}
        for _ in range(5):  # interleaved best-of-5 (see pipeline leg)
            for mode in execs:
                wall, admit_s, audit_s = one_pass(mode)
                if wall < walls[mode]:
                    walls[mode] = wall
                    admits[mode] = admit_s
                    audits[mode] = audit_s
        n_audits = sum(1 for s in execs["audit"].last_segment_stats
                       if s["audit_s"] > 0)
        v_ratio = walls["off"] / walls["validate"]
        a_ratio = walls["off"] / walls["audit"]
        row = dict(dataset=dataset, strategy="fivm",
                   batch=stream[0][1].batch, n_batches=len(stream),
                   leg="integrity",
                   wall_validation_off_s=round(walls["off"], 4),
                   wall_validation_on_s=round(walls["validate"], 4),
                   wall_audit_on_s=round(walls["audit"], 4),
                   validation_on_over_off_throughput=round(v_ratio, 3),
                   audit_on_over_off_throughput=round(a_ratio, 3),
                   admit_s_validation_on=round(admits["validate"], 4),
                   audit_s_total=round(audits["audit"], 4),
                   n_audits=n_audits,
                   dead_letters=len(cfgs["validate"].dead_letters))
        results.append(row)
        rows.append((
            f"stream/integrity/{dataset}/b={stream[0][1].batch}",
            round(1e6 * walls["validate"] / n_tuples, 1),
            f"wall_off={walls['off']:.3f}s;"
            f"wall_validate={walls['validate']:.3f}s;"
            f"wall_audit={walls['audit']:.3f}s;"
            f"validate_tput_ratio={v_ratio:.2f};"
            f"audit_tput_ratio={a_ratio:.2f};"
            f"audit_s={audits['audit']:.3f}s;n_audits={n_audits}"))
        assert v_ratio >= 0.9, (
            f"{dataset}: admission validation costs more than 10% "
            f"throughput: on={walls['validate']:.3f}s "
            f"off={walls['off']:.3f}s ({v_ratio:.2f}x)")
        assert len(cfgs["validate"].dead_letters) == 0  # clean stream


def _copy_bandwidth_bytes_per_s() -> float:
    """Measured streaming bandwidth of this host (one big f32 add: read +
    write) — the denominator of the fusion leg's roofline model."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((64, 1 << 20), jnp.float32)  # 256 MB
    f = jax.jit(lambda a: a + 1.0)
    jax.block_until_ready(f(x))
    t0 = time.perf_counter()
    reps = 3
    for _ in range(reps):
        out = f(x)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    return 2 * x.size * 4 / dt


def _plan_traffic_bytes(eng, stream) -> int:
    """Minimal memory traffic of replaying ``stream`` through ``eng``'s
    trigger plans: every delta hop reads its [B, d] plane once, every
    gather reads B rows of its source, every ⊎ read-modify-writes B rows.
    The roofline floor a perfectly fused trigger cannot beat."""
    from repro.core import plan as plan_mod
    from repro.core.storage import payload_width

    w = payload_width(eng.query.ring) * 4
    total = 0
    for rel, upd in stream:
        plan = eng.trigger_plan(rel, upd)
        b = upd.batch
        for op in plan_mod.iter_flat_ops(plan.ops + plan.ind_ops):
            if isinstance(op, (plan_mod.Gather, plan_mod.LeafDelta,
                               plan_mod.Lift, plan_mod.JoinContract)):
                total += b * w
            elif isinstance(op, plan_mod.ScatterAccum):
                total += 3 * b * w  # gather-add-scatter of touched rows
    return total


def _fusion_leg(results, rows, seed: int = 0, repeats: int = 5):
    """Plan-level fusion on vs off (DESIGN.md §13) on the housing
    ``pc=65536`` sparse stream and the degree-m cofactor stream: same
    prepared streams, fused plans replace each Gather→Lift→…→ScatterAccum
    chain with one megakernel dispatch.  Reports the on/off throughput
    ratio (gate: fused must not lose to unfused) and the roofline
    fraction — minimal-traffic time over measured wall — per stream."""
    from repro.core import plan as plan_mod

    ring = sum_ring()
    big = dict(HOUSING_DOMS_BIG)
    sq = Query(relations=HOUSING_RELATIONS, free_vars=(), ring=ring,
               domains=big, lifts={"h2": ("value",)})
    sdb, active = synth_low_fill_db(HOUSING_RELATIONS, big, ring,
                                    np.random.default_rng(seed), "pc",
                                    n_active=512)
    sstream = update_stream(HOUSING_RELATIONS, big, ring,
                            np.random.default_rng(seed + 1), 64, 20,
                            key_pools={"pc": active})
    cq = regression.cofactor_query(RETAILER_RELATIONS, RETAILER_DOMS)
    cdb = synth_db(RETAILER_RELATIONS, RETAILER_DOMS, cq.ring,
                   np.random.default_rng(seed))
    cstream = update_stream(RETAILER_RELATIONS, RETAILER_DOMS, cq.ring,
                            np.random.default_rng(seed + 2), 256, 10)
    # (name, query, db, var order, stream, hard gate, target ratio) — the
    # hard gate is parity (the flat-XLA lowering must not lose to op-by-op
    # replay); the target is what the VMEM-resident megakernel aims for on
    # TPU, reported alongside so the gap is visible per run.
    datasets = (("housing_sparse_pc65536", sq, sdb, housing_vo(), sstream,
                 1.0, 1.0),
                ("retailer_cofactor_degree_m", cq, cdb, retailer_vo(),
                 cstream, 1.0, 1.5))
    bw = _copy_bandwidth_bytes_per_s()

    for dataset, q, db, vo, stream, min_ratio, target in datasets:
        import jax

        from repro.core import StreamExecutor, prepare_stream

        n_tuples = sum(u.batch for _, u in stream)
        # build + warm both modes first, then interleave the timed passes
        # (off, on, off, on, …): host-load drift hits both modes alike
        # instead of systematically penalizing whichever runs second
        runs = {}
        for mode in ("off", "on"):
            with plan_mod.use_fusion(mode):
                eng = IVMEngine.build(q, db, var_order=vo, strategy="fivm")
                ex = StreamExecutor(eng)
                prepared = prepare_stream(eng, stream)
                state = ex.run(prepared, update_engine=False)
                jax.block_until_ready(jax.tree.leaves(state)[0])
                runs[mode] = dict(
                    eng=eng, ex=ex, prepared=prepared, state=state,
                    best=float("inf"),
                    chains=sum(isinstance(op, plan_mod.FusedChain)
                               for p in eng.plans.plans.values()
                               for op in p.ops),
                    traffic=_plan_traffic_bytes(eng, stream))
        for _ in range(repeats):
            for mode in ("off", "on"):
                r = runs[mode]
                with plan_mod.use_fusion(mode):
                    t0 = time.perf_counter()
                    r["state"] = r["ex"].run(
                        r["prepared"], state=r["state"],
                        update_engine=False, donate_input=True)
                    jax.block_until_ready(jax.tree.leaves(r["state"])[0])
                    r["best"] = min(r["best"],
                                    time.perf_counter() - t0)
        leg = {}
        for mode, r in runs.items():
            r["eng"].set_state(r["state"])
            res = r["eng"].result()
            res = res.to_dense() if hasattr(res, "to_dense") else res
            leg[mode] = dict(
                tps=n_tuples / r["best"], wall=r["best"],
                chains=r["chains"],
                roofline_frac=(r["traffic"] / bw) / r["best"],
                result={c: np.asarray(v)
                        for c, v in res.payload.items()})
        assert leg["on"]["chains"] > 0, f"{dataset}: nothing fused"
        assert leg["off"]["chains"] == 0
        ref, got = leg["off"]["result"], leg["on"]["result"]
        max_rel = float(max(
            np.abs(ref[c] - got[c]).max()
            / max(float(np.abs(ref[c]).max()), 1e-30) for c in ref))
        assert max_rel <= 1e-6, f"{dataset}: fused diverged ({max_rel})"
        ratio = leg["on"]["tps"] / leg["off"]["tps"]
        results.append(dict(
            dataset=dataset, strategy="fivm", batch=stream[0][1].batch,
            n_batches=len(stream), leg="fusion",
            fusion_on_tuples_per_s=round(leg["on"]["tps"]),
            fusion_off_tuples_per_s=round(leg["off"]["tps"]),
            fusion_on_over_off=round(ratio, 3),
            target_on_over_off=target,
            fused_chains=leg["on"]["chains"],
            roofline_frac_on=round(leg["on"]["roofline_frac"], 4),
            roofline_frac_off=round(leg["off"]["roofline_frac"], 4),
            max_rel_diff=max_rel))
        rows.append((
            f"stream/fusion/{dataset}/b={stream[0][1].batch}",
            round(1e6 * n_tuples / len(stream) / leg["on"]["tps"], 1),
            f"fusion_on_tps={leg['on']['tps']:.0f};"
            f"fusion_off_tps={leg['off']['tps']:.0f};"
            f"on_over_off={ratio:.2f}x;"
            f"target={target:.1f}x;"
            f"chains={leg['on']['chains']};"
            f"roofline_frac_on={leg['on']['roofline_frac']:.4f};"
            f"roofline_frac_off={leg['off']['roofline_frac']:.4f}"))
        assert ratio >= min_ratio * 0.95, (
            f"{dataset}: fused plans lose to unfused: {ratio:.2f}x "
            f"(gate {min_ratio}x, 5% noise allowance)")


def run(batches=(16, 64, 256), n_batches: int = 30, seed: int = 0,
        strategies=("fivm", "fivm_1", "dbt", "reeval"), repeats: int = 5,
        json_path: str | None = JSON_PATH,
        kernel_backends=("jnp", "compact_xla"),
        baseline_min_ratio: float | None = None):
    """``baseline_min_ratio`` (or env ``REPRO_BENCH_BASELINE_MIN``) turns on
    the refactor guard: every fused-throughput row is compared against the
    previous BENCH_stream.json and must stay within the given fraction
    (e.g. 0.5 = within 2× noise) — the plan refactor must not regress the
    hot path."""
    if baseline_min_ratio is None and os.environ.get("REPRO_BENCH_BASELINE_MIN"):
        baseline_min_ratio = float(os.environ["REPRO_BENCH_BASELINE_MIN"])
    baseline = _load_baseline(json_path)
    baseline_ratios = []
    rng = np.random.default_rng(seed)
    ring = sum_ring()
    rows, results = [], []

    def record(dataset, strategy, batch, n_b, backend, tps_fused, tps_percall,
               plan_stats=None):
        speedup = tps_fused / tps_percall
        derived = (f"fused_tps={tps_fused:.0f};percall_tps={tps_percall:.0f};"
                   f"speedup={speedup:.2f}x")
        row = dict(
            dataset=dataset, strategy=strategy, batch=batch, n_batches=n_b,
            scatter_backend=backend or "auto",
            fused_tuples_per_s=round(tps_fused),
            percall_tuples_per_s=round(tps_percall),
            speedup=round(speedup, 2))
        if plan_stats is not None:
            row.update(
                plan_compile_ms_total=plan_stats["compile_ms_total"],
                plan_compile_ms_per_plan=plan_stats["compile_ms_per_plan"],
                plan_cache_hit_rate=plan_stats["hit_rate"],
                plan_verify_ms=plan_stats["verify_ms_total"],
                plans_compiled=plan_stats["plans"])
            derived += (f";plan_compile_ms={plan_stats['compile_ms_total']};"
                        f"plan_hit_rate={plan_stats['hit_rate']}")
        prev = baseline.get((dataset, strategy, batch, backend or "auto"))
        if prev:
            ratio = tps_fused / prev
            baseline_ratios.append(
                ((dataset, strategy, batch, backend or "auto"), ratio))
            row["fused_vs_baseline"] = round(ratio, 3)
        rows.append((f"stream/{dataset}/{strategy}"
                     f"{'' if backend is None else '/' + backend}/b={batch}",
                     round(1e6 * batch / tps_fused, 1), derived))
        results.append(row)

    # -- retailer sum aggregate: strategy × batch (PR-1 trajectory rows) ----
    q = Query(relations=RETAILER_RELATIONS, free_vars=(), ring=ring,
              domains=RETAILER_DOMS, lifts={"units": ("value",)})
    db = synth_db(RETAILER_RELATIONS, RETAILER_DOMS, ring, rng)
    for strategy in strategies:
        for batch in batches:
            stream = update_stream(RETAILER_RELATIONS, RETAILER_DOMS, ring,
                                   rng, batch, n_batches)
            tps_f, tps_p, pstats = _measure(q, db, retailer_vo(), strategy,
                                            stream, repeats)
            record("retailer_sum_aggregate", strategy, batch, n_batches,
                   None, tps_f, tps_p, pstats)

    # -- housing star schema: wide pc dictionary, kernel-on vs kernel-off --
    hq = Query(relations=HOUSING_RELATIONS, free_vars=(), ring=ring,
               domains=HOUSING_DOMS, lifts={"h2": ("value",)})
    hdb = synth_db(HOUSING_RELATIONS, HOUSING_DOMS, ring, rng,
                   density=0.05)
    for backend in kernel_backends:
        for batch in batches:
            stream = update_stream(HOUSING_RELATIONS, HOUSING_DOMS, ring,
                                   rng, batch, n_batches)
            tps_f, tps_p, pstats = _measure(hq, hdb, housing_vo(), "fivm",
                                            stream, repeats, backend=backend)
            record("housing_sum_aggregate", "fivm", batch, n_batches,
                   backend, tps_f, tps_p, pstats)

    # -- housing pc=65536: dense vs sparse view storage (ISSUE 3) ----------
    big = dict(HOUSING_DOMS_BIG)
    sq = Query(relations=HOUSING_RELATIONS, free_vars=(), ring=ring,
               domains=big, lifts={"h2": ("value",)})
    sdb, active = synth_low_fill_db(HOUSING_RELATIONS, big, ring,
                                    np.random.default_rng(seed), "pc",
                                    n_active=512)
    fresh = np.setdiff1d(np.arange(big["pc"]), active)
    pool = np.concatenate([active, np.random.default_rng(seed).choice(
        fresh, size=256, replace=False)])
    sparse_stream = update_stream(
        HOUSING_RELATIONS, big, ring, np.random.default_rng(seed + 1),
        64, 30, key_pools={"pc": pool})
    leg = {}
    for mode in ("dense", "auto"):
        eng = IVMEngine.build(sq, sdb, var_order=housing_vo(),
                              strategy="fivm", storage=mode)
        kinds = sorted(s.kind for s in eng.storage_plan.values())
        tps, _ = run_engine_stream(eng, sparse_stream, fused=True,
                                   repeats=repeats)
        leg[mode] = dict(tps=tps, bytes=eng.memory_bytes(),
                         result=np.asarray(eng.result().payload["v"]),
                         n_sparse=kinds.count("sparse"),
                         pstats=eng.plans.stats())
    bit_identical = bool(np.array_equal(leg["dense"]["result"],
                                        leg["auto"]["result"]))
    mem_ratio = leg["dense"]["bytes"] / leg["auto"]["bytes"]
    fill = 512 / big["pc"]
    for mode, label in (("dense", "dense"), ("auto", "sparse")):
        e = leg[mode]
        rows.append((f"stream/housing_sparse_pc65536/{label}/b=64",
                     round(1e6 * 64 / e["tps"], 1),
                     f"fused_tps={e['tps']:.0f};view_bytes={e['bytes']};"
                     f"mem_ratio={mem_ratio:.1f}x;"
                     f"bit_identical={bit_identical}"))
        results.append(dict(
            dataset="housing_sparse_pc65536", strategy="fivm", batch=64,
            n_batches=30, storage=label, fill=round(fill, 4),
            sparse_views=e["n_sparse"],
            fused_tuples_per_s=round(e["tps"]),
            peak_view_bytes=int(e["bytes"]),
            dense_over_sparse_mem=round(mem_ratio, 2),
            bit_identical_to_dense=bit_identical,
            plan_compile_ms_total=e["pstats"]["compile_ms_total"],
            plan_compile_ms_per_plan=e["pstats"]["compile_ms_per_plan"],
            plan_cache_hit_rate=e["pstats"]["hit_rate"],
            plan_verify_ms=e["pstats"]["verify_ms_total"]))
    assert bit_identical, "sparse housing run diverged from dense"
    assert mem_ratio >= 10, f"sparse memory win below 10x: {mem_ratio:.1f}"

    # -- degree-m cofactor ring: wide payloads through the scatter shim ----
    cq = regression.cofactor_query(RETAILER_RELATIONS, RETAILER_DOMS)
    cdb = synth_db(RETAILER_RELATIONS, RETAILER_DOMS, cq.ring, rng)
    for backend in kernel_backends:
        for batch in batches[:2]:
            stream = update_stream(RETAILER_RELATIONS, RETAILER_DOMS, cq.ring,
                                   rng, batch, 10)
            tps_f, tps_p, pstats = _measure(cq, cdb, retailer_vo(), "fivm",
                                            stream, max(2, repeats - 3),
                                            backend=backend)
            record("retailer_cofactor_degree_m", "fivm", batch, 10,
                   backend, tps_f, tps_p, pstats)

    # -- sharded scan carry: per-device-count subprocess sweep (CPU) -------
    import jax

    if jax.default_backend() != "cpu":
        print("# sharded sweep refused: its children force CPU devices; "
              "run `python chip_smoke.py --chips 4` on chips")
    elif os.environ.get("REPRO_BENCH_SKIP_SHARDED") != "1":
        _sharded_sweep(results, rows, DEVICE_COUNTS, seed=seed)

    # -- segmented stream pipeline: two-deep admit/run overlap -------------
    _segmented_pipeline_leg(results, rows, seed=seed)

    # -- segment-boundary checkpointing: durability cost + restore latency --
    _checkpointing_leg(results, rows, seed=seed)

    # -- integrity: admission-validation + audit-interval overhead ---------
    _integrity_leg(results, rows, seed=seed)

    # -- plan-level fusion: megakernel chains on vs op-by-op replay --------
    _fusion_leg(results, rows, seed=seed)

    # refactor guard: fused throughput vs the previous BENCH_stream.json
    if baseline_ratios:
        ratios = [r for _, r in baseline_ratios]
        med = sorted(ratios)[len(ratios) // 2]
        worst_key, worst = min(baseline_ratios, key=lambda kv: kv[1])
        print(f"# fused vs baseline: median {med:.2f}x, "
              f"worst {worst:.2f}x at {worst_key}")
        if baseline_min_ratio is not None:
            assert worst >= baseline_min_ratio, (
                f"fused throughput regressed below {baseline_min_ratio}x of "
                f"the previous BENCH_stream.json: {worst:.2f}x at "
                f"{worst_key}")

    if json_path is not None:
        with open(json_path, "w") as f:
            json.dump({"benchmark": "fused_stream_executor",
                       "results": results}, f, indent=2)
        print(f"# wrote {os.path.abspath(json_path)}")
    return emit(rows, ("name", "us_per_call", "derived"))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--sharded-child":
        child_rows = _sharded_child(seed=int(sys.argv[2])
                                    if len(sys.argv) > 2 else 0)
        print(_CHILD_MARKER + json.dumps(child_rows))
    else:
        run()
