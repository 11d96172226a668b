"""Bring-up run of the update-stream -> view-serving path on a TPU.

Drives the engine through the entry points a user calls --
``IVMEngine.build`` -> ``StreamExecutor.run`` (which prepares, compiles
and runs the fused stream) -> ``ViewServer`` reads of the published
generation -- and checks every answer against a numpy float64 reference
recomputed from the final base relations, with no engine code involved.

Two deployments, generated from ``--seed`` on the device:

* **housing** -- the F-IVM paper's star schema: six relations joined on
  the postcode ``pc`` (``benchmarks.common.HOUSING_RELATIONS``), here with
  2^22 postcodes instead of 4,096 and ``pc`` as each relation's last axis,
  SUM(h2) over the join on the scalar ring.  Base relations stay on the
  device (``store_base=True``), so base and view state exceed 1 GiB.
* **retailer** -- the paper's snowflake (``RETAILER_RELATIONS``) under the
  degree-m cofactor ring (m = 10): the in-database regression workload.

Each takes a round-robin stream of 1,024-tuple batches; from the second
round on, a quarter of each batch deletes tuples the relation's previous
batch inserted.  1,024 is above every kernel's block size, so the plans
resolve to the Pallas kernels, and the script checks that they did.

Usage, from the repository root on a TPU host::

    python chip_smoke.py             # one chip: both deployments
    python chip_smoke.py --chips 4   # four chips: sharded views only

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import (HOUSING_RELATIONS, RETAILER_RELATIONS,  # noqa: E402
                               housing_vo, retailer_vo, use_compile_cache)
from repro.core import (COOUpdate, DenseRelation, IVMEngine, Query,  # noqa: E402
                        StreamExecutor, plan_shards, sum_ring)
from repro.core import plan as plan_mod  # noqa: E402
from repro.core.apps import regression  # noqa: E402
from repro.core.storage import comp_width, payload_width  # noqa: E402
from repro.kernels import ring_fused, scatter_ops  # noqa: E402
from repro.serve import ViewServer  # noqa: E402

#: tuples per update batch (the F-IVM paper's batch size)
BATCH = 1024
#: rounds of one batch per relation
N_ROUNDS = 4
#: backends the 1,024-tuple plans must resolve to on the chip
PALLAS_BACKENDS = frozenset({"onehot", "onehot_dedup", "compact",
                             "fused_pallas", "fused_compact"})
#: engine state (views + stored base) the housing deployment must hold
MIN_STATE_BYTES = 1 << 30
#: f32 bound on the housing root: a sum of 2^22 per-postcode products,
#: each exact in f32, whose total exceeds 2^24 and so rounds
HOUSING_ROOT_RTOL = 1e-5
#: f32 bound on the cofactor triple: sums over ~10^7 join tuples
#: weighted by values up to 63^2, accumulated over several contractions
#: (a single bf16 pass per contraction misses it by 50x)
RETAILER_ROOT_RTOL = 1e-5
#: sharded vs single placement for values past f32's exact integers
#: (the degree-m bound of the sharded-views benchmark)
SHARDED_RTOL = 1e-6

HOUSING_SCALE = dict(pc=1 << 22, h1=8, h2=8, s1=8, i1=8, r1=8, d1=8, t1=8)
#: the housing relations with the postcode as their minor (last) axis.
#: XLA:TPU tiles an array's two minor axes by (8, 128): with an 8-value
#: attribute minor, the stream program's copies of the 2^22-postcode base
#: relations pad 16x (26 GiB for House alone, over the chip's 16 GB)
HOUSING_PC_MINOR = {r: sch[1:] + sch[:1]
                    for r, sch in HOUSING_RELATIONS.items()}
RETAILER_SCALE = dict(locn=32, dateid=32, ksn=64, units=8, cat=8, price=8,
                      temp=8, zip=16, rgn=4, pop=8)


@dataclasses.dataclass(frozen=True)
class Deployment:
    name: str
    relations: dict
    domains: dict
    density: float  # share of the domain product present as tuples
    build_kwargs: dict
    segment_updates: int  # stream updates between published generations

    def query(self) -> Query:
        if self.name == "housing":
            return Query(relations=self.relations, free_vars=(),
                         ring=sum_ring(), domains=self.domains,
                         lifts={"h2": ("value",)})
        return regression.cofactor_query(self.relations, self.domains)

    def var_order(self):
        return housing_vo() if self.name == "housing" else retailer_vo()


HOUSING = Deployment("housing", HOUSING_PC_MINOR, HOUSING_SCALE, 0.3,
                     dict(store_base=True), 12)
RETAILER = Deployment("retailer", RETAILER_RELATIONS, RETAILER_SCALE, 0.05,
                      {}, 10)


# ---------------------------------------------------------------------------
# data, made from the seed
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(1, 2))
def _bernoulli(key, p: float, shape: tuple):
    return jax.random.bernoulli(key, p, shape).astype(jnp.float32)


def device_database(dep: Deployment, ring, seed: int) -> dict:
    """Dense 0/1 multiplicity tensors, drawn on the device."""
    key = jax.random.PRNGKey(seed)
    db = {}
    for i, (name, sch) in enumerate(dep.relations.items()):
        shape = tuple(dep.domains[v] for v in sch)
        mult = _bernoulli(jax.random.fold_in(key, i), dep.density, shape)
        if set(ring.components) == {"v"}:
            db[name] = DenseRelation(tuple(sch), ring, {"v": mult})
        else:
            db[name] = regression.relation_from_multiplicities(
                tuple(sch), ring, mult)
    return db


def update_stream(dep: Deployment, ring, seed: int):
    """Round-robin ``BATCH``-tuple batches.  Returns the engine stream
    ``[(rel, COOUpdate)]`` and its host copy ``[(rel, keys, mult)]``."""
    rng = np.random.default_rng(seed)
    n_del = BATCH // 4
    inserted: dict = {}
    stream, host = [], []
    for _ in range(N_ROUNDS):
        for name, sch in dep.relations.items():
            keys = np.stack([rng.integers(0, dep.domains[v], BATCH)
                             for v in sch], axis=1).astype(np.int32)
            mult = np.ones(BATCH, np.float32)
            if name in inserted:
                keys[:n_del] = inserted[name][:n_del]
                mult[:n_del] = -1.0
            inserted[name] = keys[mult > 0]
            if set(ring.components) == {"v"}:
                payload = {"v": jnp.asarray(mult)}
            else:
                payload = {**ring.zeros((BATCH,)), "c": jnp.asarray(mult)}
            stream.append((name, COOUpdate(tuple(sch), jnp.asarray(keys),
                                           payload)))
            host.append((name, keys, mult))
    return stream, host


def final_base(db: dict, host_stream) -> dict:
    """Host float64 multiplicities after the stream: initial base plus
    every update, applied with ``np.add.at``."""
    comp = "v" if "v" in next(iter(db.values())).payload else "c"
    base = {r: np.asarray(jax.device_get(rel.payload[comp]), np.float64)
            for r, rel in db.items()}
    for rel, keys, mult in host_stream:
        np.add.at(base[rel], tuple(keys.T), mult)
    return base


# ---------------------------------------------------------------------------
# host references (numpy float64, no engine code)
# ---------------------------------------------------------------------------
def housing_reference(dep: Deployment, base: dict):
    """Per-postcode leaf aggregates keyed by each relation's first
    attribute, and the root Σ_pc Π_r leaf_r[pc]."""
    leaves = {}
    for rel, sch in dep.relations.items():
        attrs = [v for v in sch if v != "pc"]
        weights = [np.arange(dep.domains[v], dtype=np.float64) if v == "h2"
                   else np.ones(dep.domains[v]) for v in attrs]
        letters = "".join("p" if v == "pc" else chr(ord("a") + i)
                          for i, v in enumerate(sch))
        spec = ",".join([letters] + [c for c in letters if c != "p"])
        leaves[attrs[0]] = np.einsum(spec + "->p", base[rel], *weights,
                                     optimize=True)
    root = np.prod(np.stack(list(leaves.values())), axis=0).sum()
    return leaves, root


def cofactor_reference(dep: Deployment, base: dict):
    """(c, s, Q) over the join: M = Σ_t mult_t u_t u_tᵀ with u_t =
    (1, x_1..x_m), one einsum per entry."""
    vars_ = []
    for sch in dep.relations.values():
        vars_ += [v for v in sch if v not in vars_]
    letter = {v: chr(ord("a") + i) for i, v in enumerate(vars_)}
    specs = ["".join(letter[v] for v in sch)
             for sch in dep.relations.values()]
    rels = [base[r] for r in dep.relations]
    m = len(vars_)
    M = np.zeros((m + 1, m + 1))
    for a in range(m + 1):
        for b in range(a, m + 1):
            sub, ops = list(specs), list(rels)
            for i in (a, b):
                if i:
                    v = vars_[i - 1]
                    sub.append(letter[v])
                    ops.append(np.arange(dep.domains[v], dtype=np.float64))
            M[a, b] = M[b, a] = np.einsum(",".join(sub) + "->", *ops,
                                          optimize="greedy")
    return {"c": M[0, 0], "s": M[0, 1:], "Q": M[1:, 1:]}


def rel_err(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1.0))


# ---------------------------------------------------------------------------
# instrumentation
# ---------------------------------------------------------------------------
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_seconds = [0.0]


def _on_event(event: str, duration: float, **_):
    if event in _COMPILE_EVENTS:
        _compile_seconds[0] += duration


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def resolved_backends(engine) -> set:
    """Kernel backends of the engine's ``BATCH``-tuple trigger plans: each
    ScatterAccum's scatter backend, each FusedChain's lowering, and the
    base-relation ⊎ (resolved when ``DenseRelation.scatter_add`` traces)."""
    out = set()
    for plan in engine.plans.plans.values():
        if plan.batch != BATCH:
            continue
        for op in plan.ops + plan.ind_ops:
            if isinstance(op, plan_mod.FusedChain):
                out.add(ring_fused.resolve_backend(op.ops[-1].backend))
            elif isinstance(op, plan_mod.ScatterAccum) and op.backend:
                out.add(op.backend)
    d = payload_width(engine.query.ring)
    for rel in engine.base.values():
        out.add(scatter_ops.resolve_backend(comp_width(rel.domains),
                                            BATCH, d))
    return out


def check(ok, what) -> None:
    """Fail the run (``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def log(dep: Deployment, msg: str) -> None:
    print(f"[{dep.name}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# one chip: build -> stream -> serve -> check
# ---------------------------------------------------------------------------
def build(dep: Deployment, seed: int):
    q = dep.query()
    db = device_database(dep, q.ring, seed)
    stream, host = update_stream(dep, q.ring, seed + 1)
    engine = IVMEngine.build(q, db, var_order=dep.var_order(),
                             strategy="fivm", **dep.build_kwargs)
    return q, db, stream, host, engine


def served_phase(dep: Deployment, seed: int) -> None:
    t0 = time.perf_counter()
    c0 = _compile_seconds[0]
    q, db, stream, host, engine = build(dep, seed)
    jax.block_until_ready(engine.state)
    build_s = time.perf_counter() - t0
    shapes = {r: tuple(dep.domains[v] for v in sch)
              for r, sch in dep.relations.items()}
    log(dep, f"domains {dep.domains}")
    log(dep, f"relations {shapes}, ring components "
             f"{dict(q.ring.components)}, density {dep.density}")
    log(dep, f"views {sorted(engine.views)}; stream {len(stream)} batches "
             f"x {BATCH} tuples, deletes from round 2")
    state_bytes = nbytes(engine.state)
    log(dep, f"device bytes: views {nbytes(engine.views)}, stored base "
             f"{nbytes(engine.base)}, engine state {state_bytes}, input "
             f"database {nbytes(db)}")
    log(dep, "view storage " + str(sorted(
        {s.kind for s in engine.storage_plan.values()})))
    if dep.name == "housing":
        check(state_bytes >= MIN_STATE_BYTES, state_bytes)

    t1 = time.perf_counter()
    executor = StreamExecutor(engine)
    server = ViewServer(executor, segment_updates=dep.segment_updates)
    executor.run(stream)
    jax.block_until_ready(engine.state)
    stream_s = time.perf_counter() - t1
    backends = resolved_backends(engine)
    log(dep, f"resolved backends at B={BATCH}: {sorted(backends)}")
    check(backends and backends <= PALLAS_BACKENDS, backends)
    check(any(b.startswith("fused") for b in backends), backends)

    n_tuples = sum(u.batch for _, u in stream)
    with server.pin() as snap:
        check(snap.offset == len(stream), (snap.offset, len(stream)))
        if dep.name == "housing":
            check_housing(dep, engine, snap, db, host, seed)
        else:
            check_retailer(dep, engine, snap, db, host)
    stats = server.stats()
    log(dep, f"published generations {stats['publishes']}, segments "
             f"{len(stats['last_segment_stats'])}")
    log(dep, f"compile_s {_compile_seconds[0] - c0:.3f} build_s "
             f"{build_s:.3f} stream_s {stream_s:.3f} ({n_tuples} tuples) "
             f"wall_s {time.perf_counter() - t0:.3f}")
    log(dep, f"peak_bytes_in_use {peak_bytes()}")


def check_housing(dep, engine, snap, db, host, seed: int) -> None:
    base = final_base(db, host)
    leaves, root = housing_reference(dep, base)
    for rel, ref in base.items():
        got = np.asarray(jax.device_get(engine.base[rel].payload["v"]))
        check(np.array_equal(got, ref), f"stored base {rel} diverged")
    by_var = {}
    for name, view in engine.views.items():
        if view.schema == ("pc",):
            var = name.split("@")[1]
            got = np.asarray(jax.device_get(view.payload["v"]))
            check(np.array_equal(got, leaves[var]), f"view {name} diverged")
            by_var[var] = name
    check(len(by_var) == len(leaves), sorted(engine.views))
    got_root = float(jax.device_get(
        engine.views[engine.tree.name].payload["v"]))
    err = rel_err(got_root, root)
    check(err <= HOUSING_ROOT_RTOL, (got_root, root, err))
    log(dep, f"check vs numpy reference: stored base exact, {len(by_var)} "
             f"pc-keyed views exact, root {got_root:.9e} vs {root:.9e} "
             f"rel_err {err:.2e} <= {HOUSING_ROOT_RTOL}")

    # served reads of the pinned (final) generation
    rng = np.random.default_rng(seed + 2)
    house, shop = by_var["h1"], by_var["s1"]
    keys = rng.integers(0, dep.domains["pc"], 256)
    got = snap.point(house, keys[:, None]).host()["v"]
    check(np.array_equal(got, leaves["h1"][keys]), "point read diverged")
    lo, hi = sorted(int(x) for x in rng.integers(0, dep.domains["pc"], 2))
    got = float(snap.range_sum(shop, lo, hi).host()["v"])
    check(got == leaves["s1"][lo:hi].sum(), (got, lo, hi))
    top = snap.top_k(house, 16).host()
    ref_top = np.sort(leaves["h1"])[::-1][:16]
    check(np.array_equal(top["values"], ref_top), (top, ref_top))
    check(np.array_equal(leaves["h1"][top["keys"][:, 0]], top["values"]),
          "top_k keys do not hold their values")
    log(dep, f"served reads match the reference: point x256 on {house}, "
             f"range_sum [{lo},{hi}) on {shop}, top_16 on {house}")


def check_retailer(dep, engine, snap, db, host) -> None:
    ref = cofactor_reference(dep, final_base(db, host))
    root = snap.range_sum(engine.tree.name, 0, 1).host()
    errs = {c: rel_err(root[c], ref[c]) for c in ("c", "s", "Q")}
    check(max(errs.values()) <= RETAILER_ROOT_RTOL, errs)
    log(dep, f"check vs numpy reference: root (c, s, Q) via served "
             f"range_sum, c {float(root['c']):.9e} vs {ref['c']:.9e}, "
             f"rel_err {errs} <= {RETAILER_ROOT_RTOL}")
    keyed = sorted(n for n, v in engine.views.items() if len(v.schema) == 1)
    top = snap.top_k(keyed[0], 8, component="c").host()
    check(np.all(np.diff(top["values"]) <= 0), top)
    got = snap.point(keyed[0], top["keys"]).host()["c"]
    check(np.array_equal(got, top["values"]), (got, top))
    log(dep, f"served reads agree: top_8 by c on {keyed[0]} == point reads "
             f"of its keys")


# ---------------------------------------------------------------------------
# four chips: plan-sharded views vs single placement
# ---------------------------------------------------------------------------
def sharded_phase(dep: Deployment, seed: int) -> None:
    t0 = time.perf_counter()
    n = len(jax.devices())
    q, db, stream, _, single = build(dep, seed)
    StreamExecutor(single).run(stream)
    ref = {name: jax.device_get(v.payload) for name, v in single.views.items()}
    del single
    engine = IVMEngine.build(q, db, var_order=dep.var_order(),
                             strategy="fivm", **dep.build_kwargs)
    plan = plan_shards(engine)
    engine.shard_state(plan)
    sharded = plan.sharded_views()
    check(sharded, plan.pretty())
    log(dep, f"{n} devices, sharded views {list(sharded)}")

    def check_placement(when: str):
        for name in sharded:
            for leaf in jax.tree.leaves(engine.views[name].payload):
                shards = leaf.addressable_shards
                check(len({s.device for s in shards}) == n, (name, when))
                check(shards[0].data.shape[0] * n == leaf.shape[0],
                      (name, when, shards[0].data.shape, leaf.shape))
        log(dep, f"placement {when}: each sharded view split 1/{n} per "
                 "device")

    check_placement("before the stream")
    StreamExecutor(engine, shard=plan).run(stream)
    jax.block_until_ready(engine.state)
    check_placement("after the stream")
    exact, worst = 0, 0.0
    for name, view in engine.views.items():
        got = jax.device_get(view.payload)
        for c, r in ref[name].items():
            if np.abs(r).max() < 2 ** 24:  # f32-exact integers
                check(np.array_equal(got[c], r), (name, c))
                exact += 1
            else:
                err = rel_err(got[c], r)
                check(err <= SHARDED_RTOL, (name, c, err))
                worst = max(worst, err)
    log(dep, f"sharded == single placement: {exact} payload planes exact, "
             f"the rest max rel_err {worst:.2e} <= {SHARDED_RTOL}; wall_s "
             f"{time.perf_counter() - t0:.3f}")


# ---------------------------------------------------------------------------
def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-views phase on 4 chips")
    args = ap.parse_args(argv)
    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {dev.platform}); "
                         "this run needs the chip")
    if args.chips == 4 and len(jax.devices()) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found "
                         f"{len(jax.devices())}")
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    print(f"device {dev.device_kind} x{len(jax.devices())}, compile cache "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)
    for dep in (HOUSING, RETAILER):
        if args.chips == 4:
            sharded_phase(dep, args.seed)
        else:
            served_phase(dep, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
