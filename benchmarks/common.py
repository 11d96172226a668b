"""Shared benchmark utilities: schemas modeled on the paper's datasets,
timing, and CSV emission.

Absolute numbers on this 1-core CPU container are not comparable to the
paper's Azure DS14; the *relative* gaps between strategies are the
reproduction target (EXPERIMENTS.md cites both).
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DenseRelation, Query, chain, sum_ring

#: in-checkout persistent compile cache, used when JAX_COMPILATION_CACHE_DIR
#: is unset (the path is part of the cache key, so it never moves)
COMPILE_CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache for an entry-point script.
    JAX itself reads ``JAX_COMPILATION_CACHE_DIR`` when it is set;
    otherwise the cache lives at the fixed :data:`COMPILE_CACHE_DIR`.
    Returns the directory in use.  The engine library never touches this
    setting."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


# ---------------------------------------------------------------------------
# Retailer-like snowflake (scaled-down dictionary domains)
# ---------------------------------------------------------------------------
RETAILER_RELATIONS = {
    "Inventory": ("locn", "dateid", "ksn", "units"),
    "Item": ("ksn", "cat", "price"),
    "Weather": ("locn", "dateid", "temp"),
    "Location": ("locn", "zip", "rgn"),
    "Census": ("zip", "pop"),
}
RETAILER_DOMS = dict(locn=24, dateid=24, ksn=32, units=8, cat=6, price=8,
                     temp=8, zip=12, rgn=4, pop=8)
# larger dictionary domains for scalar-payload benches (reevaluation cost
# must reflect |D|, not dispatch overhead; degree-m benches keep the small
# domains since payloads carry m×m matrices per key)
RETAILER_DOMS_BIG = dict(locn=96, dateid=96, ksn=128, units=8, cat=6, price=8,
                         temp=8, zip=32, rgn=4, pop=8)
HOUSING_DOMS_BIG = dict(pc=65536, h1=8, h2=8, s1=8, i1=8, r1=8, d1=8, t1=8)


def retailer_vo():
    """Paper Sec. 8.1: join variables ordered locn { dateid { ksn }, zip };
    each relation's own variables hang below its lowest join variable."""
    from repro.core import chain
    return chain(
        ["locn", "dateid", "ksn"],
        {"locn": [["zip"]],
         "zip": [["rgn"], ["pop"]],
         "dateid": [["temp"]],
         "ksn": [["units"], ["cat", "price"]]},
    )


# ---------------------------------------------------------------------------
# Housing-like star schema (join on postcode)
# ---------------------------------------------------------------------------
HOUSING_RELATIONS = {
    "House": ("pc", "h1", "h2"),
    "Shop": ("pc", "s1"),
    "Institution": ("pc", "i1"),
    "Restaurant": ("pc", "r1"),
    "Demographics": ("pc", "d1"),
    "Transport": ("pc", "t1"),
}
HOUSING_DOMS = dict(pc=4096, h1=8, h2=8, s1=8, i1=8, r1=8, d1=8, t1=8)


def housing_vo():
    from repro.core import chain
    return chain(["pc"], {"pc": [["h1", "h2"], ["s1"], ["i1"], ["r1"],
                                 ["d1"], ["t1"]]})


# ---------------------------------------------------------------------------
# Database + update-stream synthesis
# ---------------------------------------------------------------------------
def synth_db(relations, doms, ring, rng, density=0.3, scale=1.0):
    db = {}
    for name, sch in relations.items():
        shape = tuple(doms[v] for v in sch)
        mult = (rng.random(size=shape) < density * scale).astype(np.float32)
        if set(ring.components) == {"v"}:
            db[name] = DenseRelation(tuple(sch), ring, {"v": jnp.asarray(mult)})
        else:  # degree-m ring: multiplicity in c
            payload = {**ring.ones(shape)}
            payload["c"] = jnp.asarray(mult)
            db[name] = DenseRelation(tuple(sch), ring, payload)
    return db


def update_stream(relations, doms, ring, rng, batch: int, n_batches: int,
                  key_pools=None):
    """Round-robin batched inserts/deletes over all relations (Sec. 8.1).

    ``key_pools`` optionally maps a variable to the array of values its
    update keys are drawn from — the sparse-view scenario keeps the wide
    ``pc`` dictionary's *active* key set small while updates still insert
    some fresh keys (capacity-headroom realism)."""
    from repro.core import COOUpdate

    names = list(relations)
    out = []
    for i in range(n_batches):
        rel = names[i % len(names)]
        sch = relations[rel]
        keys = np.stack(
            [rng.choice(key_pools[v], size=batch)
             if key_pools and v in key_pools
             else rng.integers(0, doms[v], size=batch) for v in sch],
            axis=1).astype(np.int32)
        vals = rng.choice([-1.0, 1.0, 1.0, 1.0], size=batch).astype(np.float32)
        if set(ring.components) == {"v"}:
            payload = {"v": jnp.asarray(vals)}
        else:
            payload = {**ring.zeros((batch,)), "c": jnp.asarray(vals)}
        out.append((rel, COOUpdate(tuple(sch), jnp.asarray(keys), payload)))
    return out


def synth_low_fill_db(relations, doms, ring, rng, wide_var: str,
                      n_active: int, rows_per_key: int = 8):
    """Database whose ``wide_var`` dictionary is mostly *inactive*: every
    relation's rows land on a shared pool of ``n_active`` values, so views
    keyed on ``wide_var`` have fill ``n_active / D`` — the housing
    ``pc = 65536`` sparse-view scenario.  Returns (db, active_values)."""
    from repro.core import make_base_relation

    active = np.sort(rng.choice(doms[wide_var], size=n_active, replace=False))
    db = {}
    for name, sch in relations.items():
        shape = tuple(doms[v] for v in sch)
        mult = np.zeros(shape, np.float32)
        n_rows = n_active * rows_per_key
        cols = [rng.choice(active, size=n_rows) if v == wide_var
                else rng.integers(0, doms[v], size=n_rows) for v in sch]
        np.add.at(mult, tuple(cols), 1.0)
        mult = np.minimum(mult, 1.0)  # 0/1 multiplicities
        db[name] = make_base_relation(tuple(sch), ring,
                                      {"v": jnp.asarray(mult)})
    return db, active


# ---------------------------------------------------------------------------
# Timing + reporting
# ---------------------------------------------------------------------------
def run_engine_stream(engine, stream, fused: bool = True, repeats: int = 3,
                      shard=None):
    """Apply a pre-built stream; returns (tuples/s, seconds).

    ``fused=True`` (default) compiles the whole stream into one XLA program
    via the stream executor (scan/switch dispatch, state donated through the
    scan carry).  ``fused=False`` dispatches one jitted trigger per batch
    from the host loop — kept as the measurement baseline and correctness
    oracle.  ``shard`` (a ``repro.core.shard.ShardPlan``) runs the fused
    program SPMD over the plan's mesh, state placed per the plan.  The
    stream is replayed ``repeats`` times and the best pass is reported
    (timed regions are short; best-of-N rejects scheduler noise).
    """
    if fused:
        return _run_fused(engine, stream, repeats, shard=shard)
    assert shard is None, "per-call dispatch is single-placement"
    return _run_percall(engine, stream, repeats)


def _run_fused(engine, stream, repeats: int, shard=None):
    from repro.core import StreamExecutor, prepare_stream

    if shard is not None:
        engine.shard_state(shard)
    ex = StreamExecutor(engine, shard=shard)
    prepared = prepare_stream(engine, stream)
    # warmup: compile + absorb any first-call constant folding
    state = ex.run(prepared, update_engine=False)
    jax.block_until_ready(jax.tree.leaves(state)[0])
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        # states after warmup are fresh (nothing else aliases them), so the
        # timed calls donate outright — no defensive copy in the timed region
        state = ex.run(prepared, state=state, update_engine=False,
                       donate_input=True)
        jax.block_until_ready(jax.tree.leaves(state)[0])
        best = min(best, time.perf_counter() - t0)
    engine.set_state(state)
    return prepared.n_tuples / best, best


def _run_percall(engine, stream, repeats: int):
    triggers = {}
    for rel, upd in stream:
        if rel not in triggers:
            triggers[rel] = engine.make_trigger(rel)
    # deep-copy: triggers donate their input state, and the engine's state
    # shares base-relation buffers with the caller's database
    state = jax.tree.map(lambda x: x.copy() if hasattr(x, "copy") else x,
                         engine.state)
    # warm per (relation, batch_size): heterogeneous batch sizes compile
    # distinct programs, and warming only the first-seen batch per relation
    # would retrace inside the timed loop
    seen = set()
    for rel, upd in stream:
        if (rel, upd.batch) in seen:
            continue
        state = triggers[rel](state, upd)
        seen.add((rel, upd.batch))
    jax.block_until_ready(jax.tree.leaves(state)[0])
    best = float("inf")
    n_tuples = sum(upd.batch for _, upd in stream)
    for _ in range(repeats):
        t0 = time.perf_counter()
        for rel, upd in stream:
            state = triggers[rel](state, upd)
        jax.block_until_ready(jax.tree.leaves(state)[0])
        best = min(best, time.perf_counter() - t0)
    engine.set_state(state)
    return n_tuples / best, best


def emit(rows, header):
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))
    return rows
