"""The scalar SUM ring: SUM over the join, with the configuration's lifts.

Builds the system's ``Query`` and its data.  Every relation's payload is
a 0/1 multiplicity tensor over its domain product, drawn on the device
in one jitted call; an update row carries its multiplicity (+1 insert,
-1 delete).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DenseRelation, Query, sum_ring


def query(cfg) -> Query:
    return Query(relations={r: tuple(s) for r, s in cfg["relations"].items()},
                 free_vars=(), ring=sum_ring(), domains=dict(cfg["domains"]),
                 lifts={v: (kind,) for v, kind in cfg["lifts"].items()})


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, density: float, shapes: tuple):
    return tuple(jax.random.bernoulli(jax.random.fold_in(key, i), density,
                                      shape).astype(jnp.float32)
                 for i, shape in enumerate(shapes))


def database(cfg, q: Query, key) -> dict:
    rels = cfg["relations"]
    shapes = tuple(tuple(cfg["domains"][v] for v in sch)
                   for sch in rels.values())
    mults = _draw(key, float(cfg["density"]), shapes)
    return {r: DenseRelation(tuple(sch), q.ring, {"v": m})
            for (r, sch), m in zip(rels.items(), mults)}


def multiplicities(db: dict) -> dict:
    """Device multiplicity tensors of a database made by :func:`database`."""
    return {r: rel.payload["v"] for r, rel in db.items()}


def update_payload(q: Query, mult: np.ndarray) -> dict:
    return {"v": mult}


def width(cfg) -> int:
    """Payload entries per key."""
    return 1


def mul_flops(cfg) -> int:
    """Operations of one ring product."""
    return 1
