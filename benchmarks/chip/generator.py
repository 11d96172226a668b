"""The one traffic generator: every mix under ``traffic/`` is data it reads.

Update batches are numpy arrays, as a client hands them over:
``(relation, keys [B, k] int32, mult [B] float32)``.  Batches go
round-robin over the configuration's relations.  From a relation's
second batch on, ``delete_share`` of each batch deletes tuples that the
relation's previous batch inserted.  Keys are uniform over each
variable's domain.

Reads follow an open-loop schedule: one every ``1 / read_rate`` seconds,
their kinds in the configuration's weights, shuffled within each block
of one weight-sum, so every seed gets the same count of each kind in
another order.  A read's parameters (keys,
ranges) are drawn from the seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: parameters a traffic file may set, with their defaults
DEFAULTS = dict(batch=1024, delete_share=0.25, inflight_segments=2,
                read_rate=50.0, warm_segments=1, trace_after_s=3.0,
                trace_seconds=3.0)


def load(raw: dict) -> dict:
    """The traffic parameters: the file's, over the defaults."""
    unknown = sorted(set(raw) - set(DEFAULTS) - {"about"})
    if unknown:
        raise ValueError(f"unknown traffic parameters: {unknown}")
    return {**DEFAULTS, **{k: v for k, v in raw.items() if k != "about"}}


class UpdateGenerator:
    """Deterministic batch sequence from the seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.rng = np.random.default_rng([seed, 1])
        self.relations = {r: tuple(s) for r, s in cfg["relations"].items()}
        self.domains = cfg["domains"]
        self.batch = int(traffic["batch"])
        self.n_del = int(round(self.batch * float(traffic["delete_share"])))
        self._inserted: dict = {}
        self.order = list(self.relations)  # round-robin
        self.count = 0

    def next(self):
        """The next batch: ``(relation, keys, mult)``."""
        rel = self.order[self.count % len(self.order)]
        self.count += 1
        keys = np.stack([self.rng.integers(0, self.domains[v], self.batch)
                         for v in self.relations[rel]],
                        axis=1).astype(np.int32)
        mult = np.ones(self.batch, np.float32)
        prev = self._inserted.get(rel)
        if prev is not None and self.n_del:
            n = min(self.n_del, len(prev))
            keys[:n] = prev[:n]
            mult[:n] = -1.0
        self._inserted[rel] = keys[mult > 0]
        return rel, keys, mult


@dataclasses.dataclass
class Read:
    """One scheduled read and, once served, what it returned."""

    index: int
    due: float  # seconds after the window opens
    kind: str  # "point" | "range_sum" | "top_k"
    spec: dict  # the configuration's read entry
    params: dict  # keys / lo, hi
    schema: tuple = ()  # key variables of the served view, in its order
    dispatched: float | None = None  # host clock, absolute
    done: float | None = None
    generation: int | None = None
    offset: int | None = None
    result: dict | None = None
    error: str | None = None


def read_schedule(cfg: dict, rate: float, seed: int, seconds: float,
                  key_space: dict, weights=None) -> list[Read]:
    """Every read due in ``[0, seconds)`` at ``rate`` reads a second.
    ``key_space`` maps a served view's variable to ``(key variables,
    their domains, linearized size)``; ``weights`` replaces the
    configuration's."""
    rng = np.random.default_rng([seed, 3])
    specs = list(cfg["reads"])
    weights = weights or [int(s["weight"]) for s in specs]
    block = [i for i, w in enumerate(weights) for _ in range(int(w))]
    rate = float(rate)
    n = int(np.ceil(seconds * rate)) if rate > 0 else 0
    kinds: list[int] = []
    while len(kinds) < n:
        kinds += list(rng.permutation(block))
    out = []
    for i in range(n):
        spec = specs[kinds[i]]
        schema, doms, size = key_space[spec["view"]]
        if spec["kind"] == "point":
            params = dict(keys=np.stack(
                [rng.integers(0, d, int(spec["keys"])) for d in doms],
                axis=1).astype(np.int32))
        elif spec["kind"] == "range_sum":
            lo = hi = 0
            while lo == hi:  # a non-empty range of linearized key ids
                lo, hi = sorted(int(x) for x in rng.integers(0, size + 1, 2))
            params = dict(lo=lo, hi=hi)
        else:
            params = {}
        out.append(Read(i, i / rate, spec["kind"], spec, params, schema))
    return out
