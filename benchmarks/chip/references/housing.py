"""Plain reference of the housing deployment: SUM over a star join.

Each view is one ``np.einsum`` over the base relations under its
variable, weighted by the lifted variables' values.  Views over a single
relation are also kept up to date batch by batch (each tuple adds its
multiplicity times its lifted values at its key), so that a read can be
checked against the generation it pinned; :meth:`Reference.self_check`
holds those against the einsum of the final base.
"""
from __future__ import annotations

import numpy as np

from .plain import VarTree, rounder, work_dtype


class Reference:
    components = {"v": ()}

    def __init__(self, cfg: dict, base: dict, precision: str = "float64"):
        self.tree = VarTree(cfg)
        self.dom = cfg["domains"]
        self.dtype = work_dtype(precision)
        self.rnd = rounder(precision)
        self.lifted = set(cfg["lifts"])
        self.base = {r: np.asarray(b, self.dtype) for r, b in base.items()}
        self.single = {v: self.tree.rels_of(v)[0] for v in self.tree.parent
                       if len(self.tree.rels_of(v)) == 1}
        self.live = {v: self.einsum_view(v) for v in self.single}

    def weight(self, var: str) -> np.ndarray:
        if var in self.lifted:
            return np.arange(self.dom[var], dtype=self.dtype)
        return np.ones(self.dom[var], self.dtype)

    def einsum_view(self, var: str) -> np.ndarray:
        t = self.tree
        letter = {v: chr(ord("a") + i) for i, v in enumerate(t.all_vars)}
        subs, ops = [], []
        for r in t.rels_of(var):
            subs.append("".join(letter[v] for v in t.relations[r]))
            ops.append(self.rnd(self.base[r]))
        for v in sorted(t.subtree(var)):
            subs.append(letter[v])
            ops.append(self.weight(v))
        out = "".join(letter[v] for v in t.keys_of(var))
        return self.rnd(np.einsum(",".join(subs) + "->" + out, *ops,
                                  optimize=True))

    def apply(self, rel: str, keys: np.ndarray, mult: np.ndarray) -> None:
        sch = self.tree.relations[rel]
        np.add.at(self.base[rel], tuple(keys.T), mult)
        for var, r in self.single.items():
            if r != rel:
                continue
            contrib = mult.astype(self.dtype)
            for v in self.tree.subtree(var) & set(sch):
                contrib = contrib * self.weight(v)[keys[:, sch.index(v)]]
            idx = tuple(keys[:, sch.index(v)] for v in self.tree.keys_of(var))
            np.add.at(self.live[var], idx, contrib)
            self.live[var][idx] = self.rnd(self.live[var][idx])

    def view(self, var: str):
        """``(key variables, {component: array})`` now."""
        arr = self.live[var] if var in self.live else self.einsum_view(var)
        return self.tree.keys_of(var), {"v": arr}

    def self_check(self) -> None:
        for var in self.live:
            if not np.array_equal(self.live[var], self.einsum_view(var)):
                raise RuntimeError(f"reference disagrees with itself at {var}")
