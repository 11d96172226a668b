"""What the references share: the view semantics of a variable order.

A view sits at each variable X of the order.  It aggregates the join of
the relations placed under X's subtree (a relation sits under its
deepest variable), over X's subtree variables, and is keyed by the
ancestors of X that those relations mention.  Nothing here imports the
system under test.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np


class VarTree:
    def __init__(self, cfg: dict):
        vo = cfg["var_order"]
        self.parent: dict = {}

        def chain(vs, parent):
            for v in vs:
                self.parent[v] = parent
                parent = v

        chain(vo["chain"], None)
        pending = True
        while pending:  # branches may hang under variables of branches
            pending = False
            for at, subs in vo.get("branches", {}).items():
                if at not in self.parent:
                    pending = True
                    continue
                for sub in subs:
                    if sub[0] not in self.parent:
                        chain(sub, at)
        self.relations = {r: tuple(s) for r, s in cfg["relations"].items()}
        self.all_vars = []
        for sch in self.relations.values():
            self.all_vars += [v for v in sch if v not in self.all_vars]
        missing = sorted(set(self.all_vars) - set(self.parent))
        if missing:
            raise ValueError(f"variables not in the order: {missing}")

    def ancestors(self, var: str) -> list:
        out, p = [], self.parent[var]
        while p is not None:
            out.append(p)
            p = self.parent[p]
        return out

    def subtree(self, var: str) -> set:
        return {v for v in self.parent if v == var or var in self.ancestors(v)}

    def placed_under(self, rel: str) -> str:
        sch = self.relations[rel]
        return max(sch, key=lambda v: len(self.ancestors(v)))

    def rels_of(self, var: str) -> list:
        sub = self.subtree(var)
        return [r for r in self.relations if self.placed_under(r) in sub]

    def keys_of(self, var: str) -> tuple:
        used = {v for r in self.rels_of(var) for v in self.relations[r]}
        anc = set(self.ancestors(var))
        return tuple(v for v in self.all_vars if v in anc and v in used)


def view_var(engine_view_name: str) -> str:
    """The variable a system view sits at (``V3@ksn`` -> ``ksn``)."""
    return engine_view_name.rsplit("@", 1)[1]


def rounder(precision: str):
    """Rounds an array to the stated precision (float32 arithmetic
    underneath); float64 leaves it as it is."""
    if precision == "float64":
        return lambda a: a
    if precision == "bfloat16":
        return lambda a: np.asarray(a, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(precision)


def work_dtype(precision: str):
    return np.float64 if precision == "float64" else np.float32
