"""The comparison that decides ``correct``.

The configuration's reference replays the run's batches in order from
the initial base relations.  At each read's pinned offset it answers
the read; after the last batch it gives every view (and the stored base
relations, where the deployment keeps them).  The numbers compared:

* ``base_err``: largest absolute difference of a stored base relation
  (integer-valued, so exact);
* ``view_err``: largest difference of a view after the run, over each
  component's largest magnitude (at least 1);
* ``read_err``: the same for the served reads that are checked, each
  against the view at the generation it pinned (a range sum over its own
  magnitude);
* ``reads_missing``: reads due in the window that failed or never
  returned.

``answers="control"`` puts the reference itself, computed in the next
precision down (bfloat16), in the program's place: its views and its
answers to the same reads are compared in the same way.
"""
from __future__ import annotations

import numpy as np

from .references.plain import view_var

#: precision of the control (the configurations state float32)
CONTROL_PRECISION = "bfloat16"


def _scale(arr) -> float:
    return max(float(np.abs(arr).max()) if arr.size else 0.0, 1.0)


def _err(got, want, scale: float) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    return float(np.abs(got - want).max() / scale) if got.size else 0.0


def in_schema(ref_keys: tuple, payload: dict, schema: tuple) -> dict:
    """A reference view's arrays with their key axes in ``schema`` order."""
    perm = [ref_keys.index(v) for v in schema]
    out = {}
    for c, arr in payload.items():
        extra = list(range(len(ref_keys), arr.ndim))
        out[c] = np.transpose(arr, perm + extra)
    return out


def answer(read, view: dict, components: dict) -> dict:
    """The answer ``view`` (key axes in the served view's order) gives
    to ``read``, in the shape the server returns it."""
    nk = len(read.schema)
    if read.kind == "point":
        idx = tuple(read.params["keys"].T)
        return {c: a[idx] for c, a in view.items()}
    if read.kind == "range_sum":
        lo, hi = read.params["lo"], read.params["hi"]
        return {c: a.reshape((-1,) + a.shape[nk:])[lo:hi].sum(0)
                for c, a in view.items()}
    comp = read.spec.get("component") or next(iter(components))
    k = int(read.spec["k"])
    flat = {c: a.reshape((-1,) + a.shape[nk:]) for c, a in view.items()}
    alive = np.zeros(len(flat[comp]), bool)
    for a in flat.values():
        alive |= (a.reshape(len(a), -1) != 0).any(axis=1)
    scores = np.where(alive, flat[comp], -np.inf)
    top = np.arange(len(scores))
    if k < len(scores):  # the k largest, then in order
        top = np.argpartition(-scores, k)[:k]
    top = top[np.argsort(-scores[top], kind="stable")][:k]
    valid = alive[top]
    dims = view[comp].shape[:nk]
    keys = (np.stack(np.unravel_index(top, dims), axis=1) if nk
            else np.zeros((len(top), 0), np.int64))
    return {"keys": keys, "values": np.where(valid, scores[top], 0.0),
            "valid": valid}


def read_error(read, got: dict, view: dict, components: dict) -> float:
    """How far the result ``got`` of ``read`` lies from ``view``'s
    answer."""
    want = answer(read, view, components)
    if read.kind == "range_sum":  # a sum, over its own magnitude
        return max(_err(got[c], want[c], _scale(want[c])) for c in want)
    if read.kind == "point":
        return max(_err(got[c], want[c], _scale(view[c])) for c in want)
    comp = read.spec.get("component") or next(iter(components))
    scale = _scale(view[comp])
    valid = np.asarray(got["valid"], bool)
    if not np.array_equal(valid, want["valid"]):
        return float("inf")
    values = np.asarray(got["values"])[valid]
    err = _err(values, want["values"][valid], scale)
    # each returned key holds the value returned for it
    nk = len(read.schema)
    flat = view[comp].reshape((-1,) + view[comp].shape[nk:])
    dims = view[comp].shape[:nk]
    keys = np.asarray(got["keys"])[valid]
    lin = (np.ravel_multi_index(tuple(keys.T), dims) if nk
           else np.zeros(len(keys), np.int64))
    return max(err, _err(values, flat[lin], scale))


def compare(cfg: dict, ref_cls, base0: dict, batches: list, reads: list,
            final_views: dict, final_base: dict | None,
            answers: str = "program") -> dict:
    """The numbers compared, by name.

    ``batches`` is every applied batch in order, ``(rel, keys, mult)``;
    ``reads`` the served reads to check (``offset``, ``schema`` and
    ``result`` set); ``final_views`` maps each system view's name to
    ``(schema, {component: host array})``; ``final_base`` the stored
    base relations' multiplicities, or None where none are stored."""
    ref = ref_cls(cfg, base0)
    ctl = (ref_cls(cfg, base0, precision=CONTROL_PRECISION)
           if answers == "control" else None)
    comps = ref.components
    pending = sorted((r for r in reads if r.result is not None),
                     key=lambda r: (r.offset, r.index))
    read_err = 0.0
    j = 0
    for offset in range(len(batches) + 1):
        cache: dict = {}
        while j < len(pending) and pending[j].offset == offset:
            rd = pending[j]
            j += 1
            var = rd.spec["view"]
            if var not in cache:
                keys, payload = ref.view(var)
                cache[var] = in_schema(keys, payload, rd.schema)
                if ctl is not None:
                    ckeys, cpayload = ctl.view(var)
                    cache[var, "ctl"] = in_schema(ckeys, cpayload, rd.schema)
            got = rd.result
            if ctl is not None:  # the control's answer, in its precision
                got = {k: (ctl.rnd(v) if k not in ("keys", "valid") else v)
                       for k, v in answer(rd, cache[var, "ctl"],
                                          comps).items()}
            read_err = max(read_err, read_error(rd, got, cache[var], comps))
        if offset < len(batches):
            ref.apply(*batches[offset])
            if ctl is not None:
                ctl.apply(*batches[offset])
    if j != len(pending):
        raise RuntimeError("a read pinned an offset past the last batch")
    ref.self_check()

    view_err = 0.0
    for name, (schema, payload) in final_views.items():
        var = view_var(name)
        keys, want = ref.view(var)
        want = in_schema(keys, want, schema)
        if ctl is not None:
            ckeys, got = ctl.view(var)
            payload = in_schema(ckeys, got, schema)
        for c, arr in want.items():
            view_err = max(view_err, _err(payload[c], arr, _scale(arr)))
    out = {"view_err": view_err, "read_err": read_err}
    if final_base is not None:
        src = ctl.base if ctl is not None else final_base
        out["base_err"] = max(
            float(np.abs(np.asarray(src[r], np.float64) - ref.base[r]).max())
            for r in ref.base)
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, list]:
    """``correct`` and the lines that show each number beside its limit."""
    lines, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        passed = value is not None and bool(value <= limit)
        ok &= passed
        lines.append((name, value, limit, passed))
    return ok, lines
