"""Ring-bilinear contraction engine.

A view in F-IVM is a join of child views followed by marginalization of the
node's variable (Fig. 3).  Over dense dictionary-encoded relations this is a
*tensor contraction in the ring*:

    V[out] = ⊕_{marg} A[sch_A] ⊗ B[sch_B]

Because every ring product we use is bilinear in its payload components
(``Ring.mul_terms``), the contraction decomposes into one ``jnp.einsum`` per
bilinear term — each runs on the MXU.  This file also implements the
batched-COO delta algebra used for incremental maintenance: a delta is COO
over the variables bound by the update and dense over variables contributed
by materialized sibling views, matching the paper's complexity claims
(single-tuple updates propagate in O(1)/O(D) per the bound/free structure).
"""
from __future__ import annotations

import dataclasses
import functools
import string
from typing import Sequence

import jax
import jax.numpy as jnp

from .relations import COOUpdate, DenseRelation
from .rings import EXACT, Payload, Ring

_KEY_LETTERS = string.ascii_lowercase
_PAY_LETTERS = string.ascii_uppercase


def _pay_map(subs: str) -> str:
    """Map MulTerm payload subscripts (i, j, k...) into the uppercase pool."""
    return "".join(_PAY_LETTERS[ord(c) - ord("i")] for c in subs)


# ---------------------------------------------------------------------------
# Contraction-plan cache.  Every bilinear contraction site reduces to a fixed
# list of (comp_out, comp_a, comp_b, einsum_spec, coef) terms determined by
# the ring's mul_terms and the key-subscript strings — pure trace-time
# metadata.  The stream executor retraces triggers inside scan/switch bodies,
# so these plans are memoized instead of rebuilt string-by-string per trace.
# mul_terms are tuples of frozen MulTerm dataclasses: hashable and equal
# across ring instances of the same shape.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _einsum_plan(mul_terms, a_key: str, b_key: str, o_key: str):
    return tuple(
        (
            t.comp_out,
            t.comp_a,
            t.comp_b,
            f"{a_key}{_pay_map(t.a_subs)},{b_key}{_pay_map(t.b_subs)}"
            f"->{o_key}{_pay_map(t.out_subs)}",
            t.coef,
        )
        for t in mul_terms
    )


def _apply_plan(plan, a_payload: Payload, b_payload: Payload) -> dict:
    out: dict[str, jnp.ndarray] = {}
    for comp_out, comp_a, comp_b, spec, coef in plan:
        term = jnp.einsum(spec, a_payload[comp_a], b_payload[comp_b],
                          precision=EXACT)
        if coef != 1.0:
            term = term * coef
        out[comp_out] = out.get(comp_out, 0) + term
    return out


@functools.lru_cache(maxsize=None)
def _dense_plan(mul_terms, a_schema: tuple, b_schema: tuple, marg: tuple,
                out_order: tuple | None):
    """(out_schema, einsum plan) for contract_dense, keyed per
    (schema_a, schema_b, marg, ring bilinear structure)."""
    all_vars = list(a_schema) + [v for v in b_schema if v not in a_schema]
    for m in marg:
        assert m in all_vars, (m, all_vars)
    out_schema = tuple(v for v in all_vars if v not in marg)
    if out_order is not None:
        assert set(out_order) == set(out_schema)
        out_schema = tuple(out_order)
    letters = {v: _KEY_LETTERS[i] for i, v in enumerate(all_vars)}
    a_key = "".join(letters[v] for v in a_schema)
    b_key = "".join(letters[v] for v in b_schema)
    o_key = "".join(letters[v] for v in out_schema)
    return out_schema, _einsum_plan(mul_terms, a_key, b_key, o_key)


def contract_dense(
    a: DenseRelation,
    b: DenseRelation,
    marg: Sequence[str] = (),
    out_order: Sequence[str] | None = None,
) -> DenseRelation:
    """V = ⊕_{marg} a ⊗ b over dense relations (einsum per bilinear term)."""
    ring = a.ring
    assert ring is b.ring or ring.name == b.ring.name
    assert ring.mul_terms is not None, f"ring {ring.name} lacks bilinear terms"
    out_schema, plan = _dense_plan(
        tuple(ring.mul_terms), tuple(a.schema), tuple(b.schema), tuple(marg),
        None if out_order is None else tuple(out_order))
    out = _apply_plan(plan, a.payload, b.payload)
    doms = []
    for v in out_schema:
        src = a if v in a.schema else b
        doms.append(src.domain_of(v))
    for comp, shp in ring.components.items():
        if comp not in out:
            out[comp] = jnp.zeros((*doms, *shp), ring.dtype)
    return DenseRelation(out_schema, ring, out)


def lift_relation(ring: Ring, var: str, domain_values: jnp.ndarray,
                  lift_spec) -> DenseRelation:
    """Build the unary 'lift relation' L_X[x] = g_X(x) over the dictionary.

    lift_spec: ("one",) | ("value",) | ("degree", j)
    """
    kind = lift_spec[0]
    if kind == "one":
        payload = ring.ones((domain_values.shape[0],))
    elif kind == "value":
        payload = ring.lift(domain_values)
    elif kind == "square":  # g(x) = x² (scalar-payload cofactor baselines)
        payload = ring.lift(domain_values * domain_values)
    elif kind == "degree":
        payload = ring.lift(domain_values, var_index=lift_spec[1])
    else:  # pragma: no cover
        raise ValueError(lift_spec)
    return DenseRelation((var,), ring, payload)


def marginalize_dense(
    rel: DenseRelation, var: str, lift_rel: DenseRelation | None
) -> DenseRelation:
    """⊕_X rel with optional lifting (contract against the lift relation)."""
    if lift_rel is None:
        # pure sum over the axis
        i = rel.schema.index(var)
        out_schema = tuple(v for v in rel.schema if v != var)
        out = {c: jnp.sum(rel.payload[c], axis=i) for c in rel.ring.components}
        return DenseRelation(out_schema, rel.ring, out)
    return contract_dense(rel, lift_rel, marg=(var,))


# ---------------------------------------------------------------------------
# Batched deltas: COO over update-bound vars × dense over sibling-contributed
# vars.  This is the device representation of a delta view (Sec. 4–5).
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class BatchedDelta:
    """payload leaves: [B, *domains(dense_schema), *comp_shape].

    ``pending_gather`` is a deferred sibling-view gather ``(src_plane
    [Sg, d], in_ids [B])``: for bilinear *commutative* rings, ``join_dense``
    against a view fully bound by the delta's COO vars is just a per-row
    gather-multiply, so it is left symbolic — the source payload plane is
    the view's flattened ``[Sg, d]`` component plane (dense views flatten
    whole; sparse views resolve hash slots at defer time and append a zero
    row that missed probes index) — and fused with the eventual scatter in
    ``apply_to``.  Scalar-payload rings take the single Pallas gather-⊗-⊎
    kernel; wider rings gather the plane once and run the ring's bilinear
    product row-wise before the scatter.  Non-commutative rings never
    defer (the gathered factor must multiply from its original side), and
    any operation that needs the materialized payload forces it first
    (:meth:`_force`)."""

    coo_schema: tuple[str, ...]
    dense_schema: tuple[str, ...]
    keys: jnp.ndarray  # [B, len(coo_schema)] int32
    ring: Ring
    payload: Payload
    dense_domains: tuple[int, ...] = ()
    pending_gather: tuple | None = None

    @property
    def batch(self) -> int:
        return self.keys.shape[0]

    def key_col(self, var: str) -> jnp.ndarray:
        return self.keys[:, self.coo_schema.index(var)]

    @classmethod
    def from_coo(cls, ring: Ring, upd: COOUpdate) -> "BatchedDelta":
        return cls(
            coo_schema=tuple(upd.schema),
            dense_schema=(),
            keys=upd.keys,
            ring=ring,
            payload=upd.payload,
            dense_domains=(),
        )

    # -- deferred sibling gather --------------------------------------------
    def _is_scalar_ring(self) -> bool:
        comps = self.ring.components
        return len(comps) == 1 and next(iter(comps.values())) == ()

    def _defer_ok(self, view) -> bool:
        """A join against ``view`` can stay symbolic when the ring product
        is bilinear and commutative (deferral reorders the gathered factor
        past later lift-multiplies), the delta carries no dense axes, and
        every view var is COO-bound (the join is a pure per-row gather)."""
        ring = self.ring
        if self.pending_gather is not None or self.dense_schema:
            return False
        if ring.mul_terms is None or not ring.commutative:
            return False
        return bool(view.schema) and all(v in self.coo_schema
                                         for v in view.schema)

    def _gather_plan(self, view, src_plane=None
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(src_plane [Sg, d], in_ids [B]) for a deferred gather of
        ``view`` at the delta's COO coordinates.  ``src_plane`` optionally
        supplies the view's prepared payload plane (the stream executor's
        per-step CSE memo computes shared planes once per fused step)."""
        from repro.core import storage

        keys = jnp.stack([self.key_col(v) for v in view.schema], axis=1)
        if isinstance(view, storage.SparseRelation):
            slots, found = view.lookup(keys)
            if src_plane is None:
                src_plane = view.gather_plane()  # [C + 1, d], zero row at C
            ids = jnp.where(found, slots, view.capacity)
            return src_plane, ids
        if src_plane is None:
            src_plane = storage.flatten_payload(self.ring, view.payload,
                                                view.domains)
        return src_plane, storage.linear_ids(keys, view.domains)

    def _force(self) -> "BatchedDelta":
        """Materialize a deferred sibling gather into the payload."""
        if self.pending_gather is None:
            return self
        from repro.core import storage

        src_plane, ids = self.pending_gather
        g = jnp.take(src_plane, ids, axis=0, mode="clip")  # [B, d]
        if self._is_scalar_ring():
            comp = next(iter(self.ring.components))
            payload = {comp: self.payload[comp] * g[:, 0]}
        else:
            gp = storage.unflatten_payload(self.ring, g, (self.batch,),
                                           dtype=self.ring.dtype)
            payload = _mul_broadcast(self.ring, self.payload, gp,
                                     self.dense_schema)
        return dataclasses.replace(self, payload=payload, pending_gather=None)

    # -- lift-and-marginalize one variable ---------------------------------
    def marginalize(self, var: str, lift_rel: DenseRelation | None) -> "BatchedDelta":
        if var in self.coo_schema:
            if (self.pending_gather is not None and self.batch > 1
                    and len(self.coo_schema) == 1):
                # batch collapse would sum rows: materialize the gather first
                return self._force().marginalize(var, lift_rel)
            i = self.coo_schema.index(var)
            payload = self.payload
            if lift_rel is not None:
                g = lift_rel.gather(self.keys[:, i : i + 1])  # [B, *comp]
                payload = _mul_broadcast(self.ring, payload, g, self.dense_schema)
            keys = jnp.delete(self.keys, i, axis=1, assume_unique_indices=True)
            new_coo = tuple(v for v in self.coo_schema if v != var)
            if not new_coo and self.batch > 1:
                # batch collapse: with no COO vars left the rows are
                # indistinguishable — sum them into one row now so every
                # downstream join/marginalize/apply streams [1, D...] instead
                # of [B, D...] (apply_to would do this sum at the end anyway)
                payload = {c: jnp.sum(p, axis=0, keepdims=True)
                           for c, p in payload.items()}
                keys = keys[:1]
            return dataclasses.replace(
                self,
                coo_schema=new_coo,
                keys=keys,
                payload=payload,
            )
        # dense axis: contract against lift vector (or plain-sum)
        i = self.dense_schema.index(var)
        axis = 1 + i  # after batch
        if lift_rel is None:
            payload = {c: jnp.sum(self.payload[c], axis=axis) for c in self.ring.components}
        else:
            payload = _contract_axis(self.ring, self.payload, lift_rel.payload, axis,
                                     len(self.dense_schema))
        return dataclasses.replace(
            self,
            dense_schema=tuple(v for v in self.dense_schema if v != var),
            dense_domains=tuple(d for j, d in enumerate(self.dense_domains) if j != i),
            payload=payload,
        )

    # -- join with a materialized sibling view ------------------------------
    def join_dense(self, view, src_plane=None) -> "BatchedDelta":
        """δ ⊗ V: coo-shared vars of V are gathered at the delta's coords;
        dense-shared vars align elementwise; fresh vars of V become new
        dense axes.  ``view`` is any ViewStorage: sparse siblings resolve
        to gathers (deferred where possible) and densify only when the
        join would grow dense axes from them.  ``src_plane`` optionally
        short-circuits the deferred gather's plane preparation (plan-level
        CSE across a fused stream step)."""
        ring = self.ring
        if self._defer_ok(view):
            return dataclasses.replace(
                self, pending_gather=self._gather_plan(view, src_plane))
        if self.pending_gather is not None:
            return self._force().join_dense(view, src_plane)
        from repro.core import storage

        if isinstance(view, storage.SparseRelation):
            if view.schema and all(v in self.coo_schema for v in view.schema):
                # per-row gather-multiply (e.g. a second sibling after a
                # forced pending gather, or a delta carrying dense axes)
                keys = jnp.stack([self.key_col(v) for v in view.schema],
                                 axis=1)
                g = view.gather(keys)  # [B, *comp]
                payload = _mul_broadcast(ring, self.payload, g,
                                         self.dense_schema)
                return dataclasses.replace(self, payload=payload)
            view = view.to_dense()  # join grows dense axes: materialize
        shared_coo = [v for v in view.schema if v in self.coo_schema]
        shared_dense = [v for v in view.schema if v in self.dense_schema]
        fresh = [v for v in view.schema if v not in shared_coo and v not in shared_dense]

        # Gather view slices at coo coordinates -> leading batch axis.
        if shared_coo:
            idx_axes = [view.schema.index(v) for v in shared_coo]
            rest_axes = [i for i in range(len(view.schema)) if i not in idx_axes]
            v_payload = {}
            for comp, shp in ring.components.items():
                arr = view.payload[comp]
                nk = len(view.schema)
                if len(idx_axes) == 1:
                    # gather along the shared axis, then move the batch axis
                    # to the front: touches O(B·|rest|) elements instead of
                    # transposing the whole materialized view first
                    ax = idx_axes[0]
                    g = jnp.take(arr, self.key_col(shared_coo[0]), axis=ax)
                    v_payload[comp] = jnp.moveaxis(g, ax, 0)
                else:
                    perm = idx_axes + rest_axes + list(range(nk, arr.ndim))
                    arr = jnp.transpose(arr, perm)
                    idx = tuple(self.key_col(v) for v in shared_coo)
                    v_payload[comp] = arr[idx]  # [B, rest..., comp]
            v_schema = [view.schema[i] for i in rest_axes]
            has_batch = True
        else:
            v_payload = view.payload
            v_schema = list(view.schema)
            has_batch = False

        # Now multiply: self.payload [B, D_dense..., comp] with
        # v_payload [B?, D_vrest..., comp] aligning shared_dense axes and
        # broadcasting fresh axes.  Use einsum per bilinear term.
        out_dense = list(self.dense_schema) + [v for v in v_schema if v not in self.dense_schema]
        letters = {v: _KEY_LETTERS[i] for i, v in enumerate(out_dense)}
        a_key = "z" + "".join(letters[v] for v in self.dense_schema)
        b_key = ("z" if has_batch else "") + "".join(letters[v] for v in v_schema)
        o_key = "z" + "".join(letters[v] for v in out_dense)
        assert ring.mul_terms is not None
        plan = _einsum_plan(tuple(ring.mul_terms), a_key, b_key, o_key)
        out = _apply_plan(plan, self.payload, v_payload)
        doms = dict(zip(self.dense_schema, self.dense_domains))
        for v in v_schema:
            doms.setdefault(v, view.domain_of(v))
        out_domains = tuple(doms[v] for v in out_dense)
        for comp, shp in ring.components.items():
            if comp not in out:
                out[comp] = jnp.zeros((self.batch, *out_domains, *shp), ring.dtype)
        return dataclasses.replace(
            self,
            dense_schema=tuple(out_dense),
            dense_domains=out_domains,
            payload=out,
        )

    # -- application ---------------------------------------------------------
    def apply_to(self, view, backend: str | None = None):
        """view ⊎ δ : scatter-add into the materialized view (any storage).

        Scatters route through the ring scatter dispatch layer
        (``repro.kernels.scatter_ops``); a pending sibling gather fuses
        into one gather-⊗-⊎ kernel call (scalar rings) or one flat
        gather + row-wise ring product + scatter (bilinear rings)."""
        ring = self.ring
        assert set(view.schema) == set(self.coo_schema) | set(self.dense_schema), (
            view.schema, self.coo_schema, self.dense_schema)
        from repro.core import storage

        if isinstance(view, storage.SparseRelation):
            return self._apply_sparse(view, backend)
        coo_axes = [view.schema.index(v) for v in self.coo_schema]
        dense_axes = [view.schema.index(v) for v in self.dense_schema]
        from repro.kernels import scatter_ops

        if coo_axes and not dense_axes:
            # pure-COO delta: one flat scatter, each view axis indexed by
            # its own key column — no transpose of the materialized view
            keys = jnp.stack([self.key_col(v) for v in view.schema], axis=1)
            if self.pending_gather is not None:
                src_plane, in_ids = self.pending_gather
                if self._is_scalar_ring():
                    comp = next(iter(ring.components))
                    new_payload = scatter_ops.gather_mul_scatter_payload(
                        view.payload, view.domains, keys, src_plane, in_ids,
                        self.payload[comp], ring, backend=backend)
                else:
                    new_payload = scatter_ops.gather_ringmul_scatter_payload(
                        view.payload, view.domains, keys, src_plane, in_ids,
                        self.payload, ring, backend=backend)
            else:
                new_payload = scatter_ops.scatter_add_payload(
                    view.payload, view.domains, keys, self.payload, ring,
                    backend=backend)
            return DenseRelation(view.schema, ring, new_payload)
        slf = self._force()
        if coo_axes:
            coo_doms = tuple(view.domain_of(v) for v in slf.coo_schema)
            resolved = scatter_ops.resolve_backend(
                scatter_ops._comp_width(coo_doms), slf.batch,
                sum(scatter_ops._comp_width(view.payload[c].shape[1:])
                    for c in ring.components), backend)
            if resolved != "jnp" and scatter_ops.kernelable(
                    ring, view.payload, slf.payload):
                return slf._apply_mixed_kernel(view, coo_axes, dense_axes,
                                               resolved)
        return slf._apply_mixed_jnp(view, coo_axes, dense_axes)

    def _apply_mixed_jnp(self, view: DenseRelation, coo_axes, dense_axes
                         ) -> DenseRelation:
        """Legacy mixed COO×dense application (XLA scatter / plain add)."""
        ring = self.ring
        nk = len(view.schema)
        new_payload = {}
        for comp, shp in ring.components.items():
            arr = view.payload[comp]
            # move coo axes to the front
            perm = coo_axes + dense_axes + list(range(nk, arr.ndim))
            inv = [perm.index(i) for i in range(arr.ndim)]
            arrp = jnp.transpose(arr, perm)
            # delta payload: [B, *dense_domains(self order), *comp] — its dense
            # order is self.dense_schema; match view's dense axis order.
            dp = self.payload[comp]
            d_perm = [0] + [1 + self.dense_schema.index(view.schema[i]) for i in dense_axes] \
                + list(range(1 + len(self.dense_schema), dp.ndim))
            dp = jnp.transpose(dp, d_perm)
            if coo_axes:
                idx = tuple(self.key_col(v) for v in self.coo_schema)
                arrp = arrp.at[idx].add(dp)
            else:
                arrp = arrp + jnp.sum(dp, axis=0)
            new_payload[comp] = jnp.transpose(arrp, inv)
        return DenseRelation(view.schema, ring, new_payload)

    def _apply_mixed_kernel(self, view: DenseRelation, coo_axes, dense_axes,
                            backend: str) -> DenseRelation:
        """Mixed COO×dense application through the kernel dispatch: the coo
        axes linearize to segment ids; the dense axes and ring components
        flatten into one [S_coo, d] feature plane per the scatter shim."""
        from repro.kernels import scatter_ops

        ring = self.ring
        nk = len(view.schema)
        coo_doms = tuple(view.domain_of(v) for v in self.coo_schema)
        S = scatter_ops._comp_width(coo_doms)
        B = self.batch
        view_planes, val_planes, metas = [], [], []
        for comp, shp in ring.components.items():
            arr = view.payload[comp]
            perm = coo_axes + dense_axes + list(range(nk, arr.ndim))
            inv = [perm.index(i) for i in range(arr.ndim)]
            arrp = jnp.transpose(arr, perm)
            dp = self.payload[comp]
            d_perm = [0] + [1 + self.dense_schema.index(view.schema[i])
                            for i in dense_axes] \
                + list(range(1 + len(self.dense_schema), dp.ndim))
            dp = jnp.transpose(dp, d_perm)
            metas.append((comp, arrp.shape, inv))
            view_planes.append(arrp.reshape(S, -1))
            val_planes.append(dp.reshape(B, -1))
        flat_view = view_planes[0] if len(view_planes) == 1 else \
            jnp.concatenate(view_planes, axis=1)
        flat_vals = val_planes[0] if len(val_planes) == 1 else \
            jnp.concatenate(val_planes, axis=1)
        ids = scatter_ops.linear_ids(
            jnp.stack([self.key_col(v) for v in self.coo_schema], axis=1),
            coo_doms)
        out = scatter_ops.scatter_add_flat(flat_view, ids, flat_vals,
                                           backend=backend)
        new_payload, off = {}, 0
        for comp, pshape, inv in metas:
            w = scatter_ops._comp_width(pshape[len(coo_doms):])
            plane = out[:, off:off + w].astype(ring.dtype)
            new_payload[comp] = jnp.transpose(plane.reshape(pshape), inv)
            off += w
        return DenseRelation(view.schema, ring, new_payload)

    def _apply_sparse(self, view, backend: str | None):
        """⊎ into a hashed-COO view: hash-slot resolution + the same flat
        kernel scatters.  Mixed COO×dense deltas enumerate their (static)
        dense grid into COO rows first."""
        import numpy as np

        ring = self.ring
        assert view.schema, "scalar-keyed views are always dense"
        if not self.dense_schema:
            keys = jnp.stack([self.key_col(v) for v in view.schema], axis=1)
            if self.pending_gather is not None and self._is_scalar_ring():
                # fused: insert slots, then one gather-⊗-⊎ over the plane
                src_plane, in_ids = self.pending_gather
                comp = next(iter(ring.components))
                return view.gather_mul_scatter(keys, src_plane, in_ids,
                                               self.payload[comp],
                                               backend=backend)
            slf = self._force()  # non-scalar pending: gather-then-scatter
            return view.scatter_add(keys, slf.payload, backend=backend)
        slf = self._force()
        B = slf.batch
        P = 1
        for d in slf.dense_domains:
            P *= int(d)
        grid = np.stack(
            np.meshgrid(*[np.arange(d) for d in slf.dense_domains],
                        indexing="ij"), -1,
        ).reshape(P, len(slf.dense_schema)).astype(np.int32)
        cols = []
        for v in view.schema:
            if v in slf.coo_schema:
                cols.append(jnp.repeat(slf.key_col(v), P))
            else:
                j = slf.dense_schema.index(v)
                cols.append(jnp.tile(jnp.asarray(grid[:, j]), B))
        keys = jnp.stack(cols, axis=1)
        payload = {c: slf.payload[c].reshape(B * P, *shp)
                   for c, shp in ring.components.items()}
        return view.scatter_add(keys, payload, backend=backend)

    def densify(self) -> DenseRelation:
        """Materialize into a dense relation over coo+dense schema (testing,
        and root-result deltas for unmaterialized ancestors)."""
        doms_coo = tuple(0 for _ in self.coo_schema)  # unknown; must be given
        raise NotImplementedError("use apply_to on a zero view with known domains")

    def total(self) -> Payload:
        """Sum payload over batch and all dense axes (for scalar-keyed roots)."""
        assert not self.coo_schema, "total() only valid once all coo vars are marginalized"
        slf = self._force()
        out = {}
        for comp, shp in slf.ring.components.items():
            arr = slf.payload[comp]
            axes = tuple(range(0, 1 + len(slf.dense_schema)))
            out[comp] = jnp.sum(arr, axis=axes)
        return out


def _mul_broadcast(ring: Ring, payload: Payload, g: Payload, dense_schema) -> Payload:
    """payload [B, D..., comp] * g [B, comp] elementwise in the ring."""
    nd = len(dense_schema)
    d_letters = _KEY_LETTERS[:nd]
    assert ring.mul_terms is not None
    plan = _einsum_plan(tuple(ring.mul_terms), f"z{d_letters}", "z",
                        f"z{d_letters}")
    out = _apply_plan(plan, payload, g)
    for comp, shp in ring.components.items():
        if comp not in out:
            b = payload[next(iter(payload))].shape[0]
            dd = payload[next(iter(payload))].shape[1 : 1 + nd]
            out[comp] = jnp.zeros((b, *dd, *shp), ring.dtype)
    return out


def _contract_axis(ring: Ring, payload: Payload, lift_payload: Payload,
                   axis: int, n_dense: int) -> Payload:
    """⊕ over one dense axis with lifting: einsum contraction of that axis."""
    assert ring.mul_terms is not None
    d_letters = _KEY_LETTERS[:n_dense]
    m = d_letters[axis - 1]
    o_letters = d_letters.replace(m, "")
    plan = _einsum_plan(tuple(ring.mul_terms), f"z{d_letters}", m,
                        f"z{o_letters}")
    out = _apply_plan(plan, payload, lift_payload)
    for comp, shp in ring.components.items():
        if comp not in out:
            ref = payload[next(iter(payload))]
            b = ref.shape[0]
            dd = tuple(d for i, d in enumerate(ref.shape[1 : 1 + n_dense]) if i != axis - 1)
            out[comp] = jnp.zeros((b, *dd, *shp), ring.dtype)
    return out
