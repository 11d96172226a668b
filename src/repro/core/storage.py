"""Pluggable view storage (DESIGN.md §7).

F-IVM's views are ring-valued dictionaries; the paper's memory wins come
from keeping each view only as large as its *active* key set.  The engine
historically materialized every view as a dense ``[D1..Dk, *payload]``
tensor (``DenseRelation``) — at housing scale (``pc = 65536``, sub-percent
fill) that burns orders of magnitude more memory and scatter bandwidth than
the fill warrants.  This module makes view storage pluggable:

* :class:`ViewStorage` — the protocol every backend implements; it is the
  formerly-implicit ``DenseRelation`` surface the delta engine, contraction
  planner, indicators, stream executor, and kernel dispatch all assume
  (``gather`` / ``scatter_add`` / ``marginalize`` / ``contract`` /
  ``zeros`` / ``from_coo`` / pytree state).
* key-space shim — multi-column key linearization and the payload-pytree ↔
  flat ``[S, d]`` plane conversion.  This is the PR-2 machinery that used to
  live in ``repro.kernels.scatter_ops``; it moved here because it is the
  shared language of *storage*, not of any one kernel: the kernel dispatch
  layer re-exports it.
* :class:`SparseRelation` — hashed-COO backend: an open-addressed int32
  table of linearized keys plus a ``[C, *comp]`` payload plane.  All probe
  loops are pure ``lax.while_loop`` jax, so sparse views ride inside jitted
  triggers, ``lax.scan`` carries, and ``lax.switch`` branches exactly like
  dense ones, and the slot-scatter reuses the ring scatter kernel dispatch.
* storage planner — picks dense vs sparse per materialized view from the
  modeled ``domain product × fill`` (extending the PR-2 element-count cost
  model), honoring the ``REPRO_VIEW_STORAGE`` env var and per-view
  overrides, so a single engine holds dense small views and sparse large
  ones.

Capacities are static (power of two): a compiled trigger can never grow a
table.  The eager per-call path (``IVMEngine.apply_update``) rehashes to
2× capacity when a sparse view crosses the load-factor bound; jitted
streams rely on the planner's headroom (an overflowing insert drops the
row — size capacities so this cannot happen; ``num_keys_sync`` /
``num_slots_used_sync`` exist for exactly this kind of audit).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Mapping, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from .relations import DenseRelation, PyRelation, axis0_leaf_shardings
from .rings import Payload, PyRing, Ring

ENV_VAR = "REPRO_VIEW_STORAGE"
MODES = ("auto", "dense", "sparse")

#: open-addressing sentinel: a table slot holding EMPTY is free
EMPTY = -1

#: auto-planner thresholds: a view flips to sparse when its key-domain
#: product is at least MIN_SPARSE_DOMAIN and its fill is at most MAX_FILL
MIN_SPARSE_DOMAIN = 4096
MAX_FILL = 0.05

#: eager-path growth trigger: rehash to 2× when occupancy crosses this
LOAD_FACTOR = 0.7


# ---------------------------------------------------------------------------
# Key-space shim (moved from repro.kernels.scatter_ops, PR 2): linearized
# keys + flat payload planes are the shared language of storage backends,
# the delta engine, and the kernel dispatch layer.
# ---------------------------------------------------------------------------
def comp_width(shp) -> int:
    """Element count of a (payload or key) shape tuple."""
    w = 1
    for s in shp:
        w *= int(s)
    return w


def linear_ids(keys: jnp.ndarray, domains) -> jnp.ndarray:
    """Row-major flat segment ids for keys [B, k] over domains (D1..Dk)."""
    assert keys.ndim == 2 and keys.shape[1] == len(domains), (
        keys.shape, domains)
    if keys.shape[1] == 0:
        return jnp.zeros((keys.shape[0],), jnp.int32)
    stride = 1
    strides = []
    for d in reversed(domains):
        strides.append(stride)
        stride *= int(d)
    strides = jnp.asarray(strides[::-1], jnp.int32)
    return jnp.sum(keys.astype(jnp.int32) * strides[None, :], axis=1)


def unlinearize_ids(ids: jnp.ndarray, domains) -> jnp.ndarray:
    """Inverse of :func:`linear_ids`: flat ids [B] -> key columns [B, k].

    Negative (sentinel) ids decompose to garbage; callers mask them.
    """
    cols = []
    rem = ids.astype(jnp.int32)
    for d in reversed(domains):
        cols.append(rem % int(d))
        rem = rem // int(d)
    if not cols:
        return jnp.zeros((ids.shape[0], 0), jnp.int32)
    return jnp.stack(cols[::-1], axis=1)


def flatten_payload(ring: Ring, payload: Payload, lead_shape) -> jnp.ndarray:
    """Concatenate ring components into one ``[prod(lead), d_total]`` plane."""
    lead = comp_width(lead_shape)
    planes = [payload[c].reshape(lead, comp_width(shp))
              for c, shp in ring.components.items()]
    return planes[0] if len(planes) == 1 else jnp.concatenate(planes, axis=1)


def unflatten_payload(ring: Ring, flat: jnp.ndarray, lead_shape, dtype=None):
    """Inverse of :func:`flatten_payload` (splits the feature axis)."""
    out, off = {}, 0
    for c, shp in ring.components.items():
        w = comp_width(shp)
        plane = flat[:, off:off + w]
        out[c] = plane.reshape(*lead_shape, *shp).astype(dtype or flat.dtype)
        off += w
    return out


def payload_width(ring: Ring) -> int:
    """Total feature-plane width of a ring's payload."""
    return sum(comp_width(shp) for shp in ring.components.values())


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# ---------------------------------------------------------------------------
# The protocol
# ---------------------------------------------------------------------------
@runtime_checkable
class ViewStorage(Protocol):
    """What the engine assumes of a materialized view / base relation.

    Implementations are registered pytrees whose aux data (schema, ring,
    static layout) is hashable and equality-comparable, so storages thread
    through jit cache keys, ``lax.scan`` carries, and state donation.
    Payload values are ring pytrees; keys are dictionary-encoded int32.
    """

    schema: tuple[str, ...]
    ring: Ring

    @property
    def domains(self) -> tuple[int, ...]: ...
    def domain_of(self, var: str): ...
    def num_keys(self): ...
    def num_keys_sync(self) -> int: ...
    def gather(self, keys: jnp.ndarray) -> Payload: ...
    def scatter_add(self, keys, payload, backend=None): ...
    def add(self, other): ...
    def marginalize(self, var: str, lift_rel=None): ...
    def contract(self, other, marg=(), out_order=None): ...
    def transpose(self, new_schema): ...
    def to_dense(self) -> DenseRelation: ...
    def nbytes(self) -> int: ...
    # multi-device placement surface (DESIGN.md §9): which axis of this
    # storage's key space splits across devices, its extent, and the
    # per-leaf NamedSharding tree for a (mesh, shard?) placement
    def shard_axis(self) -> int | None: ...
    def shard_extent(self) -> int: ...
    def leaf_shardings(self, mesh, axis_name: str, shard: bool): ...


def as_dense(rel) -> DenseRelation:
    """Coerce any storage to its dense materialization (dense: identity)."""
    return rel if isinstance(rel, DenseRelation) else rel.to_dense()


def view_nbytes(rel) -> int:
    """Device bytes held by a view under its actual storage."""
    if hasattr(rel, "nbytes") and not isinstance(rel, (jnp.ndarray, np.ndarray)):
        return rel.nbytes()
    return sum(arr.size * arr.dtype.itemsize
               for arr in jax.tree.leaves(rel.payload))


def make_base_relation(schema, ring: Ring, payload: Payload) -> DenseRelation:
    """Storage-layer constructor for base relations.

    ``apps/`` and data loaders should build relations through this factory
    instead of calling ``DenseRelation(...)`` directly (deprecated for app
    code, DESIGN.md §7): the factory keeps call sites agnostic of the
    storage backend the planner may later swap in.
    """
    return DenseRelation(tuple(schema), ring, payload)


# ---------------------------------------------------------------------------
# Open-addressed hash table primitives (pure jax, while_loop probing)
# ---------------------------------------------------------------------------
def _hash_ids(ids: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """Knuth multiplicative hash into [0, capacity); capacity power of 2."""
    h = ids.astype(jnp.uint32) * jnp.uint32(2654435761)
    return (h & jnp.uint32(capacity - 1)).astype(jnp.int32)


def _find_slots(table: jnp.ndarray, ids: jnp.ndarray):
    """Probe each id's chain: returns (slot [B], found [B]).

    ``slot`` is where the id lives (found) or the first free slot of its
    chain (not found).  Ids < 0 are sentinels: not probed, found = False.
    """
    C = table.shape[0]
    valid = ids >= 0
    slot = _hash_ids(jnp.maximum(ids, 0), C)

    def cond(s):
        _, done, i = s
        return jnp.any(~done) & (i < C)

    def body(s):
        slot, done, i = s
        cur = table[slot]
        stop = (cur == ids) | (cur == EMPTY)
        nslot = jnp.where(done | stop, slot, (slot + 1) & (C - 1))
        return nslot, done | stop, i + 1

    slot, _, _ = jax.lax.while_loop(
        cond, body, (slot, ~valid, jnp.int32(0)))
    found = valid & (table[slot] == ids)
    return slot, found


def _probe_slots(table: jnp.ndarray, ids: jnp.ndarray):
    """Batched per-row probe: returns (slot [B], found [B]).

    Bit-identical contract to :func:`_find_slots`, lowered differently:
    each row runs its *own* scalar ``while_loop`` under ``vmap``, so a
    row terminates as soon as its chain resolves instead of idling until
    the batch's longest chain finishes (``_find_slots`` advances every
    row in lockstep — right for the write path, where the batch is about
    to scatter anyway, wrong for the serving read path, where batches
    are large and chains short).  Ids < 0 are sentinels: not probed,
    found = False — the read path's padding rows."""
    C = table.shape[0]

    def one(id_):
        valid = id_ >= 0
        slot0 = _hash_ids(jnp.maximum(id_, 0), C)

        def cond(s):
            _, done, i = s
            return (~done) & (i < C)

        def body(s):
            slot, _, i = s
            cur = table[slot]
            stop = (cur == id_) | (cur == EMPTY)
            return jnp.where(stop, slot, (slot + 1) & (C - 1)), stop, i + 1

        slot, _, _ = jax.lax.while_loop(cond, body,
                                        (slot0, ~valid, jnp.int32(0)))
        return slot, valid & (table[slot] == id_)

    return jax.vmap(one)(ids)


def _insert_ids(table: jnp.ndarray, ids: jnp.ndarray):
    """Insert *distinct* ids (EMPTY = skip) into the table.

    Contention for a free slot is resolved by a scatter-min claim (lowest
    row index wins); losers keep probing.  Returns (table, slot [B],
    placed [B]); rows that never place (table full) report placed=False.
    """
    C = table.shape[0]
    B = ids.shape[0]
    row = jnp.arange(B, dtype=jnp.int32)
    pending = ids >= 0
    slot = _hash_ids(jnp.maximum(ids, 0), C)
    out_slot = jnp.zeros((B,), jnp.int32)
    placed = jnp.zeros((B,), bool)

    def cond(s):
        _, _, pending, _, _, i = s
        return jnp.any(pending) & (i < C + B)

    def body(s):
        table, slot, pending, out_slot, placed, i = s
        cur = table[slot]
        hit = pending & (cur == ids)
        out_slot = jnp.where(hit, slot, out_slot)
        placed = placed | hit
        pending = pending & ~hit
        empty = pending & (cur == EMPTY)
        claim = jnp.full((C,), B, jnp.int32).at[
            jnp.where(empty, slot, C)].min(row, mode="drop")
        won = empty & (claim[slot] == row)
        table = table.at[jnp.where(won, slot, C)].set(ids, mode="drop")
        out_slot = jnp.where(won, slot, out_slot)
        placed = placed | won
        pending = pending & ~won
        slot = jnp.where(pending, (slot + 1) & (C - 1), slot)
        return table, slot, pending, out_slot, placed, i + 1

    table, _, _, out_slot, placed, _ = jax.lax.while_loop(
        cond, body, (table, slot, pending, out_slot, placed, jnp.int32(0)))
    return table, out_slot, placed


def rank_ids(ids: jnp.ndarray):
    """Sort/rank key dedup (the compaction prepass): per-row rank into the
    distinct-id list + the distinct ids themselves (EMPTY-padded).
    Sentinel ids (< 0) collapse into one EMPTY rank, which sorts first.
    ``_insert_ids`` requires distinct ids — every insert path resolves
    slots per *rank* — and the compact ⊎ (``scatter_ops``, ``ring_fused``)
    sums duplicates over the ranks and scatters the distinct ids."""
    B = ids.shape[0]
    order = jnp.argsort(ids)
    sid = ids[order]
    first = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    rank_sorted = (jnp.cumsum(first.astype(jnp.int32)) - 1).astype(jnp.int32)
    rank = jnp.zeros((B,), jnp.int32).at[order].set(rank_sorted)
    uniq = jnp.full((B,), EMPTY, jnp.int32).at[rank].set(
        jnp.where(ids < 0, EMPTY, ids))
    return rank, uniq


def _dedup_ids(ids: jnp.ndarray, vals: jnp.ndarray):
    """Distinct ids (EMPTY-padded) + per-id summed value rows."""
    rank, uniq = rank_ids(ids)
    sums = jnp.zeros((ids.shape[0], vals.shape[1]), vals.dtype).at[rank].add(
        vals)
    return uniq, sums


# ---------------------------------------------------------------------------
# SparseRelation: hashed-COO view storage
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SparseRelation:
    """Hashed-COO relation: ``table[c]`` holds the linearized key stored in
    slot ``c`` (or EMPTY) and ``payload`` leaves ``[C, *comp]`` hold its
    ring value.  Invariant: free slots carry ring-zero payload.

    Deletions (negative multiplicities) drive payloads to ring zero but
    keep the key slot occupied — ``num_keys`` counts only non-zero keys,
    and :meth:`rehash` compacts zombies away.  Capacity is static under
    jit; see the module docstring for the growth story.
    """

    schema: tuple[str, ...]
    ring: Ring
    _domains: tuple[int, ...]
    table: jnp.ndarray
    payload: Payload

    def tree_flatten(self):
        return ((self.table, self.payload),
                (self.schema, self.ring, self._domains))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(schema=aux[0], ring=aux[1], _domains=aux[2],
                   table=children[0], payload=children[1])

    # -- layout --------------------------------------------------------------
    @property
    def domains(self) -> tuple[int, ...]:
        return self._domains

    def domain_of(self, var: str) -> int:
        return self._domains[self.schema.index(var)]

    @property
    def capacity(self) -> int:
        return int(self.table.shape[0])

    def nbytes(self) -> int:
        total = self.table.size * self.table.dtype.itemsize
        for arr in jax.tree.leaves(self.payload):
            total += arr.size * arr.dtype.itemsize
        return total

    # -- occupancy -----------------------------------------------------------
    def num_keys(self):
        """Keys with non-zero payload, as a device scalar (no host sync)."""
        return jnp.sum((self.table >= 0) & ~self.ring.is_zero(self.payload))

    def num_keys_sync(self) -> int:
        return int(self.num_keys())

    def num_slots_used(self):
        """Occupied slots (including ring-zero zombies), device scalar."""
        return jnp.sum(self.table >= 0)

    def num_slots_used_sync(self) -> int:
        return int(self.num_slots_used())

    # -- multi-device placement (DESIGN.md §9) -------------------------------
    def shard_axis(self) -> int | None:
        """Sparse storage splits along the *slot* axis: each device owns a
        contiguous range of hash-table slots (table row c and payload row
        c co-locate, so a slot scatter routes whole rows)."""
        return 0

    def shard_extent(self) -> int:
        return self.capacity

    def leaf_shardings(self, mesh, axis_name: str, shard: bool):
        """NamedSharding per leaf: table ``[C]`` and payload ``[C, *comp]``
        split their slot axis over ``axis_name`` when ``shard``."""
        return axis0_leaf_shardings(self, mesh, axis_name, shard)

    # -- construction --------------------------------------------------------
    @classmethod
    def zeros(cls, schema, ring: Ring, domains, capacity: int = 64):
        capacity = next_pow2(max(2, int(capacity)))
        return cls(tuple(schema), ring, tuple(int(d) for d in domains),
                   jnp.full((capacity,), EMPTY, jnp.int32),
                   ring.zeros((capacity,)))

    @classmethod
    def from_coo(cls, schema, ring: Ring, domains, keys, payload,
                 capacity: int | None = None):
        if capacity is None:
            capacity = next_pow2(max(64, 2 * int(keys.shape[0])))
        rel = cls.zeros(schema, ring, domains, capacity)
        return rel.scatter_add(keys, payload)

    @classmethod
    def from_dense(cls, dense: DenseRelation, capacity: int | None = None,
                   min_capacity: int = 64) -> "SparseRelation":
        """Sparsify a dense relation (host-side: reads the active key set)."""
        ring = dense.ring
        nz = np.argwhere(np.asarray(ring.is_zero(dense.payload)) == False)  # noqa: E712
        active = nz.shape[0]
        if capacity is None:
            capacity = max(min_capacity, next_pow2(max(2, 2 * active)))
        keys = jnp.asarray(nz.astype(np.int32).reshape(active,
                                                       len(dense.schema)))
        vals = {c: dense.payload[c][tuple(keys[:, i]
                                          for i in range(keys.shape[1]))]
                for c in ring.components}
        rel = cls.zeros(dense.schema, ring, dense.domains, capacity)
        if active == 0:
            return rel
        return rel.scatter_add(keys, vals)

    # -- core ops ------------------------------------------------------------
    def _scatter_lin(self, ids: jnp.ndarray, flat_vals: jnp.ndarray,
                     backend: str | None = None) -> "SparseRelation":
        """⊎ rows (linearized ids, EMPTY = drop; flat [B, d] values).

        Dedup → hash insert → one flat slot-scatter through the ring
        scatter kernel dispatch (the PR-2 ``[S, d]`` plane, with S = the
        table capacity instead of the domain product)."""
        from repro.kernels import scatter_ops

        ring = self.ring
        uniq, sums = _dedup_ids(ids, flat_vals)
        table, slots, placed = _insert_ids(self.table, uniq)
        target = jnp.where(placed, slots, EMPTY)
        plane = flatten_payload(ring, self.payload, (self.capacity,))
        if jnp.dtype(plane.dtype) == jnp.float32:
            out = scatter_ops.scatter_add_flat(plane, target,
                                               sums.astype(plane.dtype),
                                               backend=backend)
        else:  # count rings etc.: exact XLA path (negative ids wrap under
            # drop mode, so padding/overflow rows remap out of range)
            out = plane.at[jnp.where(target < 0, self.capacity, target)].add(
                sums.astype(plane.dtype), mode="drop")
        payload = unflatten_payload(ring, out, (self.capacity,),
                                    dtype=ring.dtype)
        return SparseRelation(self.schema, ring, self._domains, table,
                              payload)

    def scatter_add(self, keys: jnp.ndarray, payload: Payload,
                    backend: str | None = None) -> "SparseRelation":
        """keys [B, k]; payload leaves [B, *comp] (protocol ⊎)."""
        assert keys.ndim == 2 and keys.shape[1] == len(self.schema), (
            keys.shape, self.schema)
        ids = linear_ids(keys, self._domains)
        flat = flatten_payload(self.ring, payload, (keys.shape[0],))
        return self._scatter_lin(ids, flat, backend=backend)

    def gather_mul_scatter(self, keys: jnp.ndarray, src_plane: jnp.ndarray,
                           in_ids: jnp.ndarray, scale: jnp.ndarray,
                           backend: str | None = None) -> "SparseRelation":
        """``self ⊎ (scale[b] · src_plane[in_ids[b]])`` at ``keys`` — the
        deferred sibling gather of the delta engine fused with the sparse
        slot-scatter (scalar rings; the target slots are inserted first,
        then one gather-⊗-⊎ kernel runs over the payload plane).  Duplicate
        keys share one slot via the rank prepass (``_insert_ids`` needs
        distinct ids) and accumulate in the flat scatter."""
        from repro.kernels import scatter_ops

        ids = linear_ids(keys, self._domains)
        rank, uniq = rank_ids(ids)
        table, slots, placed = _insert_ids(self.table, uniq)
        target = jnp.where(placed, slots, EMPTY)[rank]
        plane = flatten_payload(self.ring, self.payload, (self.capacity,))
        out = scatter_ops.gather_mul_scatter_flat(
            plane, target, src_plane, in_ids.astype(jnp.int32), scale,
            backend=backend)
        payload = unflatten_payload(self.ring, out, (self.capacity,),
                                    dtype=self.ring.dtype)
        return SparseRelation(self.schema, self.ring, self._domains, table,
                              payload)

    def fused_slot_targets(self, keys: jnp.ndarray):
        """(table, target [B]) for the fused-chain megakernel: claim slots
        for ``keys`` (duplicates share one slot via the rank prepass —
        ``_insert_ids`` needs distinct ids) but do *not* dedup values; the
        fused kernel accumulates duplicates per tile.  Overflow rows (table
        full) map to EMPTY and drop."""
        ids = linear_ids(keys, self._domains)
        rank, uniq = rank_ids(ids)
        table, slots, placed = _insert_ids(self.table, uniq)
        target = jnp.where(placed, slots, EMPTY)[rank]
        return table, target

    def replace_plane(self, table: jnp.ndarray,
                      plane: jnp.ndarray) -> "SparseRelation":
        """New relation from an updated key table and a flat ``[C, d]``
        payload plane (the fused-chain writeback)."""
        payload = unflatten_payload(self.ring, plane, (self.capacity,),
                                    dtype=self.ring.dtype)
        return SparseRelation(self.schema, self.ring, self._domains, table,
                              payload)

    def replace_payload(self, table: jnp.ndarray,
                        payload: Payload) -> "SparseRelation":
        """New relation from an updated key table and per-component payload
        leaves (the fused-chain flat-XLA writeback, which scatters per
        component instead of through one flat plane)."""
        return SparseRelation(self.schema, self.ring, self._domains, table,
                              payload)

    def lookup(self, keys: jnp.ndarray):
        """(slots [B], found [B]) for keys [B, k] — the raw probe."""
        return _find_slots(self.table, linear_ids(keys, self._domains))

    def probe(self, keys: jnp.ndarray):
        """(slots [B], found [B]) via the batched per-row probe kernel
        (:func:`_probe_slots`) — the serving read path's probe; same
        contract as :meth:`lookup`, per-row loop termination."""
        return _probe_slots(self.table, linear_ids(keys, self._domains))

    def _mask_payload(self, slot: jnp.ndarray,
                      found: jnp.ndarray) -> Payload:
        out = {}
        for c, shp in self.ring.components.items():
            v = self.payload[c][slot]
            mask = found.reshape((-1,) + (1,) * len(shp))
            out[c] = jnp.where(mask, v, jnp.zeros((), self.ring.dtype))
        return out

    def gather(self, keys: jnp.ndarray) -> Payload:
        """keys [B, k] -> payload leaves [B, *comp]; absent keys read 0.

        Zombie transparency: a deleted key keeps its slot (found = True)
        but its payload is ring zero, so the masked read returns exactly
        the ring zero an absent key returns — deletes are invisible to
        readers on both probe paths (pinned by tests/test_serve.py)."""
        slot, found = self.lookup(keys)
        return self._mask_payload(slot, found)

    def gather_batched(self, keys: jnp.ndarray) -> Payload:
        """:meth:`gather` through the batched per-row probe kernel —
        bit-identical results, per-row chain termination (the serving
        plane's point-lookup lowering, DESIGN.md §12)."""
        slot, found = self.probe(keys)
        return self._mask_payload(slot, found)

    def gather_plane(self):
        """Flat ``[C + 1, d]`` payload plane with a trailing zero row — the
        deferred-sibling-gather source: a missed probe indexes row C."""
        plane = flatten_payload(self.ring, self.payload, (self.capacity,))
        return jnp.concatenate(
            [plane, jnp.zeros((1, plane.shape[1]), plane.dtype)])

    def key_columns(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(cols [C, k] clamped to valid ranges, occupied mask [C])."""
        occ = self.table >= 0
        cols = unlinearize_ids(jnp.maximum(self.table, 0), self._domains)
        return cols, occ

    # -- ring algebra --------------------------------------------------------
    def add(self, other) -> "SparseRelation":
        """⊎ with another storage over the same schema."""
        assert tuple(self.schema) == tuple(other.schema), (
            self.schema, other.schema)
        if isinstance(other, SparseRelation):
            flat = flatten_payload(other.ring, other.payload,
                                   (other.capacity,))
            return self._scatter_lin(other.table, flat)
        return self.add_dense(as_dense(other))

    def add_dense(self, dense: DenseRelation) -> "SparseRelation":
        """⊎ a dense relation by enumerating its full key grid (jit-safe;
        meant for small dense deltas — factorized-update application)."""
        S = comp_width(self._domains)
        ids = jnp.arange(S, dtype=jnp.int32)
        flat = flatten_payload(dense.ring, dense.payload, self._domains)
        return self._scatter_lin(ids, flat)

    def marginalize(self, var: str, lift_rel=None) -> "SparseRelation":
        """⊕_var with optional lifting, re-keyed into a fresh table."""
        i = self.schema.index(var)
        cols, occ = self.key_columns()
        payload = self.payload
        if lift_rel is not None:
            g = lift_rel.gather(cols[:, i:i + 1])  # [C, *comp]
            payload = self.ring.mul(payload, g)
        rem = jnp.concatenate([cols[:, :i], cols[:, i + 1:]], axis=1)
        new_schema = tuple(v for v in self.schema if v != var)
        new_doms = tuple(d for j, d in enumerate(self._domains) if j != i)
        ids = jnp.where(occ, linear_ids(rem, new_doms), EMPTY)
        out = SparseRelation.zeros(new_schema, self.ring, new_doms,
                                   self.capacity)
        return out._scatter_lin(
            ids, flatten_payload(self.ring, payload, (self.capacity,)))

    def contract(self, other, marg: Sequence[str] = (),
                 out_order=None) -> "SparseRelation":
        """⊕_marg self ⊗ other via the dense contraction engine, re-keyed
        sparse (host-side sizing: not for jitted trigger paths — the
        planner keeps contraction-fed views dense)."""
        from .contraction import contract_dense

        dense = contract_dense(self.to_dense(), as_dense(other),
                               marg=marg, out_order=out_order)
        return SparseRelation.from_dense(dense)

    def transpose(self, new_schema) -> "SparseRelation":
        perm = [self.schema.index(v) for v in new_schema]
        cols, occ = self.key_columns()
        new_doms = tuple(self._domains[p] for p in perm)
        ids = jnp.where(occ, linear_ids(cols[:, perm], new_doms), EMPTY)
        out = SparseRelation.zeros(tuple(new_schema), self.ring, new_doms,
                                   self.capacity)
        return out._scatter_lin(
            ids, flatten_payload(self.ring, self.payload, (self.capacity,)))

    def rehash(self, capacity: int | None = None) -> "SparseRelation":
        """Rebuild into a fresh table (default: same capacity), dropping
        ring-zero zombie keys.  Pure jax — capacity is static."""
        capacity = capacity or self.capacity
        live = (self.table >= 0) & ~self.ring.is_zero(self.payload)
        ids = jnp.where(live, self.table, EMPTY)
        out = SparseRelation.zeros(self.schema, self.ring, self._domains,
                                   capacity)
        return out._scatter_lin(
            ids, flatten_payload(self.ring, self.payload, (self.capacity,)))

    # -- conversion ----------------------------------------------------------
    def to_dense(self) -> DenseRelation:
        S = comp_width(self._domains)
        ids = jnp.where(self.table >= 0, self.table, S)
        out = {}
        for c, shp in self.ring.components.items():
            w = comp_width(shp)
            flat = jnp.zeros((S, w), self.ring.dtype)
            plane = self.payload[c].reshape(self.capacity, w)
            flat = flat.at[ids].add(plane, mode="drop")
            out[c] = flat.reshape(*self._domains, *shp)
        return DenseRelation(self.schema, self.ring, out)

    def to_py(self, py_ring: PyRing, to_payload=None) -> PyRelation:
        return self.to_dense().to_py(py_ring, to_payload)


# ---------------------------------------------------------------------------
# Storage planner
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StorageSpec:
    """Planner decision for one view."""

    kind: str  # "dense" | "sparse"
    capacity: int = 0  # sparse only


def resolve_storage_mode(mode: str | None = None) -> str:
    """Explicit arg > ``REPRO_VIEW_STORAGE`` env var > auto."""
    m = mode or os.environ.get(ENV_VAR) or "auto"
    assert m in MODES, m
    return m


def plan_storage(
    views: Mapping[str, ViewStorage],
    *,
    tree=None,
    updatable: Sequence[str] = (),
    strategy: str = "fivm",
    mode: str | None = None,
    overrides: Mapping[str, str] | None = None,
    min_domain: int = MIN_SPARSE_DOMAIN,
    max_fill: float = MAX_FILL,
    headroom: float = 2.0,
    min_capacity: int = 64,
) -> dict[str, StorageSpec]:
    """Pick a storage backend per materialized view.

    ``auto`` chooses sparse when the modeled dense size (key-domain
    product) clears ``min_domain``, the measured fill is at most
    ``max_fill``, *and* the view's delta interactions are gather/scatter
    shaped (``materialize.gather_scatter_profile``) — views that force
    densifying joins or mixed applies stay dense.  ``sparse`` forces every
    structurally-eligible view sparse (fallback paths cover the rest);
    ``dense`` is the seed behavior.  Per-view ``overrides``
    (name -> "dense" | "sparse") win over everything.

    1-IVM and reevaluation rebuild views from base relations inside their
    triggers (replacing storage wholesale), so only ``fivm`` / ``dbt``
    engines plan non-dense storage.  Premarg ``W:`` views stay dense
    unless explicitly overridden (their payloads are read positionally by
    the factorized-representation consumers).
    """
    mode = resolve_storage_mode(mode)
    overrides = dict(overrides or {})
    hostile: set[str] = set()
    if tree is not None and mode == "auto":
        # the eligibility walk is the trigger-plan compiler's symbolic path
        # analysis (DESIGN.md §8): storage class, densify cost, and scatter
        # backend are decided against one model
        from .plan import storage_hostility

        hostile = storage_hostility(tree, updatable)
    plan: dict[str, StorageSpec] = {}
    for name, v in views.items():
        kind = overrides.get(name)
        if kind is None:
            if (strategy not in ("fivm", "dbt") or name.startswith("W:")
                    or not v.schema or mode == "dense"):
                kind = "dense"
            elif mode == "sparse":
                kind = "sparse"
            else:  # auto: domain product × fill model
                S = comp_width(v.domains)
                fill = v.num_keys_sync() / max(S, 1)
                kind = ("sparse" if S >= min_domain and fill <= max_fill
                        and name not in hostile else "dense")
        if kind == "sparse":
            S = comp_width(v.domains)
            active = v.num_keys_sync()
            cap = next_pow2(max(min_capacity, int(active * headroom) + 1))
            # a table at least as large as the domain can never overflow
            cap = min(cap, next_pow2(S))
            plan[name] = StorageSpec("sparse", cap)
        else:
            plan[name] = StorageSpec("dense")
    return plan


def apply_storage_plan(views: Mapping[str, ViewStorage],
                       plan: Mapping[str, StorageSpec]):
    """Convert each view to its planned backend (no-op where it matches)."""
    out = {}
    for name, v in views.items():
        spec = plan.get(name, StorageSpec("dense"))
        if spec.kind == "sparse" and isinstance(v, DenseRelation):
            out[name] = SparseRelation.from_dense(v, capacity=spec.capacity)
        elif spec.kind == "dense" and isinstance(v, SparseRelation):
            out[name] = v.to_dense()
        else:
            out[name] = v
    return out


def grow_if_loaded(rel, budget: int = 0):
    """Eager-path growth: rehash a sparse view to 2× capacity when adding
    ``budget`` more keys could cross the load-factor bound.  The budget is
    clamped to the key-domain product (there are never more distinct keys
    than the domain holds), and a table covering the full domain stops
    growing — it can never overflow.  Host sync — never call from a trace
    (the jitted paths keep capacities static)."""
    if not isinstance(rel, SparseRelation):
        return rel
    full = next_pow2(comp_width(rel.domains))
    budget = min(int(budget), comp_width(rel.domains))
    cap = rel.capacity
    used = rel.num_slots_used_sync()
    while cap < full and used + budget > LOAD_FACTOR * cap:
        cap *= 2
    if cap != rel.capacity:
        rel = rel.rehash(cap)  # also compacts ring-zero zombies
    return rel


def occupancy_report(views: Mapping[str, ViewStorage]) -> dict[str, dict]:
    """Host-sync occupancy snapshot of every sparse view: capacity, slots
    used (zombies included — what the load-factor bound sees), and live
    key count.  The telemetry the integrity layer's graceful-degradation
    path records when it resegments/rehashes under capacity pressure
    (DESIGN.md §11); never call from a trace or the replay hot loop."""
    out: dict[str, dict] = {}
    for name, v in views.items():
        if isinstance(v, SparseRelation):
            out[name] = {
                "capacity": int(v.capacity),
                "slots_used": int(v.num_slots_used_sync()),
                "keys": int(v.num_keys_sync()),
            }
    return out


# ---------------------------------------------------------------------------
# Checkpoint layout export/import (DESIGN.md §10)
# ---------------------------------------------------------------------------
def export_layout(rel) -> dict:
    """JSON-serializable physical-layout descriptor of a view's storage.

    A checkpoint stores leaves positionally; to rebuild the restore
    *template* the layout must pin everything that determines leaf shapes
    but is not part of the engine's logical definition — for sparse views
    that is the hash-table capacity (a leaf shape, not pytree aux), which
    drifts at runtime via rehash/growth and rarely matches a freshly built
    engine's."""
    if isinstance(rel, SparseRelation):
        return {"kind": "sparse", "capacity": rel.capacity}
    return {"kind": "dense"}


def layout_template(rel, layout: Mapping) -> "ViewStorage":
    """An all-zeros view with ``rel``'s logical definition (schema, ring,
    domains) but the checkpointed physical layout — the shape-exact
    template :meth:`Checkpointer.restore` requires."""
    if layout.get("kind") == "sparse":
        return SparseRelation.zeros(rel.schema, rel.ring, rel.domains,
                                    capacity=int(layout["capacity"]))
    return DenseRelation.zeros(rel.schema, rel.ring, rel.domains)
