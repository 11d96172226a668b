"""Megakernel backend for fused trigger chains (DESIGN.md §13).

The trigger-plan IR (``repro.core.plan``) lowers each op — Gather, Lift,
JoinContract, Marginalize, ScatterAccum — as a separate dispatch, so every
delta hop round-trips its ``[B, d]`` payload plane through HBM.  The fusion
pass collapses an eligible Gather→Lift→JoinContract→(Marginalize)→
ScatterAccum subsequence into one :class:`~repro.core.plan.FusedChain`
whose runtime is this module: the whole chain becomes

    out = view ⊎_{out_ids}  vals ⊗ Π_i src_i[ids_i]

over *flat planes* — every gather source (sibling-view payload planes and
lift relations alike) is a ``(plane [Sg, d], ids [B])`` pair, the degree-m
(c, s, Q) ring product runs as one fused flat formula
(:func:`ring_mul_flat`, replacing the per-bilinear-term einsum soup of
``Ring.mul``), and the final ⊎ goes through the one-hot path with
*per-tile dedup* (``ring_scatter.tile_dedup``).

Lowerings, chosen by :func:`resolve_backend` from the hint the plan
resolved for the chain's terminal ScatterAccum:

* ``fused_pallas`` — the TPU megakernel: grid ``(S/bs, B/bk)``, source
  planes ride whole in VMEM (the plan-time legality pass bounds them by
  :data:`MAX_FUSED_PLANE` rows and :data:`VMEM_BUDGET` bytes), each batch
  tile gathers via one-hot MXU contractions, ring-multiplies in registers,
  dedups in-tile, and accumulates into the revisited output block.  The
  ``[B, d]`` intermediate never exists in HBM.
* ``fused_compact`` — the same megakernel for a target view past the
  onehot/compact crossover (the hint ``compact``): the batch's out ids
  are ranked once (``scatter_ops.compact_ranks``), the kernel accumulates
  over the local ranks into a zero plane of B rows (grid ``(B/bs, B/bk)``
  instead of ``(S/bs, B/bk)``), and at most B distinct rows are added into
  the view in place.  The view never enters the kernel.
* ``fused_interpret`` / ``fused_compact_interpret`` — the same in Pallas
  interpret mode (CI).
* ``fused_xla`` — flat ``take``/multiply/``.at[].add`` over the same
  planes (CPU/GPU): still one fused pipeline per chain instead of one
  einsum per bilinear term and one scatter per ring component.

Padding and key linearization are the caller's problem only at the edges:
``fused_apply`` pads to block multiples internally; ids < 0 are padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ring_scatter import (EXACT, _col, _col_spec, _iota_cols, _row,
                           _row_spec, per_device, tile_dedup)
from .scatter_ops import compact_ranks

#: largest gathered-source plane (rows) a fused chain keeps whole in VMEM;
#: chains gathering from bigger planes stay unfused (op-by-op fallback)
MAX_FUSED_PLANE = 4096

#: VMEM budget (bytes) for one fused chain — the plan-time legality bound
#: computed by :func:`chain_vmem_bytes` must stay under it
VMEM_BUDGET = 8 * 1024 * 1024

#: nominal megakernel tile sizes (also the plan-time VMEM model's tiles)
BLOCK_S = 128
BLOCK_K = 256

BACKENDS = ("fused_xla", "fused_pallas", "fused_interpret",
            "fused_compact", "fused_compact_interpret")

#: the lowerings that ⊎ through the compact prepass instead of the sweep
COMPACT_BACKENDS = ("fused_compact", "fused_compact_interpret")


# ---------------------------------------------------------------------------
# Ring spec: which payload algebras the flat megakernel formula covers
# ---------------------------------------------------------------------------
def fused_ring_spec(ring):
    """Flat-payload descriptor of ``ring`` for the megakernel, or None when
    the ring is outside the fused algebra: ``("scalar",)`` for
    single-scalar-component rings, ``("degree", m)`` for the (c, s, Q)
    cofactor ring.  Requires a commutative bilinear f32 ring: gathered
    factors reorder past later lift-multiplies (so non-commutative matrix
    rings never fuse), and int rings keep the exact ``.at[].add`` path
    (count-ring bit-identity over speed)."""
    if ring.mul_terms is None or not ring.commutative:
        return None
    if jnp.dtype(ring.dtype) != jnp.float32:
        return None
    comps = ring.components
    shapes = list(comps.values())
    if len(comps) == 1 and shapes[0] == ():
        return ("scalar",)
    m = getattr(ring, "m", None)
    if (m and list(comps.keys()) == ["c", "s", "Q"]
            and shapes == [(), (m,), (m, m)]):
        return ("degree", int(m))
    return None


def spec_width(spec) -> int:
    """Payload plane width d of a fused ring spec."""
    if spec[0] == "scalar":
        return 1
    m = spec[1]
    return 1 + m + m * m


def ring_mul_flat(a, b, spec):
    """Ring product on flat ``[..., d]`` payload planes.

    For the degree-m ring the (c, s, Q) triple lives in one
    ``d = 1 + m + m²`` plane (c at column 0, s next, Q row-major) and the
    product

        (c_a c_b,  c_b s_a + c_a s_b,
         c_b Q_a + c_a Q_b + s_a s_bᵀ + s_b s_aᵀ)

    is a single fused formula instead of seven einsum terms.  Trailing
    padding columns (inputs wider than d) stay zero.  Term order matches
    ``Ring.mul``'s accumulation, so integer-valued f32 payloads multiply
    bit-identically to the einsum path."""
    if spec[0] == "scalar":
        return a * b
    m = spec[1]
    d = 1 + m + m * m
    ca, sa, qa = a[..., :1], a[..., 1:1 + m], a[..., 1 + m:d]
    cb, sb, qb = b[..., :1], b[..., 1:1 + m], b[..., 1 + m:d]
    c = ca * cb
    s = sa * cb + ca * sb
    # s_a s_bᵀ / s_b s_aᵀ row-major: Q row i is sa_i·sb resp. sb_i·sa.
    # Terms add one at a time in Ring.mul's accumulation order, so float
    # association matches the einsum path bit for bit.
    outer_ab = jnp.concatenate(
        [sa[..., i:i + 1] * sb for i in range(m)], axis=-1)
    outer_ba = jnp.concatenate(
        [sb[..., i:i + 1] * sa for i in range(m)], axis=-1)
    q = qa * cb + ca * qb
    q = q + outer_ab
    q = q + outer_ba
    out = jnp.concatenate([c, s, q], axis=-1)
    if a.shape[-1] > d:  # padded feature plane: keep the zero columns
        out = jnp.concatenate(
            [out, jnp.zeros((*out.shape[:-1], a.shape[-1] - d), out.dtype)],
            axis=-1)
    return out


# ---------------------------------------------------------------------------
# Plan-time VMEM model
# ---------------------------------------------------------------------------
def _round_up(x: int, m: int) -> int:
    return (max(int(x), 1) + m - 1) // m * m


def chain_vmem_bytes(src_rows, width: int, *, block_s: int = BLOCK_S,
                     block_k: int = BLOCK_K) -> int:
    """Modeled VMEM footprint (bytes) of one fused chain: every gather
    source plane whole, plus the view/output tiles, the batch-tile value
    planes, and the in-VMEM one-hot / dedup matrices.  Deterministic in
    the chain's static shapes — golden-plan tests pin it."""
    dp = _round_up(width, 128)
    rows = sum(_round_up(r, 8) for r in src_rows)
    n = len(tuple(src_rows))
    planes = dp * (rows + 2 * block_s + (2 + n) * block_k)
    onehots = block_k * (sum(_round_up(r, 8) for r in src_rows)
                         + block_k + block_s)
    return 4 * (planes + onehots)


# ---------------------------------------------------------------------------
# The megakernel
# ---------------------------------------------------------------------------
def _fused_kernel(*refs, block_s: int, n_src: int, spec):
    out_ids_ref, out_ids_row_ref, vals_ref = refs[0], refs[1], refs[2]
    id_refs = refs[3:3 + n_src]
    plane_refs = refs[3 + n_src:3 + 2 * n_src]
    view_ref, out_ref = refs[-2], refs[-1]
    si = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = view_ref[...].astype(jnp.float32)

    v = vals_ref[...].astype(jnp.float32)  # [bk, dp]
    bk = v.shape[0]
    for i in range(n_src):
        ids = id_refs[i][...]  # [bk, 1]
        plane = plane_refs[i][...].astype(jnp.float32)  # [Sg, dp] whole
        onehot = (ids == _iota_cols(bk, plane.shape[0])).astype(jnp.float32)
        g = jax.lax.dot_general(
            onehot, plane, (((1,), (0,)), ((), ())),
            precision=EXACT, preferred_element_type=jnp.float32)  # [bk, dp]
        v = ring_mul_flat(v, g, spec)
    mids, sums = tile_dedup(out_ids_ref[...], out_ids_row_ref[...], v)
    local = _iota_cols(bk, block_s, offset=si * block_s)
    oh_out = (mids == local).astype(jnp.float32)
    out_ref[...] += jax.lax.dot_general(
        oh_out, sums, (((0,), (0,)), ((), ())),
        precision=EXACT, preferred_element_type=jnp.float32)


def _fused_pallas(view_plane, out_ids, vals, sources, spec, *, block_s: int,
                  block_k: int, interpret: bool):
    S, d = view_plane.shape
    B = out_ids.shape[0]
    dp = _round_up(d, 128)
    bs = min(block_s, _round_up(S, 8))
    bk = min(block_k, _round_up(B, 8))
    Sp, Bp = _round_up(S, bs), _round_up(B, bk)

    def fpad(a, rows):
        return jnp.pad(a.astype(jnp.float32),
                       ((0, rows - a.shape[0]), (0, dp - a.shape[1])))

    id_args, plane_args = [], []
    for plane, ids in sources:
        plane_args.append(fpad(plane, _round_up(plane.shape[0], 8)))
        # gather-id pad rows index row 0; their value rows are ring-zero
        # and their out_ids are -1, so they contribute nothing
        id_args.append(_col(jnp.pad(ids.astype(jnp.int32), (0, Bp - B))))
    n_src = len(id_args)
    grid = (Sp // bs, Bp // bk)
    out_ids = jnp.pad(out_ids.astype(jnp.int32), (0, Bp - B),
                      constant_values=-1)
    in_specs = (
        [_col_spec(bk), _row_spec(bk),
         pl.BlockSpec((bk, dp), lambda s, k: (k, 0))]
        + [_col_spec(bk) for _ in range(n_src)]
        + [pl.BlockSpec((p.shape[0], dp), lambda s, k: (0, 0))
           for p in plane_args]
        + [pl.BlockSpec((bs, dp), lambda s, k: (s, 0))])
    out = per_device(pl.pallas_call(
        functools.partial(_fused_kernel, block_s=bs, n_src=n_src, spec=spec),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bs, dp), lambda s, k: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((Sp, dp), jnp.float32),
        interpret=interpret,
    ))(_col(out_ids), _row(out_ids), fpad(vals, Bp), *id_args, *plane_args,
       fpad(view_plane, Sp))
    return out[:S, :d]


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------
def resolve_backend(hint: str | None = None) -> str:
    """Lowering for a fused chain: the plan bakes its ScatterAccum's
    resolved scatter-backend hint in.  A ``compact`` hint (the target view
    is past the onehot/compact crossover) takes the compact megakernel;
    other ``*_interpret`` hints (CI forcing) the interpret-mode sweep; TPU
    gets the real sweep, and everything else the flat-XLA lowering."""
    if hint in BACKENDS:
        return hint
    if hint == "compact_interpret":
        return "fused_compact_interpret"
    if hint and hint.endswith("_interpret"):
        return "fused_interpret"
    if jax.default_backend() == "tpu":
        return "fused_compact" if hint == "compact" else "fused_pallas"
    return "fused_xla"


def fused_apply(view_plane, out_ids, vals, sources, spec, *,
                backend: str | None = None, block_s: int = BLOCK_S,
                block_k: int = BLOCK_K):
    """One fused chain over flat planes:

        out = view_plane ⊎_{out_ids} (vals ⊗ Π_i plane_i[ids_i])

    ``sources`` is a sequence of ``(plane [Sg, d], ids [B])`` gather
    sources — sibling-view payload planes and lift relations alike —
    applied left to right (plan-time legality guarantees a commutative
    ring).  ``out_ids`` rows < 0 drop.  Returns the new ``[S, d]`` f32
    plane."""
    b = resolve_backend(backend)
    interpret = b.endswith("_interpret")
    if b == "fused_xla":
        cur = vals
        for plane, ids in sources:
            g = jnp.take(plane, ids, axis=0, mode="clip")
            cur = ring_mul_flat(cur, g, spec)
        S = view_plane.shape[0]
        safe = jnp.where(out_ids < 0, S, out_ids)
        return view_plane.astype(jnp.float32).at[safe].add(
            cur.astype(jnp.float32), mode="drop")
    if b in COMPACT_BACKENDS:
        S, d = view_plane.shape
        B = out_ids.shape[0]
        rank, uniq = compact_ranks(out_ids, S)
        sums = _fused_pallas(jnp.zeros((B, d), jnp.float32), rank, vals,
                             tuple(sources), spec, block_s=block_s,
                             block_k=block_k, interpret=interpret)
        return view_plane.astype(jnp.float32).at[uniq].add(sums,
                                                            mode="drop")
    return _fused_pallas(view_plane, out_ids, vals, tuple(sources), spec,
                         block_s=block_s, block_k=block_k,
                         interpret=interpret)
