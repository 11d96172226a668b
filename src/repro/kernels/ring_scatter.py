"""Pallas TPU kernels for the ring scatter subsystem (⊎ into dense views).

F-IVM's trigger cost is dominated by ⊎ — scatter-adding a delta batch into
a materialized view — and the sibling gathers that feed it.  XLA lowers a
generic scatter to a per-row serialized loop on CPU/TPU; the TPU-native
formulation is a one-hot matmul that *accumulates into an existing view*,
so the whole ⊎ is one kernel:

  ``scatter_add_onehot``     out = view + 1h(ids)ᵀ · values
  ``segment_ring_sum``       out = 1h(ids)ᵀ · values  (the same, into zeros)
  ``gather_mul_scatter``     out = view + 1h(out_ids)ᵀ · (scale ⊙ 1h(in_ids) · src)

All build their one-hot blocks on the fly in VMEM (the one-hot matrix
never exists in HBM) and run the contraction on the MXU.  Grid =
(S/bs, d/bd, B/bk) with the batch innermost: the revisited output block is
initialized from the view block once (k == 0) and accumulated into across
batch tiles.  Out-of-range ids (padding, by convention ``-1``) match no
segment and contribute nothing.

``gather_mul_scatter`` fuses the sibling-view gather that produces the
delta payload (``BatchedDelta.join_dense`` followed by ``apply_to``) with
the scatter: the gather is itself a one-hot matmul against the full source
view, so the fused kernel is two MXU contractions per tile and the [B, d]
intermediate never exists in HBM.  The source view rides along whole on
the feature-blocked axis, so the dispatch layer (scatter_ops) only selects
this kernel when the source segment space fits VMEM.

Key linearization (multi-column COO keys -> flat segment ids), payload
pytree flattening, padding to block multiples, and backend choice all live
in ``scatter_ops.py`` — these kernels see only ``[S, d]`` f32 planes.

Per-row operands (ids, scales) enter as ``[B, 1]`` columns blocked
``(block_k, 1)``, and the in-tile dedup also takes the ids as a ``[1, B]``
row.  Mosaic refuses 1-D int32 blocks smaller than the array: XLA tiles a
1-D array of 1,024 or more elements ``T(1024)``, while the kernel operand
asks for ``T(block_k)``, so every batch that spans more than one id block
would fail to compile on the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec

#: contraction precision: f32 on the MXU (``repro.core.rings.EXACT``)
EXACT = jax.lax.Precision.HIGHEST


def per_device(call):
    """``call`` (a Pallas kernel) run whole on every device of the ambient
    mesh.  JAX cannot partition a Mosaic kernel: in a program over several
    devices it refuses one outside ``shard_map``.  The sharded stream
    executor traces under its mesh (``jax.set_mesh``), so there each kernel
    takes replicated operands -- GSPMD all-gathers a sharded view first --
    and returns the same replicated result on every device.  Without a
    multi-device mesh the kernel is called directly."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return call
    return jax.shard_map(call, mesh=mesh, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), check_vma=False)


def _iota_cols(rows: int, cols: int, offset=0):
    """[rows, cols] int32 where entry (r, c) = c + offset (2-D iota: TPU has
    no 1-D iota)."""
    it = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return it + offset


def _col(x):
    """[B] -> [B, 1]: the kernels' per-row operand layout."""
    return x.reshape(-1, 1)


def _row(x):
    """[B] -> [1, B]: the transposed id layout the in-tile dedup reads."""
    return x.reshape(1, -1)


def _col_spec(block_k: int):
    """(block_k, 1) blocks of a [B, 1] column, indexed by the last grid
    axis (the batch, innermost in every kernel here)."""
    return pl.BlockSpec((block_k, 1), lambda *g: (g[-1], 0))


def _row_spec(block_k: int):
    """(1, block_k) blocks of a [1, B] row, indexed like ``_col_spec``."""
    return pl.BlockSpec((1, block_k), lambda *g: (0, g[-1]))


def _scatter_kernel(ids_ref, vals_ref, view_ref, out_ref, *, block_s: int):
    si = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = view_ref[...].astype(jnp.float32)

    ids = ids_ref[...]  # [bk, 1] int32
    vals = vals_ref[...].astype(jnp.float32)  # [bk, bd]
    local = _iota_cols(ids.shape[0], block_s, offset=si * block_s)
    onehot = (ids == local).astype(jnp.float32)  # [bk, bs]
    out_ref[...] += jax.lax.dot_general(
        onehot, vals, (((0,), (0,)), ((), ())), precision=EXACT,
        preferred_element_type=jnp.float32)


def tile_dedup(ids, ids_row, vals):
    """Per-tile key dedup, entirely in VMEM: collapse duplicate ids within
    one batch tile onto their first occurrence.  ``ids`` is the tile's
    ``[bk, 1]`` id column and ``ids_row`` the same ids as a ``[1, bk]``
    row (the TPU has no cheap in-register transpose of an id column).

    Returns ``(mids, sums)`` where ``sums[i] = Σ_j [ids[j] == ids[i]] ·
    vals[j]`` for the first occurrence of each id and ``mids`` ``[bk, 1]``
    masks every later duplicate (and padding, ids < 0) to ``-1``.  The
    duplicate-sum is a 0/1 matmul, so integer-valued f32 payloads dedup
    exactly — this is the in-kernel replacement for the global sort/rank
    compaction prepass (``scatter_ops._compact_scatter``) on the fused
    plan path; the standalone compact backends keep the global prepass,
    whose O(B log B) sort amortizes when one dedup serves the whole
    batch."""
    bk = ids.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 1)
    eq = ids == ids_row
    # row i is its id's tile-first occurrence iff no earlier row matches
    first = ~jnp.any(eq & (col < row), axis=1, keepdims=True)  # [bk, 1]
    gather = (eq & first).astype(jnp.float32)
    sums = jax.lax.dot_general(
        gather, vals, (((1,), (0,)), ((), ())),
        precision=EXACT, preferred_element_type=jnp.float32)
    mids = jnp.where(first & (ids >= 0), ids, -1)
    return mids, sums


def _scatter_dedup_kernel(ids_ref, ids_row_ref, vals_ref, view_ref, out_ref,
                          *, block_s: int):
    si = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = view_ref[...].astype(jnp.float32)

    mids, sums = tile_dedup(ids_ref[...], ids_row_ref[...],
                            vals_ref[...].astype(jnp.float32))
    local = _iota_cols(mids.shape[0], block_s, offset=si * block_s)
    onehot = (mids == local).astype(jnp.float32)  # [bk, bs]
    out_ref[...] += jax.lax.dot_general(
        onehot, sums, (((0,), (0,)), ((), ())),
        precision=EXACT, preferred_element_type=jnp.float32)


def scatter_add_onehot(
    view: jnp.ndarray,
    seg_ids: jnp.ndarray,
    values: jnp.ndarray,
    *,
    block_s: int = 128,
    block_d: int = 128,
    block_k: int = 512,
    interpret: bool = False,
    dedup: bool = False,
):
    """view [S, d] + scatter of values [B, d] at seg_ids [B] -> [S, d] f32.
    S, d, B must be multiples of the block sizes (scatter_ops pads).
    ``dedup`` runs the per-tile key dedup before the one-hot contraction
    (the fused-plan variant; bit-identical on integer-valued payloads)."""
    S, d = view.shape
    B, d2 = values.shape
    assert d2 == d, (values.shape, view.shape)
    assert B % block_k == 0 and d % block_d == 0 and S % block_s == 0
    grid = (S // block_s, d // block_d, B // block_k)
    ids_args, ids_specs = [_col(seg_ids)], [_col_spec(block_k)]
    if dedup:
        ids_args.append(_row(seg_ids))
        ids_specs.append(_row_spec(block_k))
    kernel = _scatter_dedup_kernel if dedup else _scatter_kernel
    return per_device(pl.pallas_call(
        functools.partial(kernel, block_s=block_s),
        grid=grid,
        in_specs=ids_specs + [
            pl.BlockSpec((block_k, block_d), lambda s, j, k: (k, j)),
            pl.BlockSpec((block_s, block_d), lambda s, j, k: (s, j)),
        ],
        out_specs=pl.BlockSpec((block_s, block_d), lambda s, j, k: (s, j)),
        out_shape=jax.ShapeDtypeStruct((S, d), jnp.float32),
        interpret=interpret,
    ))(*ids_args, values, view)


def segment_ring_sum(values, seg_ids, num_segments: int, *,
                     block_s: int = 128, block_d: int = 128,
                     block_k: int = 512, interpret: bool = False):
    """Group-by ⊕ of a COO batch: values [B, d] at seg_ids [B] -> [S, d]
    f32, i.e. the one-hot scatter into a zero view.  B, d, S must be
    multiples of the block sizes (callers pad)."""
    zeros = jnp.zeros((num_segments, values.shape[1]), jnp.float32)
    return scatter_add_onehot(zeros, seg_ids, values, block_s=block_s,
                              block_d=block_d, block_k=block_k,
                              interpret=interpret)


def _gms_kernel(out_ids_ref, in_ids_ref, scale_ref, src_ref, view_ref, out_ref,
                *, block_s: int, num_src: int):
    si = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = view_ref[...].astype(jnp.float32)

    oid = out_ids_ref[...]  # [bk, 1]
    iid = in_ids_ref[...]  # [bk, 1]
    scale = scale_ref[...].astype(jnp.float32)  # [bk, 1]
    src = src_ref[...].astype(jnp.float32)  # [Sg, bd]
    bk = oid.shape[0]
    # gather = one-hot(in_ids) · src, built in VMEM, contracted on the MXU
    oh_in = (iid == _iota_cols(bk, num_src)).astype(jnp.float32)
    gathered = jax.lax.dot_general(
        oh_in, src, (((1,), (0,)), ((), ())), precision=EXACT,
        preferred_element_type=jnp.float32)  # [bk, bd]
    vals = gathered * scale
    oh_out = (oid == _iota_cols(bk, block_s, offset=si * block_s))
    out_ref[...] += jax.lax.dot_general(
        oh_out.astype(jnp.float32), vals, (((0,), (0,)), ((), ())),
        precision=EXACT, preferred_element_type=jnp.float32,
    )


def gather_mul_scatter(
    view: jnp.ndarray,
    out_ids: jnp.ndarray,
    src: jnp.ndarray,
    in_ids: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    block_s: int = 128,
    block_d: int = 128,
    block_k: int = 256,
    interpret: bool = False,
):
    """view [S, d] + Σ_b 1h(out_ids[b]) · (scale[b] · src[in_ids[b]]) -> [S, d].

    src [Sg, d] rides along whole on its segment axis (feature-blocked), so
    callers must ensure Sg fits VMEM (scatter_ops guards and falls back to
    gather-then-scatter otherwise).  Padding rows: out_ids/in_ids == -1 or
    scale == 0 contribute nothing."""
    S, d = view.shape
    Sg, d2 = src.shape
    B = out_ids.shape[0]
    assert d2 == d and in_ids.shape[0] == B and scale.shape[0] == B
    assert B % block_k == 0 and d % block_d == 0 and S % block_s == 0
    grid = (S // block_s, d // block_d, B // block_k)
    return per_device(pl.pallas_call(
        functools.partial(_gms_kernel, block_s=block_s, num_src=Sg),
        grid=grid,
        in_specs=[
            _col_spec(block_k),
            _col_spec(block_k),
            _col_spec(block_k),
            pl.BlockSpec((Sg, block_d), lambda s, j, k: (0, j)),
            pl.BlockSpec((block_s, block_d), lambda s, j, k: (s, j)),
        ],
        out_specs=pl.BlockSpec((block_s, block_d), lambda s, j, k: (s, j)),
        out_shape=jax.ShapeDtypeStruct((S, d), jnp.float32),
        interpret=interpret,
    ))(_col(out_ids), _col(in_ids), _col(scale), src, view)
