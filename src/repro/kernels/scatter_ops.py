"""Dispatch layer for the ring scatter subsystem (⊎ / gather-⊗-⊎).

Every view-maintenance trigger funnels its scatter-adds through here:
``DenseRelation.scatter_add`` (hence ``IVMEngine._bump_base`` and
``IndicatorState`` dense maintenance) and ``BatchedDelta.apply_to``.  The
layer owns everything the kernels in ``ring_scatter.py`` don't:

* **Key linearization + payload pytree shim** — multi-column COO keys
  ``[B, k]`` flatten to row-major segment ids and ring payloads flatten to
  a single ``[S, d]`` plane (the degree-m (c, s, Q) triple becomes one
  ``d = 1 + m + m²`` plane instead of three kernel launches).  Since the
  ViewStorage redesign this machinery is owned by the shared storage layer
  (``repro.core.storage`` — the hashed-COO backend stores views *as* that
  plane) and re-exported here.
* **Compaction** ("compact" backends) — for large segment spaces the
  one-hot grid over the full domain product is wasted work; a sort/rank
  pass dedups the batch's keys, a segment-sum over *local* ranks (grid
  scales with the batch, not the domain) accumulates duplicates, and a
  final scatter touches at most B unique rows.
* **Backend choice** — a cost heuristic on (payload width × batch ×
  segment space) picks the Pallas kernel flavour on TPU and the XLA
  ``.at[].add`` path on CPU; ``REPRO_SCATTER_BACKEND`` / ``use_backend``
  override it (tests force ``*_interpret``; CPU benches force
  ``compact_xla``).

All paths are pure jax — safe inside ``lax.scan``/``lax.switch`` trigger
bodies and compatible with the stream executor's state donation.  The
``jnp`` backend reproduces the legacy multi-index ``.at[idx].add`` exactly
(it *is* the old code), so kernel-off runs are bit-identical to the seed.

Backends:  ``jnp`` | ``onehot`` | ``compact`` | ``compact_xla`` |
``onehot_interpret`` | ``compact_interpret`` | ``onehot_dedup`` |
``onehot_dedup_interpret`` | ``auto``.  The ``onehot_dedup`` pair runs the
per-tile key dedup *inside* the one-hot kernel (the fused-plan variant —
no global sort/rank prepass); the plain backends keep the prepass.
"""
from __future__ import annotations

import contextlib
import functools
import json
import os

import jax
import jax.numpy as jnp

from repro.core.storage import (comp_width, flatten_payload, linear_ids,
                                rank_ids, unflatten_payload)

from . import ref
from .ring_scatter import gather_mul_scatter as _gms_pallas
from .ring_scatter import scatter_add_onehot as _scatter_pallas
from .ring_scatter import segment_ring_sum as _segsum_pallas

#: back-compat alias — the key-linearization / payload-plane shim is owned
#: by the storage layer (repro.core.storage) since the ViewStorage redesign
_comp_width = comp_width

ENV_VAR = "REPRO_SCATTER_BACKEND"

BACKENDS = ("auto", "jnp", "onehot", "compact", "compact_xla",
            "onehot_interpret", "compact_interpret",
            "onehot_dedup", "onehot_dedup_interpret")

#: largest source segment space the fused gather-multiply-scatter kernel
#: keeps whole in VMEM; larger sources fall back to gather-then-scatter
MAX_FUSED_SRC = 4096

_override: str | None = None


def set_backend(backend: str | None) -> None:
    """Process-wide backend override (None restores env/auto resolution)."""
    global _override
    assert backend is None or backend in BACKENDS, backend
    _override = backend


@contextlib.contextmanager
def use_backend(backend: str | None):
    """Scoped backend override — benches/tests sweep kernel-on vs kernel-off."""
    global _override
    prev = _override
    set_backend(backend)
    try:
        yield
    finally:
        _override = prev


def active_override() -> str | None:
    """The currently forced backend (``use_backend`` scope / ``set_backend``
    / env var), or None when resolution is the cost heuristic.  Part of the
    trigger-plan cache key (``repro.core.plan``): plans bake their resolved
    scatter backends in, so an override change must recompile them."""
    return _override or os.environ.get(ENV_VAR)


#: empirically measured onehot/compact crossovers (batch -> num_segments),
#: loaded from BENCH_kernels.json's ``onehot_compact_crossover`` row when
#: present; the cost heuristic prefers these over the modeled constant
_measured_crossover: dict[int, int] = {}


def set_measured_crossover(mapping: dict[int, int] | None) -> None:
    """Install measured crossover points (batch -> segment-count threshold);
    None clears back to the modeled constant."""
    _measured_crossover.clear()
    if mapping:
        _measured_crossover.update(
            {int(k): int(v) for k, v in mapping.items()})


def measured_crossover(batch: int) -> int | None:
    """Measured onehot/compact crossover for the closest benchmarked batch
    size, or None when no measurement is loaded."""
    if not _measured_crossover:
        return None
    key = min(_measured_crossover, key=lambda b: abs(b - batch))
    return _measured_crossover[key]


def load_measured_crossover(json_path) -> bool:
    """Load crossover measurements from a BENCH_kernels.json produced by
    ``benchmarks.bench_kernels`` (its ``onehot_compact_crossover`` result
    row).  Returns True when measurements were installed."""
    try:
        with open(json_path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return False
    for row in doc.get("results", []):
        if row.get("name") == "onehot_compact_crossover":
            pts = {int(p["batch"]): int(p["measured_crossover"])
                   for p in row.get("points", [])
                   if p.get("measured_crossover") is not None}
            if pts:
                set_measured_crossover(pts)
                return True
    return False


def resolve_backend(num_segments: int, batch: int, width: int,
                    backend: str | None = None) -> str:
    """Explicit arg > ``use_backend`` override > env var > cost heuristic."""
    b = backend or _override or os.environ.get(ENV_VAR) or "auto"
    assert b in BACKENDS, b
    if b != "auto":
        return b
    if jax.default_backend() != "tpu":
        return "jnp"
    # one-hot sweeps S·d accumulators per batch tile: worth it while the
    # segment space is comparable to the batch; past that, compaction's
    # O(B log B + B²·d/bk) beats the dead tiles of the full-domain grid.
    # A measured crossover (bench_kernels sweep) overrides the model.
    cross = measured_crossover(batch)
    if cross is None:
        cross = max(4096, 8 * batch)
    return "onehot" if num_segments <= cross else "compact"


def kernelable(ring, *payloads) -> bool:
    """Kernel paths accumulate in f32; any other dtype keeps the exact
    ``.at[].add`` path (count rings are int32 — bit-exactness over speed)."""
    if jnp.dtype(ring.dtype) != jnp.float32:
        return False
    return all(jnp.dtype(leaf.dtype) == jnp.float32
               for p in payloads for leaf in jax.tree.leaves(p))


# ---------------------------------------------------------------------------
# flat [S, d] entry points
# ---------------------------------------------------------------------------
def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def scatter_add_flat(view, seg_ids, values, backend: str | None = None,
                     block_s: int = 128, block_d: int = 128,
                     block_k: int = 512):
    """view [S, d] ⊎ values [B, d] at seg_ids [B]; ids < 0 are padding.

    Resolution happens here, *outside* the jitted impl, so the jit cache is
    keyed by the resolved backend string — an override change can never hit
    a stale trace."""
    S, d = view.shape
    B = seg_ids.shape[0]
    backend = resolve_backend(S, B, d, backend)
    return _scatter_add_flat(view, seg_ids, values, backend=backend,
                             block_s=block_s, block_d=block_d,
                             block_k=block_k)


@functools.partial(jax.jit, static_argnames=("backend", "block_s", "block_d",
                                             "block_k"))
def _scatter_add_flat(view, seg_ids, values, backend: str,
                      block_s: int, block_d: int, block_k: int):
    S, d = view.shape
    B = seg_ids.shape[0]
    if backend == "jnp":
        # negative ids wrap under XLA's drop mode; remap padding to an
        # out-of-range row so it actually drops (the kernel/compact
        # backends already treat ids < 0 as padding)
        return view.at[jnp.where(seg_ids < 0, S, seg_ids)].add(
            values, mode="drop")
    if backend.startswith("compact"):
        return _compact_scatter(view, seg_ids, values, backend,
                                block_s=block_s, block_d=block_d,
                                block_k=block_k)
    interpret = backend.endswith("_interpret")
    bs = min(block_s, _round_up(S, 8))
    bd = min(block_d, _round_up(d, 8))
    bk = min(block_k, _round_up(B, 8))
    Sp, dp, Bp = _round_up(S, bs), _round_up(d, bd), _round_up(B, bk)
    out = _scatter_pallas(
        jnp.pad(view.astype(jnp.float32), ((0, Sp - S), (0, dp - d))),
        jnp.pad(seg_ids.astype(jnp.int32), (0, Bp - B), constant_values=-1),
        jnp.pad(values.astype(jnp.float32), ((0, Bp - B), (0, dp - d))),
        block_s=bs, block_d=bd, block_k=bk, interpret=interpret,
        dedup="dedup" in backend,
    )
    return out[:S, :d]


def compact_ranks(seg_ids, num_segments: int):
    """The compact ⊎'s prepass (:func:`repro.core.storage.rank_ids`):
    each row's local rank, and the view row of each rank.  Unused rank
    slots and the padding rank (ids < 0, which rank first) point at
    ``num_segments``, out of range, so ``.at[uniq].add(..., mode="drop")``
    drops them."""
    rank, uniq = rank_ids(seg_ids.astype(jnp.int32))
    return rank, jnp.where(uniq < 0, num_segments, uniq)


def _compact_scatter(view, seg_ids, values, backend: str, *, block_s: int,
                     block_d: int, block_k: int):
    """Key-dedup + local accumulate: sort the batch's ids, rank distinct
    keys, segment-sum duplicates over *local* ranks (S_local = B — the grid
    scales with the batch's active segments, not the domain product), then
    scatter at most B unique rows.  Padding ids (< 0) rank first and map to
    an out-of-range target, so they drop."""
    S, d = view.shape
    B = seg_ids.shape[0]
    rank, uniq = compact_ranks(seg_ids, S)
    inner = {"compact": "pallas", "compact_interpret": "interpret",
             "compact_xla": "jnp"}[backend]
    if inner == "jnp":
        sums = ref.segment_ring_sum_ref(values, rank, B)
    else:
        bs = min(block_s, _round_up(B, 8))
        bd = min(block_d, _round_up(d, 8))
        bk = min(block_k, _round_up(B, 8))
        Bp, dp = _round_up(B, bk), _round_up(d, bd)
        Sl = _round_up(B, bs)
        sums = _segsum_pallas(
            jnp.pad(values.astype(jnp.float32), ((0, Bp - B), (0, dp - d))),
            jnp.pad(rank, (0, Bp - B), constant_values=-1),
            Sl, block_s=bs, block_d=bd, block_k=bk,
            interpret=(inner == "interpret"),
        )[:B, :d]
    return view.at[uniq].add(sums.astype(view.dtype), mode="drop")


def gather_mul_scatter_flat(view, out_ids, src, in_ids, scale,
                            backend: str | None = None, block_s: int = 128,
                            block_d: int = 128, block_k: int = 256):
    """view [S, d] ⊎ (scale[b] · src[in_ids[b]]) at out_ids[b] — the fused
    sibling-gather ⊗ scatter of ``BatchedDelta.apply_to``."""
    backend = resolve_backend(view.shape[0], out_ids.shape[0], view.shape[1],
                              backend)
    return _gather_mul_scatter_flat(view, out_ids, src, in_ids, scale,
                                    backend=backend, block_s=block_s,
                                    block_d=block_d, block_k=block_k)


@functools.partial(jax.jit, static_argnames=("backend", "block_s", "block_d",
                                             "block_k"))
def _gather_mul_scatter_flat(view, out_ids, src, in_ids, scale,
                             backend: str, block_s: int, block_d: int,
                             block_k: int):
    S, d = view.shape
    Sg = src.shape[0]
    B = out_ids.shape[0]
    if backend == "jnp":
        vals = jnp.take(src, in_ids, axis=0, mode="clip") * scale[:, None]
        return view.at[jnp.where(out_ids < 0, S, out_ids)].add(
            vals, mode="drop")
    if backend.startswith("compact") or Sg > MAX_FUSED_SRC:
        # compaction dedups output keys; the gather stays separate
        vals = jnp.take(src, in_ids, axis=0, mode="clip") * scale[:, None]
        return scatter_add_flat(view, out_ids, vals, backend=backend,
                                block_s=block_s, block_d=block_d,
                                block_k=block_k)
    interpret = backend == "onehot_interpret"
    bs = min(block_s, _round_up(S, 8))
    bd = min(block_d, _round_up(d, 8))
    bk = min(block_k, _round_up(B, 8))
    Sp, dp, Bp = _round_up(S, bs), _round_up(d, bd), _round_up(B, bk)
    Sgp = _round_up(Sg, 8)
    out = _gms_pallas(
        jnp.pad(view.astype(jnp.float32), ((0, Sp - S), (0, dp - d))),
        jnp.pad(out_ids.astype(jnp.int32), (0, Bp - B), constant_values=-1),
        jnp.pad(src.astype(jnp.float32), ((0, Sgp - Sg), (0, dp - d))),
        jnp.pad(in_ids.astype(jnp.int32), (0, Bp - B), constant_values=-1),
        jnp.pad(scale.astype(jnp.float32), (0, Bp - B)),
        block_s=bs, block_d=bd, block_k=bk, interpret=interpret,
    )
    return out[:S, :d]


# ---------------------------------------------------------------------------
# payload-pytree entry points (what the core calls)
# ---------------------------------------------------------------------------
def scatter_add_payload(view_payload, domains, keys, values, ring,
                        backend: str | None = None):
    """``view ⊎ COO batch`` over a ring-payload pytree.

    view_payload leaves: ``[*domains, *comp]``; keys ``[B, k]``; values
    leaves ``[B, *comp]``.  Returns a new payload dict.
    """
    domains = tuple(int(x) for x in domains)
    S = _comp_width(domains)
    B = keys.shape[0]
    d = sum(_comp_width(shp) for shp in ring.components.values())
    resolved = resolve_backend(S, B, d, backend)
    if resolved == "jnp" or not kernelable(ring, view_payload, values):
        idx = tuple(keys[:, i] for i in range(keys.shape[1]))
        return {c: view_payload[c].at[idx].add(values[c])
                for c in ring.components}
    ids = linear_ids(keys, domains)
    flat_view = flatten_payload(ring, view_payload, domains)
    flat_vals = flatten_payload(ring, values, (B,))
    out = scatter_add_flat(flat_view, ids, flat_vals, backend=resolved)
    return unflatten_payload(ring, out, domains, dtype=ring.dtype)


def gather_mul_scatter_payload(view_payload, domains, keys, src_plane,
                               in_ids, scale, ring,
                               backend: str | None = None):
    """``view ⊎ (scale ⊗ src[in_ids])`` for single-scalar-component rings —
    the deferred sibling gather of ``BatchedDelta.join_dense`` fused with
    the final scatter.  ``src_plane``: [Sg, 1] flattened source payload
    plane (dense views flatten whole; sparse views append a zero row that
    missed probes index)."""
    comp = next(iter(ring.components))
    assert len(ring.components) == 1 and ring.components[comp] == (), (
        "fused gather-scatter serves scalar payload rings only")
    domains = tuple(int(x) for x in domains)
    S = _comp_width(domains)
    B = keys.shape[0]
    resolved = resolve_backend(S, B, 1, backend)
    if resolved == "jnp" or not kernelable(ring, view_payload) \
            or jnp.dtype(src_plane.dtype) != jnp.float32:
        idx = tuple(keys[:, i] for i in range(keys.shape[1]))
        vals = scale * jnp.take(src_plane[:, 0], in_ids, axis=0, mode="clip")
        return {comp: view_payload[comp].at[idx].add(vals)}
    ids = linear_ids(keys, domains)
    out = gather_mul_scatter_flat(
        view_payload[comp].reshape(S, 1), ids, src_plane,
        in_ids.astype(jnp.int32), scale, backend=resolved)
    return {comp: out.reshape(domains).astype(ring.dtype)}


def gather_ringmul_scatter_payload(view_payload, domains, keys, src_plane,
                                   in_ids, delta_payload, ring,
                                   backend: str | None = None):
    """``view ⊎ (delta ⊗ src[in_ids])`` for bilinear non-scalar rings: one
    flat gather of the concatenated component plane, a row-wise ring
    product, then the ordinary payload scatter (which dispatches to the
    kernels).  The Pallas-fused single-kernel path stays scalar-only; this
    is the multi-component analogue of the deferred sibling gather."""
    B = keys.shape[0]
    g = jnp.take(src_plane, in_ids.astype(jnp.int32), axis=0, mode="clip")
    gp = unflatten_payload(ring, g, (B,), dtype=ring.dtype)
    vals = ring.mul(delta_payload, gp)
    return scatter_add_payload(view_payload, domains, keys, vals, ring,
                               backend=backend)
