"""Jit'd public wrappers around the Pallas kernels.

Each op pads inputs to block multiples, dispatches to the Pallas kernel
(compiled on TPU; ``interpret=True`` on CPU for validation) or to the jnp
reference path, and unpads.  ``backend=`` : "pallas" | "interpret" | "jnp".
On this CPU container the default is "jnp" (XLA), with interpret mode used
by the kernel test suite; on TPU the default flips to "pallas".

The ring scatter subsystem (⊎ into materialized views — the hot path of
every view-maintenance trigger) lives in ``scatter_ops.py``: it adds key
linearization, a payload-pytree shim, key-dedup compaction, and a cost
heuristic on top of the ``ring_scatter.py`` kernels, and is what the core
(``DenseRelation.scatter_add`` / ``BatchedDelta.apply_to``) calls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .cofactor_update import cofactor_update as _cofactor_pallas
from .flash_attention import flash_attention as _flash_pallas
from .rank1_chain import matvec as _matvec_pallas
from .rank1_chain import outer_accumulate as _outer_pallas
from .ring_mul import ring_mul as _ring_mul_pallas
from .ring_scatter import segment_ring_sum as _segsum_pallas


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("backend", "block_m", "block_k"))
def cofactor_update(x, w, backend: str | None = None, block_m: int = 128,
                    block_k: int = 256):
    """(c, s, Q) sufficient statistics of a weighted tuple batch."""
    backend = backend or default_backend()
    if backend == "jnp":
        c, s, Q = ref.cofactor_update_ref(x, w)
        return c[None], s, Q
    B, m = x.shape
    bm = min(block_m, _round_up(m, 8))
    bk = min(block_k, _round_up(B, 8))
    Bp, mp = _round_up(B, bk), _round_up(m, bm)
    xp = jnp.pad(x, ((0, Bp - B), (0, mp - m)))
    wp = jnp.pad(w, (0, Bp - B))
    c, s, Q = _cofactor_pallas(xp, wp, block_m=bm, block_k=bk,
                               interpret=(backend == "interpret"))
    return c, s[:m], Q[:m, :m]


@functools.partial(jax.jit, static_argnames=("backend", "block_m"))
def ring_mul(ca, sa, Qa, cb, sb, Qb, backend: str | None = None, block_m: int = 128):
    """Batched degree-m ring product."""
    backend = backend or default_backend()
    if backend == "jnp":
        return ref.ring_mul_ref(ca, sa, Qa, cb, sb, Qb)
    K, m = sa.shape
    bm = min(block_m, _round_up(m, 8))
    mp = _round_up(m, bm)
    pad2 = ((0, 0), (0, mp - m))
    pad3 = ((0, 0), (0, mp - m), (0, mp - m))
    c, s, Q = _ring_mul_pallas(
        ca, jnp.pad(sa, pad2), jnp.pad(Qa, pad3),
        cb, jnp.pad(sb, pad2), jnp.pad(Qb, pad3),
        block_m=bm, interpret=(backend == "interpret"),
    )
    return c, s[:, :m], Q[:, :m, :m]


@functools.partial(jax.jit, static_argnames=("num_segments", "backend", "block_s",
                                             "block_d", "block_k"))
def segment_ring_sum(values, seg_ids, num_segments: int, backend: str | None = None,
                     block_s: int = 128, block_d: int = 128, block_k: int = 512):
    """Segment-sum payload rows into ``num_segments`` groups."""
    backend = backend or default_backend()
    if backend == "jnp":
        return ref.segment_ring_sum_ref(values, seg_ids, num_segments)
    B, d = values.shape
    bs = min(block_s, _round_up(num_segments, 8))
    bd = min(block_d, _round_up(d, 8))
    bk = min(block_k, _round_up(B, 8))
    Bp, dp, Sp = _round_up(B, bk), _round_up(d, bd), _round_up(num_segments, bs)
    out = _segsum_pallas(
        jnp.pad(values, ((0, Bp - B), (0, dp - d))),
        jnp.pad(seg_ids, (0, Bp - B), constant_values=-1),
        Sp, block_s=bs, block_d=bd, block_k=bk,
        interpret=(backend == "interpret"),
    )
    return out[:num_segments, :d]


@functools.partial(jax.jit, static_argnames=("backend", "block"))
def matvec(A, x, backend: str | None = None, block: int = 256):
    backend = backend or default_backend()
    if backend == "jnp":
        return ref.matvec_ref(A, x)
    n, k = A.shape
    bm = min(block, _round_up(n, 8))
    bk = min(block, _round_up(k, 8))
    np_, kp = _round_up(n, bm), _round_up(k, bk)
    out = _matvec_pallas(jnp.pad(A, ((0, np_ - n), (0, kp - k))), jnp.pad(x, (0, kp - k)),
                         block_m=bm, block_k=bk, interpret=(backend == "interpret"))
    return out[:n]


@functools.partial(jax.jit, static_argnames=("backend", "block"))
def rank1_chain_update(A1, u, v, A3, V, backend: str | None = None, block: int = 256):
    """V += (A1 u)(vᵀ A3) — O(p²) factorized chain delta (Example 7.1)."""
    backend = backend or default_backend()
    if backend == "jnp":
        return ref.rank1_chain_ref(A1, u, v, A3, V)
    u2 = matvec(A1, u, backend=backend, block=block)
    v2 = matvec(A3.T, v, backend=backend, block=block)
    n, m = V.shape
    bm = min(block, _round_up(n, 8))
    bn = min(block, _round_up(m, 8))
    np_, mp = _round_up(n, bm), _round_up(m, bn)
    out = _outer_pallas(
        jnp.pad(V.astype(jnp.float32), ((0, np_ - n), (0, mp - m))),
        jnp.pad(u2, (0, np_ - n)), jnp.pad(v2, (0, mp - m)),
        block_m=bm, block_n=bn, interpret=(backend == "interpret"),
    )
    return out[:n, :m]


@functools.partial(jax.jit, static_argnames=("causal", "backend", "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, backend: str | None = None,
                    block_q: int = 128, block_k: int = 128):
    """q [B,H,T,D], k/v [B,Hkv,Tk,D] -> [B,H,T,D].  GQA via head grouping."""
    backend = backend or default_backend()
    if backend == "jnp":
        return ref.flash_attention_ref(q, k, v, causal=causal)
    B, H, T, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    rep = H // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    bq = min(block_q, _round_up(T, 8))
    bk = min(block_k, _round_up(Tk, 8))
    Tp, Tkp = _round_up(T, bq), _round_up(Tk, bk)
    # padded keys are masked by causality (they sit after every real query);
    # non-causal callers must supply block-aligned Tk
    assert causal or Tkp == Tk, "non-causal flash requires block-aligned kv length"
    qf = jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0))).reshape(B * H, Tp, D)
    kf = jnp.pad(k, ((0, 0), (0, 0), (0, Tkp - Tk), (0, 0))).reshape(B * H, Tkp, D)
    vf = jnp.pad(v, ((0, 0), (0, 0), (0, Tkp - Tk), (0, 0))).reshape(B * H, Tkp, D)
    # padded K positions must not contribute: with causal masking, padded
    # keys sit after all real queries only if Tk == T; otherwise mask via
    # large-negative trick is handled by causal positions (Tk pads > T pads).
    out = _flash_pallas(qf, kf, vf, causal=causal, scale=1.0 / (D ** 0.5),
                        block_q=bq, block_k=bk,
                        interpret=(backend == "interpret"))
    return out.reshape(B, H, Tp, D)[:, :, :T]
