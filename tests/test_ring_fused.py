"""Fused megakernel runtime ≡ the op-by-op oracle, bit for bit.

``repro.kernels.ring_fused`` is the runtime of the plan-level fusion pass
(DESIGN.md §13): one Gather→Lift→JoinContract→(Marginalize)→ScatterAccum
chain becomes one kernel over flat payload planes.  These tests pin its
pieces to the unfused primitives they replace:

* :func:`ring_mul_flat` against ``Ring.mul``'s einsum path — bit-identical
  float association on integer-valued f32 payloads, scalar and degree-m;
* :func:`fused_apply` (flat-XLA and the interpret-mode Pallas sweep and
  compact lowerings) against the compose-by-hand ``take`` / ``Ring.mul``
  / ``.at[].add`` oracle, with duplicate out-ids and padding rows;
* the plan-time VMEM model's determinism (golden plans pin its numbers).
"""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import DegreeMRing, sum_ring
from repro.core import storage
from repro.kernels import ring_fused

FUSED_BACKENDS = ("fused_xla", "fused_interpret", "fused_compact_interpret")


def _int_floats(rng, shape, lo=-4, hi=5):
    return jnp.asarray(rng.integers(lo, hi, size=shape).astype(np.float32))


def _int_payload(rng, ring, lead):
    return {c: _int_floats(rng, (*lead, *shp))
            for c, shp in ring.components.items()}


# ---------------------------------------------------------------------------
# ring_mul_flat ≡ Ring.mul
# ---------------------------------------------------------------------------
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(1, 5),
       B=st.integers(1, 9))
@settings(max_examples=8, deadline=None)
def test_ring_mul_flat_matches_einsum_degree_m(seed, m, B):
    rng = np.random.default_rng(seed)
    ring = DegreeMRing(m)
    a, b = _int_payload(rng, ring, (B,)), _int_payload(rng, ring, (B,))
    fa = storage.flatten_payload(ring, a, (B,))
    fb = storage.flatten_payload(ring, b, (B,))
    got = storage.unflatten_payload(
        ring, ring_mul := ring_fused.ring_mul_flat(
            fa, fb, ("degree", m)), (B,))
    exp = ring.mul(a, b)
    assert ring_mul.shape == (B, ring_fused.spec_width(("degree", m)))
    for c in ring.components:
        np.testing.assert_array_equal(np.asarray(got[c]), np.asarray(exp[c]),
                                      err_msg=c)


def test_ring_mul_flat_scalar_and_padded_columns():
    rng = np.random.default_rng(2)
    a, b = _int_floats(rng, (6, 1)), _int_floats(rng, (6, 1))
    np.testing.assert_array_equal(
        np.asarray(ring_fused.ring_mul_flat(a, b, ("scalar",))),
        np.asarray(a * b))
    # padded feature planes (the in-kernel case): zero columns stay zero
    m = 2
    d = ring_fused.spec_width(("degree", m))
    ring = DegreeMRing(m)
    pa = storage.flatten_payload(ring, _int_payload(rng, ring, (4,)), (4,))
    pb = storage.flatten_payload(ring, _int_payload(rng, ring, (4,)), (4,))
    wide_a = jnp.pad(pa, ((0, 0), (0, 128 - d)))
    wide_b = jnp.pad(pb, ((0, 0), (0, 128 - d)))
    wide = ring_fused.ring_mul_flat(wide_a, wide_b, ("degree", m))
    assert wide.shape == (4, 128)
    np.testing.assert_array_equal(
        np.asarray(wide[:, :d]),
        np.asarray(ring_fused.ring_mul_flat(pa, pb, ("degree", m))))
    np.testing.assert_array_equal(np.asarray(wide[:, d:]), 0.0)


def test_fused_ring_spec_classification():
    assert ring_fused.fused_ring_spec(sum_ring()) == ("scalar",)
    assert ring_fused.fused_ring_spec(DegreeMRing(3)) == ("degree", 3)
    from repro.core import MatrixRing, count_ring
    assert ring_fused.fused_ring_spec(count_ring()) is None  # int dtype
    assert ring_fused.fused_ring_spec(MatrixRing(2)) is None  # non-commut.


# ---------------------------------------------------------------------------
# fused_apply ≡ take / mul / .at[].add composed by hand
# ---------------------------------------------------------------------------
def _oracle(view_plane, out_ids, vals, sources, ring):
    lead = (vals.shape[0],)
    cur = storage.unflatten_payload(ring, vals, lead)
    for plane, ids in sources:
        g = storage.unflatten_payload(ring, jnp.take(plane, ids, axis=0),
                                      lead)
        cur = ring.mul(cur, g)
    flat = storage.flatten_payload(ring, cur, lead)
    S = view_plane.shape[0]
    safe = jnp.where(out_ids < 0, S, out_ids)
    return view_plane.at[safe].add(flat, mode="drop")


@pytest.mark.parametrize("backend", FUSED_BACKENDS)
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(1, 3),
       n_src=st.integers(0, 3))
@settings(max_examples=6, deadline=None)
def test_fused_apply_matches_oracle_degree_m(backend, seed, m, n_src):
    rng = np.random.default_rng(seed)
    ring = DegreeMRing(m)
    spec = ("degree", m)
    d = ring_fused.spec_width(spec)
    S, B = int(rng.integers(2, 20)), int(rng.integers(1, 30))
    view = _int_floats(rng, (S, d))
    vals = _int_floats(rng, (B, d), -2, 3)
    out_ids = jnp.asarray(rng.integers(0, S, size=B).astype(np.int32))
    sources = []
    for _ in range(n_src):
        Sg = int(rng.integers(1, 15))
        sources.append((_int_floats(rng, (Sg, d), -2, 3),
                        jnp.asarray(rng.integers(0, Sg, B).astype(np.int32))))
    got = ring_fused.fused_apply(view, out_ids, vals, sources, spec,
                                 backend=backend)
    exp = _oracle(view, out_ids, vals, sources, ring)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


@pytest.mark.parametrize("backend", FUSED_BACKENDS)
def test_fused_apply_duplicates_and_padding(backend):
    """Heavy duplicate out-ids exercise the in-tile dedup; -1 rows drop."""
    rng = np.random.default_rng(7)
    ring = sum_ring()
    S, B = 5, 40
    view = _int_floats(rng, (S, 1))
    vals = _int_floats(rng, (B, 1))
    out_ids = jnp.asarray(rng.integers(0, 2, size=B).astype(np.int32))
    src = (_int_floats(rng, (6, 1)),
           jnp.asarray(rng.integers(0, 6, B).astype(np.int32)))
    exp = _oracle(view, out_ids, vals, [src], ring)
    got = ring_fused.fused_apply(view, out_ids, vals, [src], ("scalar",),
                                 backend=backend)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    # padding rows: out_id -1 with ring-zero vals are exact no-ops
    ids_p = jnp.concatenate([out_ids, jnp.full((9,), -1, jnp.int32)])
    vals_p = jnp.concatenate([vals, jnp.zeros((9, 1), jnp.float32)])
    src_p = (src[0], jnp.concatenate([src[1],
                                      jnp.zeros((9,), jnp.int32)]))
    got_p = ring_fused.fused_apply(view, ids_p, vals_p, [src_p], ("scalar",),
                                   backend=backend)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(exp))


@pytest.mark.parametrize("backend", ["fused_interpret",
                                     "fused_compact_interpret"])
def test_fused_apply_multi_tile_interpret(backend):
    """Shapes past one (block_s, block_k) tile: revisited output blocks
    accumulate across batch tiles."""
    rng = np.random.default_rng(9)
    ring = sum_ring()
    S, B = 70, 130
    view = _int_floats(rng, (S, 1))
    vals = _int_floats(rng, (B, 1))
    out_ids = jnp.asarray(rng.integers(0, S, size=B).astype(np.int32))
    src = (_int_floats(rng, (33, 1)),
           jnp.asarray(rng.integers(0, 33, B).astype(np.int32)))
    exp = _oracle(view, out_ids, vals, [src], ring)
    got = ring_fused.fused_apply(view, out_ids, vals, [src], ("scalar",),
                                 backend=backend, block_s=32, block_k=64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_compact_fused_apply_large_target():
    """A 2^16-row view and a 256-row batch: the compact lowering adds into
    the rows the batch touches, exactly as the ``.at[].add`` oracle does,
    and leaves every other row bit for bit as it was."""
    rng = np.random.default_rng(16)
    ring = sum_ring()
    S, B = 1 << 16, 256
    # dyadic values: every sum is exact in f32, in any order
    view = jnp.asarray((rng.integers(-1000, 1000, size=(S, 1))
                        + 0.25 * rng.integers(0, 4, size=(S, 1)))
                       .astype(np.float32))
    vals = _int_floats(rng, (B, 1))
    hot = rng.integers(0, S, size=B // 4)
    out_ids = np.where(rng.random(B) < 0.5, rng.choice(hot, size=B),
                       rng.integers(0, S, size=B)).astype(np.int32)
    out_ids[-5:] = -1  # padding rows drop
    src = (_int_floats(rng, (8, 1)),
           jnp.asarray(rng.integers(0, 8, B).astype(np.int32)))
    got = np.asarray(ring_fused.fused_apply(
        view, jnp.asarray(out_ids), vals, [src], ("scalar",),
        backend="fused_compact_interpret"))
    exp = np.asarray(_oracle(view, jnp.asarray(out_ids), vals, [src], ring))
    touched = np.zeros(S, bool)
    touched[out_ids[out_ids >= 0]] = True
    assert 0 < touched.sum() <= B
    np.testing.assert_array_equal(got[touched], exp[touched])
    np.testing.assert_array_equal(got[~touched], np.asarray(view)[~touched])


# ---------------------------------------------------------------------------
# plan-time VMEM model
# ---------------------------------------------------------------------------
def test_chain_vmem_model_deterministic_and_monotone():
    a = ring_fused.chain_vmem_bytes((100, 200), 13)
    assert a == ring_fused.chain_vmem_bytes((100, 200), 13)
    assert ring_fused.chain_vmem_bytes((100, 200, 300), 13) > a
    assert ring_fused.chain_vmem_bytes((100, 200), 130) > a


def test_resolve_backend_hints(monkeypatch):
    assert ring_fused.resolve_backend("fused_interpret") == "fused_interpret"
    assert ring_fused.resolve_backend("onehot_interpret") == "fused_interpret"
    assert ring_fused.resolve_backend(
        "onehot_dedup_interpret") == "fused_interpret"
    assert ring_fused.resolve_backend(
        "compact_interpret") == "fused_compact_interpret"
    assert ring_fused.resolve_backend("fused_compact") == "fused_compact"
    import jax
    if jax.default_backend() != "tpu":
        assert ring_fused.resolve_backend(None) == "fused_xla"
        assert ring_fused.resolve_backend("jnp") == "fused_xla"
        assert ring_fused.resolve_backend("compact") == "fused_xla"
    # on the chip the plan's scatter hint picks sweep or compact ⊎
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ring_fused.resolve_backend("compact") == "fused_compact"
    for hint in (None, "onehot", "onehot_dedup"):
        assert ring_fused.resolve_backend(hint) == "fused_pallas", hint
    assert ring_fused.resolve_backend(
        "onehot_interpret") == "fused_interpret"
