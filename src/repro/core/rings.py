"""Rings for F-IVM payloads.

A relation in F-IVM maps keys (tuples of attribute values) to payloads drawn
from a ring (D, +, *, 0, 1).  The key computation (joins, marginalization,
delta propagation) is ring-independent; plugging a different ring retargets
the same view tree to a different task (Sec. 2 / Sec. 7 of the paper).

TPU adaptation: every ring product used by the paper is *bilinear* in the
payload components.  We expose that bilinearity as ``mul_terms`` so that a
join-marginalization over dense dictionary-encoded key tensors decomposes
into a fixed set of ``jnp.einsum`` contractions (see contraction.py), which
XLA maps onto the MXU.  Payloads are pytrees (dicts of arrays): each
component leaf has shape ``[*key_dims, *payload_shape]``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: precision of every ring contraction.  XLA:TPU's default runs an f32
#: dot as one bf16 pass (8 significant bits), which rounds cofactor
#: payloads; HIGHEST keeps f32 semantics.  CPU dots are f32 either way.
EXACT = jax.lax.Precision.HIGHEST

Payload = Any  # pytree: dict[str, jnp.ndarray]


@dataclasses.dataclass(frozen=True)
class MulTerm:
    """One bilinear term of the ring product.

    out[comp_out][..., out_subs] += coef * a[comp_a][..., a_subs] * b[comp_b][..., b_subs]

    Subscripts refer to *payload* axes only (key axes are handled by the
    contraction engine).  Example (degree-m ring, Def. 7.2):
      Q_out += s_a s_b^T  ->  MulTerm("Q", "s", "s", "i", "j", "ij")
    """

    comp_out: str
    comp_a: str
    comp_b: str
    a_subs: str
    b_subs: str
    out_subs: str
    coef: float = 1.0


class Ring:
    """Base class.  Subclasses define components, identities, lift, mul."""

    name: str = "abstract"
    #: mapping component name -> payload shape (tuple of ints)
    components: Mapping[str, tuple] = {}
    #: bilinear expansion of * ; None means use generic `mul`
    mul_terms: Sequence[MulTerm] | None = None
    #: dtype for payload leaves
    dtype: Any = jnp.float32
    commutative: bool = True

    # Rings ride along as pytree aux metadata (DenseRelation, COOUpdate) and
    # therefore in jit cache keys and scan-carry treedefs.  Two structurally
    # identical rings built by separate calls (e.g. sum_ring() in a query
    # and in a database loader) must compare equal, or a scan carry built
    # from one would mismatch trigger output built with the other.
    def _identity(self):
        return (
            type(self).__name__,
            self.name,
            str(jnp.dtype(self.dtype)),
            tuple((k, tuple(shp)) for k, shp in self.components.items()),
        )

    def __eq__(self, other):
        return isinstance(other, Ring) and self._identity() == other._identity()

    def __hash__(self):
        return hash(self._identity())

    # -- construction ------------------------------------------------------
    def zeros(self, key_shape: Sequence[int] = ()) -> Payload:
        return {
            k: jnp.zeros((*key_shape, *shp), self.dtype)
            for k, shp in self.components.items()
        }

    def ones(self, key_shape: Sequence[int] = ()) -> Payload:
        raise NotImplementedError

    # -- ring ops (componentwise add; mul may be overridden) ---------------
    def add(self, a: Payload, b: Payload) -> Payload:
        return jax.tree.map(jnp.add, a, b)

    def neg(self, a: Payload) -> Payload:
        return jax.tree.map(jnp.negative, a)

    def sub(self, a: Payload, b: Payload) -> Payload:
        return self.add(a, self.neg(b))

    def mul(self, a: Payload, b: Payload) -> Payload:
        """Elementwise (over key dims, broadcasting) ring product."""
        if self.mul_terms is None:
            raise NotImplementedError
        out: dict[str, jnp.ndarray] = {}
        for t in self.mul_terms:
            x, y = a[t.comp_a], b[t.comp_b]
            # align payload axes via einsum on payload dims, broadcasting keys
            na, nb = len(t.a_subs), len(t.b_subs)
            kx = x.ndim - na
            ky = y.ndim - nb
            nk = max(kx, ky)
            # pad key dims to common rank
            x = x.reshape((1,) * (nk - kx) + x.shape)
            y = y.reshape((1,) * (nk - ky) + y.shape)
            key_letters = "".join(chr(ord("A") + i) for i in range(nk))
            spec = (
                f"...{t.a_subs},...{t.b_subs}->...{t.out_subs}"
                if nk == 0
                else f"{key_letters}{t.a_subs},{key_letters}{t.b_subs}->{key_letters}{t.out_subs}"
            )
            # broadcasting across key dims: einsum requires equal dims, so
            # broadcast manually first.
            kshape = tuple(
                max(x.shape[i], y.shape[i]) for i in range(nk)
            )
            x = jnp.broadcast_to(x, kshape + x.shape[nk:])
            y = jnp.broadcast_to(y, kshape + y.shape[nk:])
            term = jnp.einsum(spec, x, y, precision=EXACT) * (
                t.coef if t.coef != 1.0 else 1.0)
            out[t.comp_out] = out.get(t.comp_out, 0) + term
        # fill in components never produced (stay zero)
        any_k = next(iter(out))
        key_shape = out[any_k].shape[: out[any_k].ndim - len(self.components[any_k])]
        for k, shp in self.components.items():
            if k not in out:
                out[k] = jnp.zeros((*key_shape, *shp), self.dtype)
        return out

    # -- lifting ------------------------------------------------------------
    def lift(self, values: jnp.ndarray, var_index: int | None = None) -> Payload:
        """Lifting function g_X applied elementwise to an array of key values.

        Returns a payload with key shape = values.shape.
        """
        raise NotImplementedError

    # -- predicates ----------------------------------------------------------
    def is_zero(self, a: Payload, atol: float = 0.0) -> jnp.ndarray:
        """Boolean array over key dims: True where payload == ring zero."""
        flags = None
        for k, shp in self.components.items():
            x = a[k]
            axes = tuple(range(x.ndim - len(shp), x.ndim))
            f = (
                jnp.all(jnp.abs(x) <= atol, axis=axes)
                if axes
                else jnp.abs(x) <= atol
            )
            flags = f if flags is None else flags & f
        return flags

    def allclose(self, a: Payload, b: Payload, rtol=1e-5, atol=1e-6) -> bool:
        ok = True
        for k in self.components:
            ok = ok & jnp.allclose(a[k], b[k], rtol=rtol, atol=atol)
        return bool(ok)

    def scale(self, a: Payload, factor) -> Payload:
        """Scalar (ℤ-module) scaling — used for multiplicity-weighted sums."""
        def _s(x):
            f = factor
            # broadcast factor over payload axes
            extra = x.ndim - jnp.asarray(f).ndim
            f = jnp.asarray(f, x.dtype).reshape(jnp.asarray(f).shape + (1,) * extra)
            return x * f
        return jax.tree.map(_s, a)


# ---------------------------------------------------------------------------
# Scalar rings: ℤ and ℝ — COUNT / SUM aggregates.
# ---------------------------------------------------------------------------
class ScalarRing(Ring):
    components = {"v": ()}
    mul_terms = (MulTerm("v", "v", "v", "", "", ""),)

    def __init__(self, dtype=jnp.float32, name="scalar"):
        self.dtype = dtype
        self.name = name

    def ones(self, key_shape=()):
        return {"v": jnp.ones(key_shape, self.dtype)}

    def lift(self, values, var_index=None):
        """Default SUM lifting: g(x) = x (cast into the ring)."""
        return {"v": jnp.asarray(values, self.dtype)}

    def lift_one(self, values, var_index=None):
        """COUNT lifting: g(x) = 1."""
        return {"v": jnp.ones(jnp.shape(values), self.dtype)}


def count_ring(dtype=jnp.int32) -> ScalarRing:
    r = ScalarRing(dtype=dtype, name="count")
    r.lift = r.lift_one  # type: ignore[method-assign]
    return r


def sum_ring(dtype=jnp.float32) -> ScalarRing:
    return ScalarRing(dtype=dtype, name="sum")


# ---------------------------------------------------------------------------
# Degree-m matrix ring (Def. 7.2): payload (c, s, Q) — sufficient statistics
# for linear regression over joins.
# ---------------------------------------------------------------------------
class DegreeMRing(Ring):
    r"""(c, s, Q) triples:  c scalar count, s ∈ R^m, Q ∈ R^{m×m}.

    a * b = (c_a c_b,
             c_b s_a + c_a s_b,
             c_b Q_a + c_a Q_b + s_a s_b^T + s_b s_a^T)
    """

    commutative = True

    def __init__(self, m: int, dtype=jnp.float32):
        self.m = m
        self.dtype = dtype
        self.name = f"degree{m}"
        self.components = {"c": (), "s": (m,), "Q": (m, m)}
        self.mul_terms = (
            MulTerm("c", "c", "c", "", "", ""),
            MulTerm("s", "s", "c", "i", "", "i"),
            MulTerm("s", "c", "s", "", "i", "i"),
            MulTerm("Q", "Q", "c", "ij", "", "ij"),
            MulTerm("Q", "c", "Q", "", "ij", "ij"),
            MulTerm("Q", "s", "s", "i", "j", "ij"),
            MulTerm("Q", "s", "s", "j", "i", "ij"),
        )

    def ones(self, key_shape=()):
        return {
            "c": jnp.ones(key_shape, self.dtype),
            "s": jnp.zeros((*key_shape, self.m), self.dtype),
            "Q": jnp.zeros((*key_shape, self.m, self.m), self.dtype),
        }

    def lift(self, values, var_index: int | None = None):
        """g_j(x) = (1, e_j x, E_jj x^2) — Sec. 7.2."""
        assert var_index is not None, "degree-m lifting needs the variable index"
        x = jnp.asarray(values, self.dtype)
        key_shape = x.shape
        c = jnp.ones(key_shape, self.dtype)
        s = jnp.zeros((*key_shape, self.m), self.dtype).at[..., var_index].set(x)
        Q = (
            jnp.zeros((*key_shape, self.m, self.m), self.dtype)
            .at[..., var_index, var_index]
            .set(x * x)
        )
        return {"c": c, "s": s, "Q": Q}


# ---------------------------------------------------------------------------
# Square-matrix ring R^{p×p} — non-commutative; used for block payloads.
# (Matrix *chain* multiplication itself uses the scalar ring with matrix
#  keys; this ring is for block-partitioned payloads.)
# ---------------------------------------------------------------------------
class MatrixRing(Ring):
    commutative = False

    def __init__(self, p: int, dtype=jnp.float32):
        self.p = p
        self.dtype = dtype
        self.name = f"matrix{p}"
        self.components = {"M": (p, p)}
        self.mul_terms = (MulTerm("M", "M", "M", "ik", "kj", "ij"),)

    def ones(self, key_shape=()):
        eye = jnp.eye(self.p, dtype=self.dtype)
        return {"M": jnp.broadcast_to(eye, (*key_shape, self.p, self.p))}

    def lift(self, values, var_index=None):
        return self.ones(jnp.shape(values))


# ---------------------------------------------------------------------------
# Tuple (product) ring: componentwise product of rings — used to run several
# aggregates side by side and in tests.
# ---------------------------------------------------------------------------
class TupleRing(Ring):
    def __init__(self, rings: Sequence[Ring]):
        self.rings = tuple(rings)
        self.name = "x".join(r.name for r in rings)
        self.dtype = rings[0].dtype
        self.components = {
            f"{i}.{k}": shp
            for i, r in enumerate(rings)
            for k, shp in r.components.items()
        }
        terms = []
        for i, r in enumerate(rings):
            assert r.mul_terms is not None
            for t in r.mul_terms:
                terms.append(
                    MulTerm(
                        f"{i}.{t.comp_out}", f"{i}.{t.comp_a}", f"{i}.{t.comp_b}",
                        t.a_subs, t.b_subs, t.out_subs, t.coef,
                    )
                )
        self.mul_terms = tuple(terms)
        self.commutative = all(r.commutative for r in rings)

    def _split(self, a, i):
        pre = f"{i}."
        return {k[len(pre):]: v for k, v in a.items() if k.startswith(pre)}

    def _join(self, parts):
        return {f"{i}.{k}": v for i, p in enumerate(parts) for k, v in p.items()}

    def ones(self, key_shape=()):
        return self._join([r.ones(key_shape) for r in self.rings])

    def zeros(self, key_shape=()):
        return self._join([r.zeros(key_shape) for r in self.rings])

    def lift(self, values, var_index=None):
        return self._join([r.lift(values, var_index) for r in self.rings])


# ---------------------------------------------------------------------------
# Host-side (pure python) ring mirrors — exact oracles for tests, and the
# relational data ring F[ℤ] (Def. 7.4) whose payloads are relations (dynamic
# size, hence host-only; see DESIGN.md §3).
# ---------------------------------------------------------------------------
class PyRing:
    """Protocol for host-side rings operating on opaque python payloads."""

    name = "py-abstract"

    def zero(self):  # pragma: no cover - interface
        raise NotImplementedError

    def one(self):  # pragma: no cover - interface
        raise NotImplementedError

    def add(self, a, b):  # pragma: no cover - interface
        raise NotImplementedError

    def neg(self, a):  # pragma: no cover - interface
        raise NotImplementedError

    def mul(self, a, b):  # pragma: no cover - interface
        raise NotImplementedError

    def lift(self, value, var_index=None):  # pragma: no cover - interface
        raise NotImplementedError

    def is_zero(self, a) -> bool:
        return a == self.zero()


class PyNumberRing(PyRing):
    """ℤ / ℝ with numeric lifting (COUNT if count=True else SUM)."""

    def __init__(self, count=False):
        self.count = count
        self.name = "py-count" if count else "py-sum"

    def zero(self):
        return 0

    def one(self):
        return 1

    def add(self, a, b):
        return a + b

    def neg(self, a):
        return -a

    def mul(self, a, b):
        return a * b

    def lift(self, value, var_index=None):
        return 1 if self.count else value


class PyDegreeMRing(PyRing):
    """Exact numpy mirror of DegreeMRing."""

    def __init__(self, m: int):
        self.m = m
        self.name = f"py-degree{m}"

    def zero(self):
        return (0.0, np.zeros(self.m), np.zeros((self.m, self.m)))

    def one(self):
        return (1.0, np.zeros(self.m), np.zeros((self.m, self.m)))

    def add(self, a, b):
        return (a[0] + b[0], a[1] + b[1], a[2] + b[2])

    def neg(self, a):
        return (-a[0], -a[1], -a[2])

    def mul(self, a, b):
        ca, sa, Qa = a
        cb, sb, Qb = b
        return (
            ca * cb,
            cb * sa + ca * sb,
            cb * Qa + ca * Qb + np.outer(sa, sb) + np.outer(sb, sa),
        )

    def lift(self, value, var_index=None):
        assert var_index is not None
        s = np.zeros(self.m)
        s[var_index] = value
        Q = np.zeros((self.m, self.m))
        Q[var_index, var_index] = value * value
        return (1.0, s, Q)

    def is_zero(self, a):
        return a[0] == 0 and not a[1].any() and not a[2].any()


class PyRelationalRing(PyRing):
    """The relational data ring F[ℤ] (Def. 7.4).

    Payloads are relations over ℤ: dict mapping tuples -> int multiplicity.
    0 = {} (empty relation); 1 = {(): 1}.  + is union (⊎); * is join (⊗)
    implemented as concatenating Cartesian product of tuples with multiplied
    multiplicities.

    ``tagged=True`` activates the footnote-2 generalization needed for
    *incremental* maintenance: payload entries are (var, value) pairs and
    join canonicalizes by sorting on var — so delta payloads align with view
    payloads regardless of the order joins happen to be applied in during
    propagation (evaluation joins children left-to-right; a delta joins its
    siblings around the propagation path, a different order).
    """

    def __init__(self, tagged: bool = False):
        self.tagged = tagged
        self.name = "py-relational" + ("-tagged" if tagged else "")

    def zero(self):
        return {}

    def one(self):
        return {(): 1}

    def add(self, a, b):
        out = dict(a)
        for t, mult in b.items():
            out[t] = out.get(t, 0) + mult
            if out[t] == 0:
                del out[t]
        return out

    def neg(self, a):
        return {t: -m for t, m in a.items()}

    def mul(self, a, b):
        out: dict[tuple, int] = {}
        for ta, ma in a.items():
            for tb, mb in b.items():
                t = ta + tb
                if self.tagged:
                    t = tuple(sorted(t, key=lambda p: p[0]))
                out[t] = out.get(t, 0) + ma * mb
                if out[t] == 0:
                    del out[t]
        return out

    def lift(self, value, var_index=None, free=True):
        return {(value,): 1} if free else {(): 1}

    def is_zero(self, a):
        return len(a) == 0
