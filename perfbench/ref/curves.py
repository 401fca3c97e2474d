"""Pure-Python BLS12-381 curve groups G1 (over Fp) and G2 (over Fp2).

Reference semantics: the point types behind `GenericPublicKey` /
`GenericSignature` in Lighthouse crypto/bls/src/generic_public_key.rs and
generic_signature.rs; subgroup/infinity policy per
Lighthouse crypto/bls/src/lib.rs:61-64.

Points are affine with an explicit infinity flag; works generically over any
field object exposing +, -, *, square, inv, is_zero, zero(), one().
"""

from __future__ import annotations

from .constants import B_G1, B_G2, G1_GENERATOR_X, G1_GENERATOR_Y, G2_GENERATOR_X, G2_GENERATOR_Y, H_G2, P, R, X
from .fields import Fp, Fp2


class Point:
    """Affine point on y^2 = x^3 + b over a generic field.

    `_limbs` is an opaque staging-cache slot: the jax backend's host packer
    (jax_backend/pack.py) memoizes the point's device limb rows here, so a
    point packed once (a cached validator pubkey, a signature re-staged by
    bisection) is gathered — not recomputed — on every later staging. It is
    derived purely from (x, y), which are immutable after construction, so
    it can never go stale. Left unset until first packed."""

    __slots__ = ("x", "y", "inf", "b", "_limbs")

    def __init__(self, x, y, inf: bool, b):
        self.x, self.y, self.inf, self.b = x, y, inf, b

    # -- constructors --------------------------------------------------------

    @classmethod
    def infinity(cls, b):
        z = b - b  # field zero of the right type
        return cls(z, z, True, b)

    def is_on_curve(self) -> bool:
        if self.inf:
            return True
        return self.y * self.y == self.x * self.x * self.x + self.b

    # -- group law -----------------------------------------------------------

    def __neg__(self) -> "Point":
        return Point(self.x, -self.y, self.inf, self.b)

    def __add__(self, o: "Point") -> "Point":
        if self.inf:
            return o
        if o.inf:
            return self
        if self.x == o.x:
            if self.y == o.y:
                return self.double()
            return Point.infinity(self.b)
        lam = (o.y - self.y) * (o.x - self.x).inv()
        x3 = lam * lam - self.x - o.x
        y3 = lam * (self.x - x3) - self.y
        return Point(x3, y3, False, self.b)

    def __sub__(self, o: "Point") -> "Point":
        return self + (-o)

    def double(self) -> "Point":
        if self.inf or self.y.is_zero():
            return Point.infinity(self.b)
        three = self.x + self.x + self.x
        lam = (three * self.x) * (self.y + self.y).inv()
        x3 = lam * lam - self.x - self.x
        y3 = lam * (self.x - x3) - self.y
        return Point(x3, y3, False, self.b)

    def mul(self, k: int) -> "Point":
        if k < 0:
            return (-self).mul(-k)
        acc = Point.infinity(self.b)
        add = self
        while k:
            if k & 1:
                acc = acc + add
            add = add.double()
            k >>= 1
        return acc

    def __eq__(self, o: object) -> bool:
        if not isinstance(o, Point):
            return NotImplemented
        if self.inf or o.inf:
            return self.inf and o.inf
        return self.x == o.x and self.y == o.y

    def __repr__(self) -> str:
        return "Point(inf)" if self.inf else f"Point({self.x}, {self.y})"


# -- group-specific helpers ---------------------------------------------------

_B1 = Fp(B_G1)
_B2 = Fp2.from_ints(*B_G2)


def g1_generator() -> Point:
    return Point(Fp(G1_GENERATOR_X), Fp(G1_GENERATOR_Y), False, _B1)


def g2_generator() -> Point:
    return Point(Fp2.from_ints(*G2_GENERATOR_X), Fp2.from_ints(*G2_GENERATOR_Y), False, _B2)


def g1_infinity() -> Point:
    return Point.infinity(_B1)


def g2_infinity() -> Point:
    return Point.infinity(_B2)


def g1_in_subgroup(p: Point) -> bool:
    """Full r-torsion check (reference rejects non-subgroup keys/sigs:
    Lighthouse crypto/bls/src/impls/blst.rs key_validate usage)."""
    return p.is_on_curve() and p.mul(R).inf


def g2_in_subgroup(p: Point) -> bool:
    return p.is_on_curve() and p.mul(R).inf


def g2_clear_cofactor(p: Point) -> Point:
    """Map an arbitrary E2 point into G2. Reference method: multiply by the
    full cofactor h2 — slower than the endomorphism method but unambiguous:
    h2 * P always lands in the r-torsion. NOTE: RFC 9380's h_eff for G2
    differs from h2 by a factor coprime to r, so the *subgroup image* of a
    hashed point is identical; but the exact point differs. For spec-exact
    hash_to_curve output we use h_eff (see hash_to_curve.py)."""
    return p.mul(H_G2)


# RFC 9380 §8.8.2 effective cofactor for G2 cofactor clearing:
# h_eff = mul_by_x(mul_by_x(P - psi(P))...) method or the scalar
# h_eff = (x^2 - x - 1)*h2-ish; the spec gives h_eff as an explicit scalar.
# We compute it from the curve family: h_eff = 3 * (x^2 - 1) * h2 / ... is
# NOT memorized; instead hash_to_curve uses the psi-endomorphism method
# (Budroni–Pintore), implemented in hash_to_curve.py and *checked* to land
# in the r-torsion.
