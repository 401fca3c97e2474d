"""Pure-Python optimal-ate pairing on BLS12-381.

Reference role: the pairing engine inside `blst` that
`verify_signature_sets` / `fast_aggregate_verify` call into
(Lighthouse crypto/bls/src/impls/blst.rs:36-119,233-244).

Algorithm:
  - untwist E'(Fp2) -> E(Fp12) via (x, y) -> (x / w^2, y / w^3); valid since
    w^6 = v^3 = xi and E': y^2 = x^3 + 4*xi.
  - Miller loop of length |X| (ate pairing, loop count = t - 1 = X); X < 0 is
    handled by conjugating the Miller value.
  - final exponentiation f^((p^12-1)/r) split into the easy part
    (p^6-1)(p^2+1) and the BLS12 hard part
    (p^4 - p^2 + 1)/r = (X-1)^2 * (X + p) * (X^2 + p^2 - 1) / 3 + 1
    ... the exact integer identity used is asserted at import time in
    `_check_hard_part_identity` so a mis-remembered decomposition cannot
    produce silently-wrong pairings.

Affine coordinates with field inversions throughout: this is the correctness
oracle, not the fast path (the JAX backend is the fast path).
"""

from __future__ import annotations

from .constants import P, R, X
from .curves import Point
from .fields import Fp, Fp2, Fp6, Fp12

# -- Fp2 -> Fp12 embedding and untwist ---------------------------------------


def fp2_to_fp12(c: Fp2) -> Fp12:
    return Fp12(Fp6(c, Fp2.zero(), Fp2.zero()), Fp6.zero())


def fp_to_fp12(c: Fp) -> Fp12:
    return fp2_to_fp12(Fp2(c, Fp.zero()))


# w^2 = v, w^3 = v*w as Fp12 elements.
_W2 = Fp12(Fp6(Fp2.zero(), Fp2.one(), Fp2.zero()), Fp6.zero())
_W3 = Fp12(Fp6.zero(), Fp6(Fp2.zero(), Fp2.one(), Fp2.zero()))
_W2_INV = _W2.inv()
_W3_INV = _W3.inv()


def untwist(q: Point) -> tuple[Fp12, Fp12]:
    """Map affine Q in E'(Fp2) to affine coordinates in E(Fp12)."""
    return fp2_to_fp12(q.x) * _W2_INV, fp2_to_fp12(q.y) * _W3_INV


# -- Miller loop ---------------------------------------------------------------


def _line_and_step(t, q, p12):
    """Chord/tangent line through T (and Q) evaluated at P, plus the next T.

    t, q: (x, y) affine Fp12 pairs; q may be None for a doubling step.
    p12: (x, y) of the G1 point embedded in Fp12.
    Constant subfield factors in the line value are harmless: they are killed
    by the final exponentiation.
    """
    tx, ty = t
    px, py = p12
    if q is None:
        lam = (tx * tx + tx * tx + tx * tx) * (ty + ty).inv()
        x3 = lam * lam - tx - tx
        y3 = lam * (tx - x3) - ty
    else:
        qx, qy = q
        if tx == qx and ty == qy:
            return _line_and_step(t, None, p12)
        lam = (qy - ty) * (qx - tx).inv()
        x3 = lam * lam - tx - qx
        y3 = lam * (tx - x3) - ty
    line = lam * (px - tx) + ty - py
    return line, (x3, y3)


def miller_loop(p: Point, q: Point) -> Fp12:
    """f_{|X|, Q}(P) with the BLS12 sign fix for X < 0.

    p: G1 affine point (Fp coords); q: G2 affine point (Fp2 coords).
    Infinity in either argument yields 1 (neutral for products), matching the
    aggregate-verify semantics of the reference.
    """
    if p.inf or q.inf:
        return Fp12.one()
    q12 = untwist(q)
    p12 = (fp_to_fp12(p.x), fp_to_fp12(p.y))
    t = q12
    f = Fp12.one()
    n = abs(X)
    for bit in bin(n)[3:]:  # MSB already consumed by initializing T = Q
        line, t = _line_and_step(t, None, p12)
        f = f.square() * line
        if bit == "1":
            line, t = _line_and_step(t, q12, p12)
            f = f * line
    if X < 0:
        f = f.conj()
    return f


# -- Frobenius ----------------------------------------------------------------

# gamma constants: h = xi^((p-1)/6), g = h^2 = xi^((p-1)/3).
assert (P - 1) % 6 == 0
_H = Fp2.xi().pow((P - 1) // 6)
_G = _H.square()
_G2C = _G.square()  # xi^(2(p-1)/3) = g^2


def frobenius(f: Fp12) -> Fp12:
    """f^p via coefficient-wise conjugation and basis constants."""
    a0, a1, a2 = f.c0.c0, f.c0.c1, f.c0.c2
    b0, b1, b2 = f.c1.c0, f.c1.c1, f.c1.c2
    c0 = Fp6(a0.conj(), a1.conj() * _G, a2.conj() * _G2C)
    c1 = Fp6(b0.conj() * _H, b1.conj() * _G * _H, b2.conj() * _G2C * _H)
    return Fp12(c0, c1)


def frobenius_n(f: Fp12, n: int) -> Fp12:
    for _ in range(n):
        f = frobenius(f)
    return f


# -- final exponentiation ------------------------------------------------------


def _check_hard_part_identity() -> int:
    """Return the exact hard-part exponent and sanity-check its decomposition.

    hard = (p^4 - p^2 + 1) / r. The multiple we actually compute is
    3 * hard = (X-1)^2 * (X + p) * (X^2 + p^2 - 1) + 3, which differs from
    `hard` by the factor 3 (coprime to r) — a standard, harmless substitution
    for pairing equality checks since gcd(3, r) = 1 keeps the map injective
    on r-th roots of unity structure.
    """
    hard = (P**4 - P**2 + 1) // R
    assert (P**4 - P**2 + 1) % R == 0
    decomp = (X - 1) ** 2 * (X + P) * (X**2 + P**2 - 1) + 3
    assert decomp == 3 * hard, "BLS12 hard-part decomposition identity failed"
    return hard


_HARD_EXPONENT = _check_hard_part_identity()


def _cyclotomic_pow(f: Fp12, e: int) -> Fp12:
    """Power in the cyclotomic subgroup where inversion is conjugation."""
    if e < 0:
        return _cyclotomic_pow(f.conj(), -e)
    return f.pow(e)


def final_exponentiation(f: Fp12) -> Fp12:
    # Easy part: f^((p^6 - 1)(p^2 + 1)).
    f = f.conj() * f.inv()
    f = frobenius_n(f, 2) * f
    # Hard part: f^(3 * (p^4 - p^2 + 1)/r) via the verified decomposition.
    a = _cyclotomic_pow(f, (X - 1) ** 2)
    b = _cyclotomic_pow(a, X) * frobenius(a)  # a^(X + p)
    c = _cyclotomic_pow(b, X * X) * frobenius_n(b, 2) * b.conj()  # b^(X^2 + p^2 - 1)
    return c * f * f * f


def pairing(p: Point, q: Point) -> Fp12:
    """e(P, Q)^3 — the full pairing composed with z -> z^3.

    Every use in BLS verification is an equality/product-is-one check, for
    which composing with the injective-on-mu_r map z -> z^3 is sound
    (gcd(3, r) = 1). Bilinearity is preserved exactly.
    """
    return final_exponentiation(miller_loop(p, q))


def multi_pairing(pairs: list[tuple[Point, Point]]) -> Fp12:
    """prod_i e(P_i, Q_i)^3 with a single final exponentiation — the shape of
    blst's verify_multiple_aggregate_signatures
    (Lighthouse crypto/bls/src/impls/blst.rs:114-116)."""
    f = Fp12.one()
    for p, q in pairs:
        f = f * miller_loop(p, q)
    return final_exponentiation(f)


def pairings_equal(p1: Point, q1: Point, p2: Point, q2: Point) -> bool:
    """e(P1, Q1) == e(P2, Q2), evaluated as e(-P1,Q1)*e(P2,Q2) == 1."""
    return multi_pairing([(-p1, q1), (p2, q2)]).is_one()
