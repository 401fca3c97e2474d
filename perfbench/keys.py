"""Key and signature material made cheaply on the host, from plain ints.

Secret keys are the small consecutive integers 1, 2, 3, ... (validator v
holds v + 1). Public keys are then a chain of additions of the G1
generator, and the one-key signatures of a committee a chain of additions
of the committee's message point H(m): sk * H(m) for consecutive sk. An
aggregate signature is (sum of the members' keys) * H(m), one short
double-and-add. All chains run in Jacobian coordinates and are brought to
affine with one shared inversion (Montgomery's trick).

Points are tuples of ints: G1 (x, y), G2 (x0, x1, y0, y1) with
x = x0 + x1*u, u^2 = -1. The device's work does not depend on key values,
so these keys cost the verifier what any keys would.
"""

from __future__ import annotations

from .ref.constants import G1_GENERATOR_X, G1_GENERATOR_Y, P

G1_GEN = (G1_GENERATOR_X, G1_GENERATOR_Y)


class _Fp:
    """Field operations on ints mod P."""

    zero, one = 0, 1

    @staticmethod
    def add(a, b):
        return (a + b) % P

    @staticmethod
    def sub(a, b):
        return (a - b) % P

    @staticmethod
    def mul(a, b):
        return a * b % P

    @staticmethod
    def small(a, k):
        return a * k % P

    @staticmethod
    def inv(a):
        return pow(a, -1, P)


class _Fp2:
    """Field operations on (c0, c1) pairs, u^2 = -1."""

    zero, one = (0, 0), (1, 0)

    @staticmethod
    def add(a, b):
        return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)

    @staticmethod
    def sub(a, b):
        return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)

    @staticmethod
    def mul(a, b):
        return ((a[0] * b[0] - a[1] * b[1]) % P, (a[0] * b[1] + a[1] * b[0]) % P)

    @staticmethod
    def small(a, k):
        return (a[0] * k % P, a[1] * k % P)

    @staticmethod
    def inv(a):
        n = pow((a[0] * a[0] + a[1] * a[1]) % P, -1, P)
        return (a[0] * n % P, -a[1] * n % P)


def _madd(F, p, q):
    """Jacobian p plus affine q (madd-2007-bl); p, q distinct, neither at
    infinity, p != -q."""
    X1, Y1, Z1 = p
    X2, Y2 = q
    z1z1 = F.mul(Z1, Z1)
    u2 = F.mul(X2, z1z1)
    s2 = F.mul(Y2, F.mul(Z1, z1z1))
    h = F.sub(u2, X1)
    hh = F.mul(h, h)
    i = F.small(hh, 4)
    j = F.mul(h, i)
    r = F.small(F.sub(s2, Y1), 2)
    v = F.mul(X1, i)
    X3 = F.sub(F.sub(F.mul(r, r), j), F.small(v, 2))
    Y3 = F.sub(F.mul(r, F.sub(v, X3)), F.small(F.mul(Y1, j), 2))
    zh = F.add(Z1, h)
    Z3 = F.sub(F.sub(F.mul(zh, zh), z1z1), hh)
    return (X3, Y3, Z3)


def _dbl(F, p):
    """Jacobian doubling on y^2 = x^3 + b (dbl-2009-l)."""
    X1, Y1, Z1 = p
    a = F.mul(X1, X1)
    b = F.mul(Y1, Y1)
    c = F.mul(b, b)
    xb = F.add(X1, b)
    d = F.small(F.sub(F.sub(F.mul(xb, xb), a), c), 2)
    e = F.small(a, 3)
    X3 = F.sub(F.mul(e, e), F.small(d, 2))
    Y3 = F.sub(F.mul(e, F.sub(d, X3)), F.small(c, 8))
    Z3 = F.small(F.mul(Y1, Z1), 2)
    return (X3, Y3, Z3)


def _to_affine(F, pts):
    """Jacobian -> affine for many points with one inversion."""
    if not pts:
        return []
    prefix = [F.one]
    for _, _, z in pts:
        prefix.append(F.mul(prefix[-1], z))
    inv = F.inv(prefix[-1])
    out = [None] * len(pts)
    for i in range(len(pts) - 1, -1, -1):
        x, y, z = pts[i]
        zi = F.mul(inv, prefix[i])
        inv = F.mul(inv, z)
        zi2 = F.mul(zi, zi)
        out[i] = (F.mul(x, zi2), F.mul(y, F.mul(zi2, zi)))
    return out


def _jac(F, q):
    return (q[0], q[1], F.one)


def _mul_jac(F, q, k: int):
    """k * q (k >= 1, far below the group order) in Jacobian coordinates."""
    acc = _jac(F, q)
    for bit in bin(k)[3:]:
        acc = _dbl(F, acc)
        if bit == "1":
            acc = _madd(F, acc, q)
    return acc


def _chain(F, q, k0: int, n: int):
    """k0*q, (k0+1)*q, ..., (k0+n-1)*q, affine."""
    if n <= 0:
        return []
    p = _mul_jac(F, q, k0)
    pts = [p]
    for k in range(k0, k0 + n - 1):
        p = _dbl(F, p) if k == 1 else _madd(F, p, q)
        pts.append(p)
    return _to_affine(F, pts)


def _g2_flat(pts):
    return [(x[0], x[1], y[0], y[1]) for x, y in pts]


def _g2_pair(q):
    return ((q[0], q[1]), (q[2], q[3]))


def g1_chain(k0: int, n: int) -> list[tuple[int, int]]:
    """Public keys of secret keys k0 .. k0+n-1."""
    return _chain(_Fp, G1_GEN, k0, n)


def g2_chain(h, k0: int, n: int) -> list[tuple[int, int, int, int]]:
    """Signatures over message point `h` by secret keys k0 .. k0+n-1."""
    return _g2_flat(_chain(_Fp2, _g2_pair(h), k0, n))


def g2_muls(items) -> list[tuple[int, int, int, int]]:
    """[k * h for (h, k) in items], affine, one shared inversion."""
    return _g2_flat(_to_affine(_Fp2, [_mul_jac(_Fp2, _g2_pair(h), k) for h, k in items]))
