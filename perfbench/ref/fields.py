"""Pure-Python BLS12-381 field towers (reference backend).

This is the ground-truth implementation the JAX/TPU backend is tested
against — the role `milagro` plays for `blst` in the reference
(Lighthouse crypto/bls/Cargo.toml:10, compile-time backend selection at
Lighthouse crypto/bls/src/lib.rs:8-20).

Tower construction (standard for BLS12-381):
    Fp2  = Fp[u]  / (u^2 + 1)
    Fp6  = Fp2[v] / (v^3 - xi),  xi = u + 1
    Fp12 = Fp6[w] / (w^2 - v)

Elements are immutable; arithmetic is schoolbook/Karatsuba over Python ints.
"""

from __future__ import annotations

from .constants import P


class Fp:
    """Base field element, canonical representative in [0, P)."""

    __slots__ = ("n",)

    def __init__(self, n: int):
        self.n = n % P

    def __add__(self, o: "Fp") -> "Fp":
        return Fp(self.n + o.n)

    def __sub__(self, o: "Fp") -> "Fp":
        return Fp(self.n - o.n)

    def __mul__(self, o: "Fp") -> "Fp":
        return Fp(self.n * o.n)

    def __neg__(self) -> "Fp":
        return Fp(-self.n)

    def square(self) -> "Fp":
        return Fp(self.n * self.n)

    def inv(self) -> "Fp":
        if self.n == 0:
            raise ZeroDivisionError("inverse of zero in Fp")
        return Fp(pow(self.n, P - 2, P))

    def pow(self, e: int) -> "Fp":
        return Fp(pow(self.n, e, P))

    def sqrt(self) -> "Fp | None":
        """Square root via p = 3 (mod 4): candidate = self^((p+1)/4)."""
        c = Fp(pow(self.n, (P + 1) // 4, P))
        return c if c.square() == self else None

    def is_zero(self) -> bool:
        return self.n == 0

    def sgn0(self) -> int:
        """RFC 9380 sign: parity of the canonical representative."""
        return self.n & 1

    def __eq__(self, o: object) -> bool:
        return isinstance(o, Fp) and self.n == o.n

    def __hash__(self) -> int:
        return hash(("Fp", self.n))

    def __repr__(self) -> str:
        return f"Fp(0x{self.n:x})"

    @staticmethod
    def zero() -> "Fp":
        return Fp(0)

    @staticmethod
    def one() -> "Fp":
        return Fp(1)


class Fp2:
    """Fp2 = Fp[u]/(u^2+1); element c0 + c1*u."""

    __slots__ = ("c0", "c1")

    def __init__(self, c0: Fp, c1: Fp):
        self.c0, self.c1 = c0, c1

    @staticmethod
    def from_ints(c0: int, c1: int) -> "Fp2":
        return Fp2(Fp(c0), Fp(c1))

    def __add__(self, o: "Fp2") -> "Fp2":
        return Fp2(self.c0 + o.c0, self.c1 + o.c1)

    def __sub__(self, o: "Fp2") -> "Fp2":
        return Fp2(self.c0 - o.c0, self.c1 - o.c1)

    def __neg__(self) -> "Fp2":
        return Fp2(-self.c0, -self.c1)

    def __mul__(self, o: "Fp2") -> "Fp2":
        # Karatsuba: (a0 + a1 u)(b0 + b1 u), u^2 = -1.
        t0 = self.c0 * o.c0
        t1 = self.c1 * o.c1
        t2 = (self.c0 + self.c1) * (o.c0 + o.c1)
        return Fp2(t0 - t1, t2 - t0 - t1)

    def square(self) -> "Fp2":
        # (a0 + a1 u)^2 = (a0+a1)(a0-a1) + 2 a0 a1 u
        a, b = self.c0, self.c1
        return Fp2((a + b) * (a - b), (a * b) + (a * b))

    def scale(self, k: Fp) -> "Fp2":
        return Fp2(self.c0 * k, self.c1 * k)

    def conj(self) -> "Fp2":
        return Fp2(self.c0, -self.c1)

    def mul_by_nonresidue(self) -> "Fp2":
        # multiply by xi = 1 + u: (c0 - c1) + (c0 + c1) u
        return Fp2(self.c0 - self.c1, self.c0 + self.c1)

    def inv(self) -> "Fp2":
        # 1/(a+bu) = (a - bu)/(a^2 + b^2)
        d = (self.c0.square() + self.c1.square()).inv()
        return Fp2(self.c0 * d, -(self.c1 * d))

    def pow(self, e: int) -> "Fp2":
        if e < 0:
            return self.inv().pow(-e)
        acc = Fp2.one()
        base = self
        while e:
            if e & 1:
                acc = acc * base
            base = base.square()
            e >>= 1
        return acc

    def sqrt(self) -> "Fp2 | None":
        """Square root in Fp2 via the p = 3 (mod 4) complex method
        (Adj–Rodríguez-Henríquez): a1 = x^((p-3)/4); x0 = a1*x;
        alpha = a1*x0; if alpha = -1 -> sqrt = u * x0 ... handled by
        candidate checks below (reference semantics only, not constant-time).
        """
        if self.is_zero():
            return Fp2.zero()
        a1 = self.pow((P - 3) // 4)
        x0 = a1 * self
        alpha = a1 * x0
        if alpha == Fp2(Fp(P - 1), Fp.zero()):
            cand = Fp2(-x0.c1, x0.c0)  # u * x0
        else:
            b = (alpha + Fp2.one()).pow((P - 1) // 2)
            cand = b * x0
        return cand if cand.square() == self else None

    def is_zero(self) -> bool:
        return self.c0.is_zero() and self.c1.is_zero()

    def sgn0(self) -> int:
        """RFC 9380 sgn0 for an extension field element (little-endian order)."""
        sign_0 = self.c0.n & 1
        zero_0 = self.c0.n == 0
        sign_1 = self.c1.n & 1
        return sign_0 | (zero_0 & sign_1)

    def __eq__(self, o: object) -> bool:
        return isinstance(o, Fp2) and self.c0 == o.c0 and self.c1 == o.c1

    def __hash__(self) -> int:
        return hash(("Fp2", self.c0.n, self.c1.n))

    def __repr__(self) -> str:
        return f"Fp2(0x{self.c0.n:x}, 0x{self.c1.n:x})"

    @staticmethod
    def zero() -> "Fp2":
        return Fp2(Fp.zero(), Fp.zero())

    @staticmethod
    def one() -> "Fp2":
        return Fp2(Fp.one(), Fp.zero())

    @staticmethod
    def xi() -> "Fp2":
        return Fp2(Fp.one(), Fp.one())


class Fp6:
    """Fp6 = Fp2[v]/(v^3 - xi); element c0 + c1*v + c2*v^2."""

    __slots__ = ("c0", "c1", "c2")

    def __init__(self, c0: Fp2, c1: Fp2, c2: Fp2):
        self.c0, self.c1, self.c2 = c0, c1, c2

    def __add__(self, o: "Fp6") -> "Fp6":
        return Fp6(self.c0 + o.c0, self.c1 + o.c1, self.c2 + o.c2)

    def __sub__(self, o: "Fp6") -> "Fp6":
        return Fp6(self.c0 - o.c0, self.c1 - o.c1, self.c2 - o.c2)

    def __neg__(self) -> "Fp6":
        return Fp6(-self.c0, -self.c1, -self.c2)

    def __mul__(self, o: "Fp6") -> "Fp6":
        a0, a1, a2 = self.c0, self.c1, self.c2
        b0, b1, b2 = o.c0, o.c1, o.c2
        t0, t1, t2 = a0 * b0, a1 * b1, a2 * b2
        c0 = ((a1 + a2) * (b1 + b2) - t1 - t2).mul_by_nonresidue() + t0
        c1 = (a0 + a1) * (b0 + b1) - t0 - t1 + t2.mul_by_nonresidue()
        c2 = (a0 + a2) * (b0 + b2) - t0 - t2 + t1
        return Fp6(c0, c1, c2)

    def square(self) -> "Fp6":
        return self * self

    def scale(self, k: Fp2) -> "Fp6":
        return Fp6(self.c0 * k, self.c1 * k, self.c2 * k)

    def mul_by_v(self) -> "Fp6":
        # v * (c0 + c1 v + c2 v^2) = xi*c2 + c0 v + c1 v^2
        return Fp6(self.c2.mul_by_nonresidue(), self.c0, self.c1)

    def inv(self) -> "Fp6":
        a, b, c = self.c0, self.c1, self.c2
        t0 = a.square() - (b * c).mul_by_nonresidue()
        t1 = (c.square()).mul_by_nonresidue() - a * b
        t2 = b.square() - a * c
        d = (a * t0 + (c * t1 + b * t2).mul_by_nonresidue()).inv()
        return Fp6(t0 * d, t1 * d, t2 * d)

    def is_zero(self) -> bool:
        return self.c0.is_zero() and self.c1.is_zero() and self.c2.is_zero()

    def __eq__(self, o: object) -> bool:
        return (
            isinstance(o, Fp6) and self.c0 == o.c0 and self.c1 == o.c1 and self.c2 == o.c2
        )

    def __repr__(self) -> str:
        return f"Fp6({self.c0}, {self.c1}, {self.c2})"

    @staticmethod
    def zero() -> "Fp6":
        return Fp6(Fp2.zero(), Fp2.zero(), Fp2.zero())

    @staticmethod
    def one() -> "Fp6":
        return Fp6(Fp2.one(), Fp2.zero(), Fp2.zero())


class Fp12:
    """Fp12 = Fp6[w]/(w^2 - v); element c0 + c1*w."""

    __slots__ = ("c0", "c1")

    def __init__(self, c0: Fp6, c1: Fp6):
        self.c0, self.c1 = c0, c1

    def __add__(self, o: "Fp12") -> "Fp12":
        return Fp12(self.c0 + o.c0, self.c1 + o.c1)

    def __sub__(self, o: "Fp12") -> "Fp12":
        return Fp12(self.c0 - o.c0, self.c1 - o.c1)

    def __neg__(self) -> "Fp12":
        return Fp12(-self.c0, -self.c1)

    def __mul__(self, o: "Fp12") -> "Fp12":
        # Karatsuba with w^2 = v.
        t0 = self.c0 * o.c0
        t1 = self.c1 * o.c1
        t2 = (self.c0 + self.c1) * (o.c0 + o.c1)
        return Fp12(t0 + t1.mul_by_v(), t2 - t0 - t1)

    def square(self) -> "Fp12":
        return self * self

    def conj(self) -> "Fp12":
        """Conjugation = Frobenius^6 (inversion on the cyclotomic subgroup)."""
        return Fp12(self.c0, -self.c1)

    def inv(self) -> "Fp12":
        d = (self.c0.square() - self.c1.square().mul_by_v()).inv()
        return Fp12(self.c0 * d, -(self.c1 * d))

    def pow(self, e: int) -> "Fp12":
        if e < 0:
            return self.inv().pow(-e)
        acc = Fp12.one()
        base = self
        while e:
            if e & 1:
                acc = acc * base
            base = base.square()
            e >>= 1
        return acc

    def is_one(self) -> bool:
        return self == Fp12.one()

    def __eq__(self, o: object) -> bool:
        return isinstance(o, Fp12) and self.c0 == o.c0 and self.c1 == o.c1

    def __repr__(self) -> str:
        return f"Fp12({self.c0}, {self.c1})"

    @staticmethod
    def zero() -> "Fp12":
        return Fp12(Fp6.zero(), Fp6.zero())

    @staticmethod
    def one() -> "Fp12":
        return Fp12(Fp6.one(), Fp6.zero())
