"""Hash-to-G2 split across host and device, TPU-first.

The reference calls blst's hash-to-curve inside sign/verify
(/root/reference/crypto/bls/src/impls/blst.rs:14 DST). Here the pipeline is
split at the natural boundary:

  HOST  : expand_message_xmd (SHA-256 over a few hundred bytes — a hashlib
          call; bytes -> two Fp2 field elements per message, reduced mod p
          with bigint arithmetic and packed to Montgomery limbs). Tiny
          (256 B/message), so host->device transfer is negligible.
  DEVICE: everything algebraic — branch-free simplified SWU onto E', the
          3-isogeny to E2, Jacobian point addition, and psi-method cofactor
          clearing. This is thousands of field muls per message and batches
          perfectly.

Semantics are pinned to the oracle (ref/hash_to_curve.py), which itself is
pinned to RFC 9380 external vectors (tests/test_bls_kat.py), and the
device output is differentially tested point-for-point against the oracle.
"""

from __future__ import annotations

import threading

import numpy as np
import jax.numpy as jnp
from jax import lax

from ..constants import DST, P, X as X_PARAM
from ..ref import hash_to_curve as ref_h2c
from . import fp
from .curve import FP2, Jac, psi, scalar_mul_int, add as jac_add
from .tower import (
    fp2,
    fp2_add,
    fp2_eq,
    fp2_is_zero,
    fp2_mul,
    fp2_neg,
    fp2_one,
    fp2_select,
    fp2_sgn0,
    fp2_sqr,
    fp2_sub,
    fp2_inv,
    fp2_scale,
)

# -- host-side constants (packed once) ----------------------------------------


def _pack2(el) -> np.ndarray:
    from .pack import pack_fp2_el

    return pack_fp2_el(el)


_A = _pack2(ref_h2c.ISO_A)
_B = _pack2(ref_h2c.ISO_B)
_Z = _pack2(ref_h2c.SSWU_Z)
# x1 constants: C1 = -B/A (generic branch), C2 = B/(Z*A) (tv1 == 0 branch).
_C1 = _pack2(-(ref_h2c.ISO_B * ref_h2c.ISO_A.inv()))
_C2 = _pack2(ref_h2c.ISO_B * (ref_h2c.SSWU_Z * ref_h2c.ISO_A).inv())
_X0 = _pack2(ref_h2c._ISO_X0)
_T = _pack2(ref_h2c._ISO_T)
_U = _pack2(ref_h2c._ISO_U)


def _pack_fp_scaled(x) -> np.ndarray:
    return fp.to_mont_host(x.n)


_INV9 = _pack_fp_scaled(ref_h2c._INV9)
_INV27 = _pack_fp_scaled(ref_h2c._INV27)  # already carries the -1/27 sign pin

# Exponent bit tables for the Fp2 square-root candidate (p = 3 mod 4 method).
_SQRT_E1_BITS = np.array([int(b) for b in bin((P - 3) // 4)[2:]], dtype=np.int32)
_SQRT_E2_BITS = np.array([int(b) for b in bin((P - 1) // 2)[2:]], dtype=np.int32)

_MINUS_ONE = None  # packed lazily (avoids import cycle at module load)


def _minus_one():
    global _MINUS_ONE
    if _MINUS_ONE is None:
        from ..ref.fields import Fp2 as RefFp2, Fp as RefFp

        _MINUS_ONE = _pack2(RefFp2(RefFp(P - 1), RefFp(0)))
    return _MINUS_ONE


# -- device primitives ---------------------------------------------------------


def _fp2_pow_bits(base, bits: np.ndarray):
    """base^e for a fixed public exponent (MSB-first bit table), in Fp2 —
    2^4-ary windowed (see fp._pow_bits_windowed: scan-depth, not FLOPs, is
    what this kernel pays for)."""
    return fp._pow_bits_windowed(base, bits, fp2_mul, fp2_sqr, fp2_one(base.shape[:-2]))


def fp2_sqrt_candidate(x):
    """Branch-free Fp2 square root candidate (Adj–Rodríguez-Henríquez for
    p = 3 mod 4, mirroring the oracle ref/fields.py:142-158). Returns cand;
    callers must check cand^2 == x. Correct candidate also for x = 0."""
    a1 = _fp2_pow_bits(x, _SQRT_E1_BITS)  # x^((p-3)/4)
    x0 = fp2_mul(a1, x)
    alpha = fp2_mul(a1, x0)
    # u * x0 = (-x0.c1, x0.c0)
    ux0 = fp2(fp.neg(x0[..., 1, :]), x0[..., 0, :])
    b = _fp2_pow_bits(fp2_add(alpha, fp2_one(alpha.shape[:-2])), _SQRT_E2_BITS)
    cand = fp2_mul(b, x0)
    is_m1 = fp2_eq(alpha, jnp.asarray(_minus_one()))
    return fp2_select(is_m1, ux0, cand)


def sswu(u):
    """Simplified SWU onto E' (branch-free; oracle: ref/hash_to_curve.py:257).

    u: (..., 2, 32) Fp2. Returns affine (x, y) on E'."""
    A, B, Z = jnp.asarray(_A), jnp.asarray(_B), jnp.asarray(_Z)
    u2 = fp2_sqr(u)
    zu2 = fp2_mul(Z, u2)
    t1 = fp2_add(fp2_sqr(zu2), zu2)
    t1_zero = fp2_is_zero(t1)
    x1_generic = fp2_mul(
        jnp.asarray(_C1), fp2_add(fp2_one(t1.shape[:-2]), fp2_inv(t1))
    )
    x1 = fp2_select(t1_zero, jnp.broadcast_to(jnp.asarray(_C2), x1_generic.shape), x1_generic)
    gx1 = fp2_add(fp2_add(fp2_mul(fp2_sqr(x1), x1), fp2_mul(A, x1)), B)
    x2 = fp2_mul(zu2, x1)
    gx2 = fp2_add(fp2_add(fp2_mul(fp2_sqr(x2), x2), fp2_mul(A, x2)), B)
    # The two square-root candidates are independent: stack them so the two
    # ~380-bit exponent scans (the single most sequential part of hash-to-G2)
    # run as ONE scan at doubled batch width.
    cand = fp2_sqrt_candidate(jnp.stack([gx1, gx2]))
    y1, y2 = cand[0], cand[1]
    is_sq = fp2_eq(fp2_sqr(y1), gx1)
    x = fp2_select(is_sq, x1, x2)
    y = fp2_select(is_sq, y1, y2)
    flip = fp2_sgn0(u) != fp2_sgn0(y)
    y = fp2_select(flip, fp2_neg(y), y)
    return x, y


def iso3_map(x, y) -> Jac:
    """The Vélu-derived 3-isogeny E' -> E2 with the externally-pinned sign
    (oracle: ref/hash_to_curve.py:207-219), as a Jacobian point (kernel
    points map to infinity via the z=0 encoding)."""
    d = fp2_sub(x, jnp.asarray(_X0))
    dinv = fp2_inv(d)  # inv0: kernel point handled by mask below
    d2 = fp2_sqr(dinv)
    d3 = fp2_mul(d2, dinv)
    T, U = jnp.asarray(_T), jnp.asarray(_U)
    xo = fp2_scale(
        fp2_add(x, fp2_add(fp2_mul(T, dinv), fp2_mul(U, d2))), jnp.asarray(_INV9)
    )
    one = fp2_one(x.shape[:-2])
    yo = fp2_scale(
        fp2_mul(y, fp2_sub(one, fp2_add(fp2_mul(T, d2), fp2_mul(fp2_add(U, U), d3)))),
        jnp.asarray(_INV27),
    )
    kernel = fp2_is_zero(d)
    # Kernel points map to the canonical projective infinity (0, 1, 0) —
    # complete-addition inputs must be genuine curve points.
    zero, one = FP2.zero(kernel.shape), FP2.one(kernel.shape)
    return Jac(
        fp2_select(kernel, zero, xo),
        fp2_select(kernel, one, yo),
        fp2_select(kernel, zero, one),
    )


# [X^2 - X - 1] and [X - 1] for the psi-method cofactor clearing.
_CC_K1 = X_PARAM * X_PARAM - X_PARAM - 1  # positive
_CC_K2 = X_PARAM - 1  # negative


_CC_WIDTH = _CC_K1.bit_length()  # 127
_CC_BITS = np.array(
    [
        [(_CC_K1 >> (_CC_WIDTH - 1 - i)) & 1 for i in range(_CC_WIDTH)],
        [(abs(_CC_K2) >> (_CC_WIDTH - 1 - i)) & 1 for i in range(_CC_WIDTH)],
    ],
    dtype=np.int32,
)


def clear_cofactor(p: Jac) -> Jac:
    """Budroni–Pintore psi-method cofactor clearing, matching the oracle
    (ref/hash_to_curve.py:298-304): [X^2-X-1]P + [X-1]psi(P) + psi^2(2P).

    The two ladders ([X^2-X-1]P and [|X-1|]psi(P)) run as ONE 2-stacked
    ladder — a single compiled scan."""
    from .curve import dbl, neg as jac_neg, scalar_mul_bits, _stack2

    pp = psi(p)
    base = _stack2(FP2, p, pp)
    batch_rank = p.z.ndim - 2  # z is (..., 2, 32); leading dims are batch
    bits = _CC_BITS.reshape(2, *([1] * batch_rank), _CC_WIDTH)
    u = scalar_mul_bits(FP2, base, jnp.asarray(bits))
    t1 = Jac(u.x[0], u.y[0], u.z[0])
    t2 = jac_neg(FP2, Jac(u.x[1], u.y[1], u.z[1]))  # X-1 < 0
    t3 = psi(psi(dbl(FP2, p)))
    return jac_add(FP2, jac_add(FP2, t1, t2), t3)


def map_to_g2(u0, u1) -> Jac:
    """Device portion of hash_to_curve: SSWU + isogeny evaluated ONCE on the
    2-stacked (u0, u1) batch (the heavy sqrt/inv exponent scans compile a
    single instantiation), then point addition and cofactor clearing.

    The barrier keeps SSWU's output a value of its own. Without it, the TPU
    compiler fuses SSWU into the cofactor clearing and gets y wrong (x stays
    right): with a barrier every output matched the CPU on the v5e. Each
    sub-step also matched when compiled alone, and so did the chain up to
    the point addition (my chip runs, PR 21). `chip_smoke.py` checks this
    map against the oracle at every batch width it compiles."""
    us = jnp.stack([u0, u1])  # (2, ..., 2, 32)
    q = iso3_map(*lax.optimization_barrier(sswu(us)))
    q0 = Jac(q.x[0], q.y[0], q.z[0])
    q1 = Jac(q.x[1], q.y[1], q.z[1])
    return clear_cofactor(jac_add(FP2, q0, q1))


# -- host-side field derivation ------------------------------------------------


class _H2CFieldCache:
    """Process-wide LRU of packed hash_to_field limb rows keyed by
    (message, dst). Gossip attestations for the same slot/target share a
    signing root, so repeated roots across coalesced batches hit memory
    instead of re-running expand_message_xmd (SHA-256) + bigint reduction.
    Rows are deterministic functions of the key — a hit is byte-identical
    to recomputation by construction. Stored rows are read-only views; the
    staging path copies them into its output buffer."""

    def __init__(self, maxsize: int = 4096):
        import collections

        self.maxsize = int(maxsize)
        self._lock = threading.Lock()
        self._entries: "collections.OrderedDict[tuple[bytes, bytes], np.ndarray]" = (
            collections.OrderedDict()
        )

    def get(self, key):
        with self._lock:
            row = self._entries.get(key)
            if row is not None:
                self._entries.move_to_end(key)
            return row

    def put(self, key, row) -> None:
        with self._lock:
            self._entries[key] = row
            self._entries.move_to_end(key)
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


H2C_FIELD_CACHE = _H2CFieldCache()


def hash_to_field_limbs(messages: list[bytes], dst: bytes = DST) -> np.ndarray:
    """Host: RFC 9380 hash_to_field for count=2, m=2 — returns Montgomery
    limb array (S, 2, 2, 32): [message, u-index, component, limbs].

    Fast path: SHA-256/reduction runs once per UNIQUE (message, dst) pair
    in the batch (coalesced gossip batches repeat signing roots heavily),
    results scatter back by index, and unique rows are additionally served
    from / stored into the process-wide H2C_FIELD_CACHE LRU. Byte-identical
    to the per-message slow path."""
    from .pack import _count_staging_cache

    out = np.empty((len(messages), 2, 2, fp.N_LIMBS), dtype=np.int32)
    by_msg: dict[bytes, list[int]] = {}
    for i, msg in enumerate(messages):
        by_msg.setdefault(msg, []).append(i)
    pending: dict[bytes, list[int]] = {}
    hits = 0
    for msg, idxs in by_msg.items():  # one LRU lookup per unique message
        row = H2C_FIELD_CACHE.get((msg, dst))
        if row is not None:
            for i in idxs:
                out[i] = row
            hits += len(idxs)
        else:
            pending[msg] = idxs
    if pending:
        # one bulk Montgomery-limb conversion for all unique messages
        coords: list[int] = []
        for msg in pending:
            u0, u1 = ref_h2c.hash_to_field_fp2(msg, dst, 2)
            coords.extend((u0.c0.n, u0.c1.n, u1.c0.n, u1.c1.n))
        rows = fp.to_mont_host_bulk(coords).reshape(len(pending), 2, 2, fp.N_LIMBS)
        for k, (msg, idxs) in enumerate(pending.items()):
            # store a copy, not a view: a view's .base is the whole batch's
            # rows array, so one surviving LRU entry would pin all of it
            row = rows[k].copy()
            row.setflags(write=False)
            H2C_FIELD_CACHE.put((msg, dst), row)
            for i in idxs:
                out[i] = row
            hits += len(idxs) - 1  # intra-batch duplicates beyond the first
    _count_staging_cache("h2c", hits, len(pending))
    return out


def hash_to_g2_device(u: jnp.ndarray) -> Jac:
    """u: (..., 2, 2, 32) packed field elements -> G2 Jacobian points."""
    return map_to_g2(u[..., 0, :, :], u[..., 1, :, :])


# -- analyzer registry hooks ---------------------------------------------------
#
# The SSWU/isogeny/cofactor stages register individually in the fast tier
# (each traces in seconds); the fused hash_to_g2_device composite takes
# ~60 s to trace, so it is slow-tier (`scripts/lint.py --jaxpr
# --all-tiers` / the nightly @slow gate).

from . import registry as _reg


def _u2(batch=()):
    return np.zeros((*batch, 2, fp.N_LIMBS), np.int32)


@_reg.register("h2c.fp2_sqrt_candidate")
def _spec_sqrt():
    return fp2_sqrt_candidate, (_u2(),), [_reg.LIMB]


@_reg.register("h2c.iso3_map")
def _spec_iso3():
    a = _u2()
    return iso3_map, (a, a.copy()), [_reg.LIMB, _reg.LIMB]


@_reg.register("h2c.clear_cofactor", tier="slow")
def _spec_clear_cofactor():
    x = _u2((4,))

    def fn(x, y, z):
        return clear_cofactor(Jac(x, y, z))

    return fn, (x, x.copy(), x.copy()), [_reg.LIMB] * 3


@_reg.register("h2c.hash_to_g2_device", tier="slow")
def _spec_hash_to_g2():
    u = np.zeros((4, 2, 2, fp.N_LIMBS), np.int32)
    return hash_to_g2_device, (u,), [_reg.LIMB]
