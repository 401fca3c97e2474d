"""Plain signature checks on the copied reference, and the two controls.

`verify_set` is the Ethereum FastAggregateVerify of one signature set,
done the straightforward way: sum the keys, check the signature is in G2,
hash the message to G2, and test e(-g1, sig) * e(agg, H(m)) == 1.

The controls are this reference with one guarantee broken, as a later
change might be tempted to break it:

- `uncleared_h2c`: hash-to-G2 without its cofactor clearing (the most
  expensive part of the map). Valid signatures then fail.
- `unit_scalars`: a block checked as one batch with every random scalar
  set to 1 (no ladders on keys and signatures). Two sets that swap
  signatures then pass together.
"""

from __future__ import annotations

from .constants import DST, R
from .curves import Point, _B1, _B2, g1_generator, g1_infinity, g2_infinity
from .fields import Fp, Fp2
from .hash_to_curve import hash_to_field_fp2, hash_to_g2, iso3_map, sswu
from .pairing import multi_pairing

CONTROLS = ("uncleared_h2c", "unit_scalars")


def g1_point(xy) -> Point:
    return Point(Fp(xy[0]), Fp(xy[1]), False, _B1)


def g2_point(c) -> Point:
    return Point(Fp2.from_ints(c[0], c[1]), Fp2.from_ints(c[2], c[3]), False, _B2)


def hash_to_g2_uncleared(msg: bytes, dst: bytes) -> Point:
    u0, u1 = hash_to_field_fp2(msg, dst, 2)
    return iso3_map(*sswu(u0)) + iso3_map(*sswu(u1))


def _aggregate(pks: list[Point]) -> Point:
    acc = g1_infinity()
    for pk in pks:
        acc = acc + pk
    return acc


def verify_set(pks: list[Point], msg: bytes, sig: Point, h2g2=hash_to_g2) -> bool:
    if not pks:
        return False
    agg = _aggregate(pks)
    if agg.inf or not sig.is_on_curve() or not sig.mul(R).inf:
        return False
    return multi_pairing([(-g1_generator(), sig), (agg, h2g2(msg, DST))]).is_one()


def verify_sets_unit_scalars(sets) -> bool:
    """All of `sets` ((pks, msg, sig) triples) as one batch with r_i = 1."""
    pairs, acc = [], g2_infinity()
    for pks, msg, sig in sets:
        pairs.append((_aggregate(pks), hash_to_g2(msg, DST)))
        acc = acc + sig
    pairs.append((-g1_generator(), acc))
    return multi_pairing(pairs).is_one()


def control_set(name: str, pks, msg, sig) -> bool:
    """One set's verdict under control `name`."""
    if name == "uncleared_h2c":
        return verify_set(pks, msg, sig, h2g2=hash_to_g2_uncleared)
    if name == "unit_scalars":
        return verify_sets_unit_scalars([(pks, msg, sig)])
    raise ValueError(f"unknown control {name!r}; expected one of {CONTROLS}")


def control_request(name: str, sets) -> bool:
    """A whole request's single verdict under control `name`."""
    if name == "unit_scalars":
        return verify_sets_unit_scalars(sets)
    return all(control_set(name, *s) for s in sets)
