"""Host-side packing between Python-int field elements / ref-backend objects
and the device limb representation (Montgomery form).

Only used at the host<->device boundary (loading constants, staging inputs,
reading back test results) — never inside jitted code.
"""

from __future__ import annotations

import numpy as np

from ..constants import (
    G1_GENERATOR_X,
    G1_GENERATOR_Y,
    G2_GENERATOR_X,
    G2_GENERATOR_Y,
    P,
)
from ..ref.curves import Point, g1_infinity, g2_infinity
from ..ref.fields import Fp, Fp2, Fp6, Fp12
from . import fp


def pack_fp(x: int) -> np.ndarray:
    return fp.to_mont_host(x)


def unpack_fp(limbs) -> int:
    return fp.from_mont_host(limbs)


def pack_fp2(c0: int, c1: int) -> np.ndarray:
    return np.stack([pack_fp(c0), pack_fp(c1)])


def unpack_fp2(arr) -> tuple[int, int]:
    return unpack_fp(arr[..., 0, :]), unpack_fp(arr[..., 1, :])


def pack_fp2_el(x: Fp2) -> np.ndarray:
    return pack_fp2(x.c0.n, x.c1.n)


def unpack_fp2_el(arr) -> Fp2:
    return Fp2.from_ints(*unpack_fp2(arr))


def pack_fp6_el(x: Fp6) -> np.ndarray:
    return np.stack([pack_fp2_el(x.c0), pack_fp2_el(x.c1), pack_fp2_el(x.c2)])


def unpack_fp6_el(arr) -> Fp6:
    return Fp6(unpack_fp2_el(arr[0]), unpack_fp2_el(arr[1]), unpack_fp2_el(arr[2]))


def pack_fp12_el(x: Fp12) -> np.ndarray:
    return np.stack([pack_fp6_el(x.c0), pack_fp6_el(x.c1)])


def unpack_fp12_el(arr) -> Fp12:
    arr = np.asarray(arr)
    return Fp12(unpack_fp6_el(arr[0]), unpack_fp6_el(arr[1]))


# -- points --------------------------------------------------------------------
#
# Device points are affine coordinate pairs plus an explicit infinity flag
# (branch-free code carries the flag; see curve.py). G1 coords are Fp limbs,
# G2 coords are Fp2 limb pairs.


def pack_g1(pt: Point) -> tuple[np.ndarray, np.ndarray, np.bool_]:
    if pt.inf:
        z = np.zeros(fp.N_LIMBS, np.int32)
        return z, z, np.bool_(True)
    return pack_fp(pt.x.n), pack_fp(pt.y.n), np.bool_(False)


def pack_g2(pt: Point) -> tuple[np.ndarray, np.ndarray, np.bool_]:
    if pt.inf:
        z = np.zeros((2, fp.N_LIMBS), np.int32)
        return z, z, np.bool_(True)
    return pack_fp2_el(pt.x), pack_fp2_el(pt.y), np.bool_(False)


def unpack_g1(x, y, inf) -> Point:
    if bool(inf):
        return g1_infinity()
    from ..ref.curves import _B1

    return Point(Fp(unpack_fp(x)), Fp(unpack_fp(y)), False, _B1)


def unpack_g2(x, y, inf) -> Point:
    if bool(inf):
        return g2_infinity()
    from ..ref.curves import _B2

    return Point(unpack_fp2_el(x), unpack_fp2_el(y), False, _B2)


# Packed generator constants (Montgomery limbs), used as safe substitutes for
# masked-out lanes in branch-free pairing code and as fixed pairing inputs.
G1_GEN_X_L = pack_fp(G1_GENERATOR_X)
G1_GEN_Y_L = pack_fp(G1_GENERATOR_Y)
G1_GEN_NEG_Y_L = pack_fp(P - G1_GENERATOR_Y)
G2_GEN_X_L = pack_fp2(*G2_GENERATOR_X)
G2_GEN_Y_L = pack_fp2(*G2_GENERATOR_Y)


# -- batch packing with per-point limb-row caching -----------------------------
#
# The batch packers are the host staging hot path (stage_sets,
# batch_validate_public_keys). Two levers keep them off the profile:
#   - limb rows are memoized on the Point object itself (the `_limbs` slot,
#     the bytes of its int32 (x, y) rows): a validator pubkey held by the
#     PubkeyCache is packed once per process lifetime, a signature re-staged
#     by bisection is packed once per batch failure — later stagings GATHER
#     the rows instead of re-deriving them.
#   - cache misses are converted in ONE `fp.to_mont_host_bulk` call (the
#     per-int Python shift/mask loop was ~10x the bigint mulmod cost).
# Output is byte-identical to stacking the per-point pack_g1/pack_g2 results.

def _count_staging_cache(cache: str, hits: int, misses: int) -> None:
    from ....common.metrics import (
        BLS_STAGING_CACHE_HITS_TOTAL,
        BLS_STAGING_CACHE_MISSES_TOTAL,
    )

    if hits:
        BLS_STAGING_CACHE_HITS_TOTAL.labels(cache=cache).inc(hits)
    if misses:
        BLS_STAGING_CACHE_MISSES_TOTAL.labels(cache=cache).inc(misses)


def _pack_batch(pts, row_shape, coords_of, split_rows, label):
    # A point's rows are kept as the bytes of its (2, *row_shape) int32
    # array (x, y), and an infinity point's are zeros: the batch's rows are
    # ONE bytes join, ~7x cheaper than assigning or concatenating per-point
    # arrays (a block's ~18,700 keys on a CPU host: ~2 ms against ~14). The
    # x and y returned are read-only views of that buffer.
    zero = bytes(8 * int(np.prod(row_shape)))
    rows = [zero if pt.inf else getattr(pt, "_limbs", None) for pt in pts]
    miss: dict[int, list[int]] = {}  # id(pt) -> positions (dedup in-batch)
    for i in [i for i, r in enumerate(rows) if r is None]:
        miss.setdefault(id(pts[i]), []).append(i)
    if miss:
        coords: list[int] = []
        for idxs in miss.values():
            coords.extend(coords_of(pts[idxs[0]]))
        limbs = fp.to_mont_host_bulk(coords)
        for k, idxs in enumerate(miss.values()):
            r = np.stack(split_rows(limbs, k)).tobytes()
            pts[idxs[0]]._limbs = r
            for i in idxs:
                rows[i] = r
    xy = np.frombuffer(b"".join(rows), dtype=np.int32).reshape(len(pts), 2, *row_shape)
    infs = np.fromiter((pt.inf for pt in pts), dtype=bool, count=len(pts))
    _count_staging_cache(label, len(pts) - int(infs.sum()) - len(miss), len(miss))
    return xy[:, 0], xy[:, 1], infs


def pack_g1_batch(pts: list[Point]) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    return _pack_batch(
        pts,
        (fp.N_LIMBS,),
        lambda pt: (pt.x.n, pt.y.n),
        lambda limbs, k: (limbs[2 * k], limbs[2 * k + 1]),
        "pk_limbs",
    )


def pack_g2_batch(pts: list[Point]) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    return _pack_batch(
        pts,
        (2, fp.N_LIMBS),
        lambda pt: (pt.x.c0.n, pt.x.c1.n, pt.y.c0.n, pt.y.c1.n),
        lambda limbs, k: (limbs[4 * k : 4 * k + 2], limbs[4 * k + 2 : 4 * k + 4]),
        "sig_limbs",
    )


def precompute_limbs(pt: Point) -> None:
    """Eagerly attach a point's packed limb rows (no-op for infinity or an
    already-warm point) — the PubkeyCache calls this at resolve time so the
    first batch that references a validator is already a cache hit."""
    if pt.inf or getattr(pt, "_limbs", None) is not None:
        return
    if isinstance(pt.x, Fp2):
        pack_g2_batch([pt])
    else:
        pack_g1_batch([pt])
