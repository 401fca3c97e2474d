"""The `jax` BLS backend: batched, device-resident signature verification.

This is the accelerated counterpart of the reference's blst backend
(/root/reference/crypto/bls/src/impls/blst.rs). The verification workload —
hash-to-G2, subgroup checks, random-linear-combination accumulation, Miller
loops, one final exponentiation — runs on the device, as a chain of stage
programs per (batch-size, keys-per-set) bucket (_VERIFY_CHAIN):

    host:   expand_message_xmd (SHA-256), point decompression (no subgroup
            check — deferred to the device), RLC scalar sampling, packing
    device: SSWU/isogeny/cofactor hash-to-G2; psi-criterion subgroup checks
            for every signature; G1 ladders for r_i * aggpk_i; G2 ladders
            for r_i * sig_i; n+1 Miller loops; one final exponentiation.
            A batch with at most S/2 distinct messages sums the r_i * aggpk_i
            of each message first, and pairs S/2 rows (_GROUPED_CHAIN)

Semantics match the reference exactly (impls/blst.rs:36-119):
  - independent nonzero 64-bit scalars per set (RAND_BITS = 64)
  - empty batch and empty signing_keys are failures
  - infinity public keys are rejected (lib.rs:61-64)
  - signatures are subgroup-checked (device, Scott psi criterion)

Deliberate deviation: `Signature.from_bytes` here defers the subgroup check
to verification time (the device batch does it for free); the oracle checks
at deserialization. Both reject non-subgroup signatures before they count.

Batch shapes are bucketed to powers of two to bound XLA recompilation; the
compiled stage programs are shared across buckets where their shapes allow,
cached in-process and in the persistent JAX cache (jax_backend/__init__.py),
and `compile_verify_kernels` builds a bucket's missing stages concurrently.
"""

from __future__ import annotations

import secrets
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from functools import lru_cache, partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..constants import DST
from ..ref import api as _ref
from ..ref.curves import Point, g1_infinity, g2_infinity

# Re-used host-side types (serialization, keys, signing).
DecodeError = _ref.DecodeError
PublicKey = _ref.PublicKey
RAND_BITS = _ref.RAND_BITS

aggregate_public_keys = _ref.aggregate_public_keys
interop_secret_key_ref = _ref.interop_secret_key


def _coalescer():
    """The process-wide BatchVerifier when it is running over THIS backend
    module (crypto/bls/batch_verifier.py), else None. Single-set entry
    points route through it so gossip-path callers share device batches
    instead of each paying the S=4 padding floor + dispatch fixed cost."""
    import sys

    from ..batch_verifier import active_for

    return active_for(sys.modules[__name__])


def device_fingerprint(refresh_gauge: bool = True) -> dict:
    """Backend provenance: the platform, device and compile-cache state,
    which /metrics exports as an info-style gauge (value 1, identity in the
    labels). Host-side only — never called from (or reachable by) the
    jitted kernels, so trace purity is untouched."""
    from ..batch_verifier import DEFAULT_MAX_WAIT, DEFAULT_S_BUCKET
    from ....common.metrics import DEVICE_PROVENANCE_INFO

    devices = jax.devices()
    dev = devices[0]
    svc = _coalescer()
    info = {
        "platform": str(dev.platform),
        "device_kind": str(getattr(dev, "device_kind", dev.platform)),
        "chip_count": len(devices),
        "backend": str(jax.default_backend()),
        "jit_cache": {
            "verify_kernels_cached": sum(f.done() for f in list(_kernels.values())),
            "hits": _kernel_stats["hits"],
            "misses": _kernel_stats["misses"],
        },
        "coalescer": {
            "running": svc is not None,
            "s_bucket": int(svc.s_bucket) if svc is not None else DEFAULT_S_BUCKET,
            "max_wait": float(svc.max_wait) if svc is not None else DEFAULT_MAX_WAIT,
        },
    }
    if refresh_gauge:
        DEVICE_PROVENANCE_INFO.labels(
            platform=info["platform"],
            device_kind=info["device_kind"],
            chip_count=str(info["chip_count"]),
        ).set(1)
    return info


class Signature(_ref.Signature):
    """Signature whose verification runs on the accelerator.

    from_bytes decompresses and on-curve-checks on the host but defers the
    subgroup check to the device batch (see module docstring)."""

    @staticmethod
    def from_bytes(data: bytes) -> "Signature":
        return Signature(_ref.g2_from_compressed(data, subgroup_check=False))

    @staticmethod
    def infinity() -> "Signature":
        return Signature(g2_infinity())

    def verify(self, pk: PublicKey, message: bytes) -> bool:
        return self.fast_aggregate_verify([pk], message)

    def fast_aggregate_verify(self, pks: list[PublicKey], message: bytes) -> bool:
        if not pks:
            return False
        s = SignatureSet(signature=self, signing_keys=list(pks), message=message)
        svc = _coalescer()
        if svc is not None:
            # coalesced: the set rides a shared RLC batch (random nonzero
            # r_i keeps the single-set verdict exact); bisection blames it
            # individually if the shared batch fails
            return bool(svc.submit([s]).result()[0])
        return verify_signature_sets([s], rng=_ONE_RNG)

    def aggregate_verify(self, pks: list[PublicKey], messages: list[bytes]) -> bool:
        """Distinct-message aggregate verify (impls/blst.rs:246-257), mapped
        onto the batch kernel: n sets with r_i = 1; the aggregate signature
        rides on the first set, the rest carry infinity (sum = sig)."""
        if not pks or len(pks) != len(messages):
            return False
        sets = [
            SignatureSet(
                signature=self if i == 0 else Signature.infinity(),
                signing_keys=[pk],
                message=msg,
            )
            for i, (pk, msg) in enumerate(zip(pks, messages))
        ]
        return verify_signature_sets(sets, rng=_ONE_RNG)

    def eth_fast_aggregate_verify(self, pks: list[PublicKey], message: bytes) -> bool:
        if not pks and self.is_infinity():
            return True
        return self.fast_aggregate_verify(pks, message)


class SecretKey(_ref.SecretKey):
    def sign(self, message: bytes) -> Signature:
        return Signature(super().sign(message).point)

    @staticmethod
    def from_bytes(data: bytes) -> "SecretKey":
        if len(data) != 32:
            raise DecodeError("secret key must be 32 bytes")
        return SecretKey(int.from_bytes(data, "big"))

    @staticmethod
    def random() -> "SecretKey":
        return SecretKey(secrets.randbelow(_ref.R - 1) + 1)

    def public_key(self) -> PublicKey:
        return PublicKey(_ref.g1_generator().mul(self.k))


def aggregate_signatures(sigs: list["Signature"]) -> "Signature":
    if not sigs:
        raise ValueError("cannot aggregate empty signature list")
    acc = g2_infinity()
    for s in sigs:
        acc = acc + s.point
    return Signature(acc)


@dataclass
class SignatureSet:
    """{signature, signing_keys, message} — mirrors
    /root/reference/crypto/bls/src/generic_signature_set.rs:61-72."""

    signature: Signature
    signing_keys: list[PublicKey]
    message: bytes


def verify_signature_set(s: SignatureSet) -> bool:
    return s.signature.fast_aggregate_verify(s.signing_keys, s.message)


# -- the device kernel ---------------------------------------------------------


def _next_pow2(n: int, floor: int = 4) -> int:
    """Bucket size: next power of two, floored at 4 so that single-set
    verifies share the small-batch compiled kernel instead of each (S, K)
    shape compiling its own program."""
    return max(floor, 1 << max(0, (n - 1)).bit_length())


def batch_bucket(n_sets: int, max_keys: int, s_floor: int = 4) -> tuple[int, int]:
    """The (S, K) program a batch of `n_sets` sets of at most `max_keys`
    keys is staged into (stage_sets' padding rule)."""
    return _next_pow2(max(n_sets, s_floor)), _next_pow2(max_keys)


# Key slots per chunk of the chunked key layout (key_chunks).
KEY_CHUNK = 32


def key_chunks(S: int, K: int) -> int | None:
    """The chunks of KEY_CHUNK key slots the dispatch stages a bucket's
    keys in, or None where K <= KEY_CHUNK and the keys keep the dense
    (S, K) layout. Each set's keys fill ceil(k / KEY_CHUNK) chunks of their
    own, so a bucket of ragged sets (a block: 1 to 512 keys) stages about
    its real keys instead of S x K slots. N(S, K) = next_pow2(max(S K /
    (4 KEY_CHUNK), S + K / KEY_CHUNK)): room for every set's first chunk
    and one full K set, and a quarter of the dense slots (a block, 131 sets
    in (256, 512), needs 783 of 1,024). A batch that needs more chunks
    takes the next power of two (a chain of its own, built on first use)."""
    if K <= KEY_CHUNK:
        return None
    return _next_pow2(max(S * K // (4 * KEY_CHUNK), S + K // KEY_CHUNK))


def _tree_fold(F, pts, axis: int):
    """Sum projective points along `axis` with a pairwise halving tree:
    log2(n) batched additions instead of an n-step scan. Odd leftovers ride
    along unpaired. Safe without masking: the Renes–Costello–Batina complete
    formulas handle doubling and identity operands."""
    from .curve import Proj, add as p_add

    if axis != 0:
        pts = Proj(
            jnp.moveaxis(pts.x, axis, 0),
            jnp.moveaxis(pts.y, axis, 0),
            jnp.moveaxis(pts.z, axis, 0),
        )
    n = pts.x.shape[0]
    while n > 1:
        half = n // 2
        lo = Proj(pts.x[:half], pts.y[:half], pts.z[:half])
        hi = Proj(pts.x[half : 2 * half], pts.y[half : 2 * half], pts.z[half : 2 * half])
        summed = p_add(F, lo, hi)
        if n % 2:
            rem = Proj(pts.x[2 * half :], pts.y[2 * half :], pts.z[2 * half :])
            pts = Proj(
                jnp.concatenate([summed.x, rem.x]),
                jnp.concatenate([summed.y, rem.y]),
                jnp.concatenate([summed.z, rem.z]),
            )
        else:
            pts = summed
        n = pts.x.shape[0]
    return Proj(pts.x[0], pts.y[0], pts.z[0])


def _keys_stage(pk_x, pk_y, pk_inf, r_bits, chunk_set=None, spans: int = 1):
    """G1 side, per (S, K) bucket: aggregate each set's keys with a
    log-depth pairwise tree over the slot axis (the complete addition
    formulas make P+P and P+inf safe, so a plain halving tree needs no
    masking), then r_i * aggpk_i (G1 ladders, per-set 64-bit scalars).
    Returns (r_pk, any aggregate at infinity).

    Dense layout: (S, K) slots, one row a set. Chunked layout (key_chunks):
    (N, KEY_CHUNK) slots and `chunk_set` (N,), the set of each chunk
    (sorted; S for an unused chunk), no set spanning more than `spans`
    chunks: each chunk is folded, then a segmented scan of log2(spans)
    steps sums each set's chunks (_segment_sums). The dense layout is the
    chunked one with N = S and no scan."""
    from .curve import FP, from_affine, is_infinity, scalar_mul_bits

    pks = from_affine(FP, pk_x, pk_y, pk_inf)  # (S, K) or (N, KEY_CHUNK) batch
    agg = _tree_fold(FP, pks, axis=1)
    if chunk_set is not None:
        agg = _segment_sums(agg, chunk_set, r_bits.shape[0], spans.bit_length() - 1)
    return scalar_mul_bits(FP, agg, r_bits), jnp.any(is_infinity(FP, agg))


def _sigs_stage(sig_x, sig_y, sig_inf, r_bits):
    """G2 side, per S: subgroup checks (psi criterion: psi(sig) ==
    -[|z|]sig) and r_i * sig_i share ONE 2-stacked ladder, then
    sig_acc = sum_i r_i sig_i by a log-depth tree over S. Returns
    (sig_acc, all subgroup checks passed)."""
    from . import pairing
    from .curve import FP2, Proj, _stack2, eq_points, from_affine, is_infinity
    from .curve import neg as p_neg, psi, scalar_mul_bits

    sigs = from_affine(FP2, sig_x, sig_y, sig_inf)
    absx = jnp.broadcast_to(jnp.asarray(pairing._ABS_X_BITS_MSB[-64:]), r_bits.shape)
    both = scalar_mul_bits(FP2, _stack2(FP2, sigs, sigs), jnp.stack([absx, r_bits]))
    zsig = Proj(both.x[0], both.y[0], both.z[0])  # [|z|] sig
    rsig = Proj(both.x[1], both.y[1], both.z[1])  # [r] sig
    sub_ok = eq_points(FP2, psi(sigs), p_neg(FP2, zsig)) | is_infinity(FP2, sigs)
    return _tree_fold(FP2, rsig, axis=0), jnp.all(sub_ok)


def _segment_sums(pts, seg, n: int, steps: int):
    """G1 points summed per segment: lane i of `pts` belongs to segment
    seg[i], each segment's lanes contiguous; returns the n sums of
    segments 0..n-1 (a segment with no lane is infinity).

    A segmented inclusive scan (Hillis-Steele) over the lanes: `steps`
    steps, each one complete addition per lane, which adds the lane d = 2^k
    back only when it holds the same segment; the steps are one loop, so
    the addition compiles once. After them each lane holds the sum of the
    last 2^steps lanes of its segment, so a segment of at most that many
    lanes has its sum in its last lane."""
    from .curve import FP, Proj, _sel, add, infinity

    L = seg.shape[0]
    lane = jnp.arange(L, dtype=jnp.int32)
    inf = infinity(FP, (L,))

    def step(k, p):
        d = jnp.left_shift(jnp.int32(1), k)
        back = Proj(*(jnp.roll(c, d, axis=0) for c in p))
        same = (lane >= d) & (jnp.roll(seg, d) == seg)
        return add(FP, p, _sel(FP, same, back, inf))

    p = lax.fori_loop(0, steps, step, pts)
    rows = jnp.arange(n, dtype=jnp.int32)
    last = jnp.max(jnp.where(seg[None, :] == rows[:, None], lane[None, :], -1), axis=1)
    sums = Proj(*(jnp.take(c, last, axis=0, mode="clip") for c in p))
    return _sel(FP, last >= 0, sums, infinity(FP, (n,)))


def _group_sums(r_pk, group, U: int):
    """G1 side of the grouped chain: the S points r_i * aggpk_i summed per
    message group into U points, one per staged message row. The host
    plan (_message_plan) orders the rows so each group is contiguous and
    numbers the groups 0, 1, ... in row order. A scan of log2(S) steps
    (_segment_sums), as a group may span every row; a group with no row
    (an unused message row) is infinity, so its Miller pair is 1."""
    return _segment_sums(r_pk, group, U, group.shape[0].bit_length() - 1)


def _pairs_stage(r_pk, H, sig_acc):
    """S+1 Miller pairs: (r_i aggpk_i, H_i) and (-g1, local sig_acc),
    reduced to one Fp12 partial product.

    Batch-affine: every denominator reduces to one Fp value — a G1 z
    directly, a G2 z through its norm z0^2 + z1^2 (1/(z0 + z1 u) =
    (z0 - z1 u)/norm) — so all 2S+1 conversions share ONE Fermat
    inversion via fp.batch_inv instead of paying a ~380-squaring chain
    each. Infinity lanes carry z = 0 -> inv0 -> zeroed affine coords,
    byte-identical to per-point to_affine."""
    from . import fp, pairing
    from .curve import FP, FP2, Proj, is_infinity
    from .pack import G1_GEN_NEG_Y_L, G1_GEN_X_L
    from .tower import fp2_mul

    S = r_pk.x.shape[0]
    g2_z = jnp.concatenate([H.z, sig_acc.z[None]], axis=0)  # (S+1, 2, 32)
    z0, z1 = g2_z[..., 0, :], g2_z[..., 1, :]
    zsq = fp.sqr(jnp.stack([z0, z1]))
    dens = jnp.concatenate([r_pk.z, fp.add(zsq[0], zsq[1])], axis=0)
    inv_all = fp.batch_inv(dens)  # (2S+1, 32)
    g1_aff = fp.mul(
        jnp.stack([r_pk.x, r_pk.y]), jnp.broadcast_to(inv_all[:S], (2, S, 32))
    )
    pk_ainf = is_infinity(FP, r_pk)
    nm = fp.mul(jnp.stack([z0, z1]), jnp.broadcast_to(inv_all[S:], (2, S + 1, 32)))
    zinv2 = jnp.stack([nm[0], fp.neg(nm[1])], axis=-2)  # conj(z) * norm^-1
    g2_aff = fp2_mul(
        jnp.stack([jnp.concatenate([H.x, sig_acc.x[None]], axis=0),
                   jnp.concatenate([H.y, sig_acc.y[None]], axis=0)]),
        jnp.broadcast_to(zinv2, (2, S + 1, 2, 32)),
    )
    px = jnp.concatenate([g1_aff[0], jnp.asarray(G1_GEN_X_L)[None]], axis=0)
    py = jnp.concatenate([g1_aff[1], jnp.asarray(G1_GEN_NEG_Y_L)[None]], axis=0)
    p_in = jnp.concatenate([pk_ainf, jnp.zeros(1, bool)])
    qx, qy = g2_aff[0], g2_aff[1]
    q_in = is_infinity(FP2, Proj(qx, qy, g2_z))

    f = pairing.miller_loop(px, py, p_in, qx, qy, q_in)
    return pairing.product_reduce(f)


def _final_stage(partial, sub_ok, agg_inf):
    """The one final exponentiation of the batch, and the verdict."""
    from . import pairing
    from .tower import fp12_is_one

    return fp12_is_one(pairing.final_exponentiation(partial)) & sub_ok & ~agg_inf


def verify_pipeline_local(pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, u, r_bits):
    """The per-shard verification pipeline: everything except the final
    exponentiation, for S_local sets x K keys/set.

    Returns (miller_partial, ok_flags): the product of the local Miller
    values INCLUDING this shard's own (-g1, sum_local r_i sig_i) pair, and
    the AND of local subgroup/infinity checks. Partial products from
    different shards just multiply:
        prod_shards e(-g1, sum_local r s) = e(-g1, sum_global r s),
    so multi-chip reduction is an all-gather of one Fp12 per shard followed
    by one replicated final exponentiation (SURVEY.md §2.8 item 1).

    Single-chip callers multiply nothing: final_exponentiation(partial).
    The single-chip path runs the same stages as separate programs
    (_VERIFY_CHAIN)."""
    from . import h2c

    r_pk, agg_inf = _keys_stage(pk_x, pk_y, pk_inf, r_bits)
    sig_acc, sub_ok = _sigs_stage(sig_x, sig_y, sig_inf, r_bits)
    partial = _pairs_stage(r_pk, h2c.hash_to_g2_device(u), sig_acc)
    return partial, sub_ok & ~agg_inf


def _pack_staged(staged) -> np.ndarray:
    """Write the staged arrays into ONE int32 buffer: a single host->device
    transfer instead of eight, paying the per-transfer fixed cost once.
    Each array is copied once, straight into its place (a block's buffer
    is 34 MB)."""
    staged = [np.asarray(a) for a in staged]
    flat = np.empty(sum(a.size for a in staged), dtype=np.int32)
    off = 0
    for a in staged:
        flat[off : off + a.size].reshape(a.shape)[...] = a
        off += a.size
    return flat


def _unpack_staged(flat, chain: "Chain"):
    staged, off = {}, 0
    for name, shape, is_bool in chain.staged_fields():
        n = int(np.prod(shape))
        a = flat[off : off + n].reshape(shape)
        staged[name] = a.astype(bool) if is_bool else a
        off += n
    if chain.U is not None:  # the grouped chain hashes its U rows at S-row width
        staged["u"] = jnp.concatenate([staged["u"]] * (chain.S // chain.U))
    return tuple(staged.values())


# -- the single-chip program chain ---------------------------------------------
#
# One batch runs as a chain of stage programs instead of one fused program:
# compiled whole, a bucket was 7-9 min of TPU compile and ~10 GB of host
# memory at its peak (PR 21 AOT), because XLA's cost grows faster than the
# program; the stages compile in seconds to ~2 min each, concurrently, and the
# final exponentiation (shape-free) and the S-only stages are shared by
# every bucket that needs them. Intermediates stay on the device.
#
# A Chain is the one place a batch's shapes and programs are decided: bucket
# (S, K), U message rows where it groups sets by message, N chunks of
# KEY_CHUNK key slots where K > KEY_CHUNK (key_chunks: a set's keys in chunks
# of its own, in place of S x K slots). It gives each stage program's key,
# only the shapes that program depends on (so chains share programs), and
# the staged buffer's fields by name. _dispatch_chain chooses a batch's chain.
#
# Hash-to-G2 is a stage of its own, with a barrier inside (h2c.map_to_g2):
# fused whole, the TPU compiler got the map's y wrong (my chip runs, PR 21).
#
# Each bucket with S >= GROUP_MIN_S has a second chain, for batches whose
# sets carry at most U = S/2 distinct messages (gossip attestations share a
# committee's AttestationData): by bilinearity
#     prod_i e(r_i pk_i, H(m_i)) = prod_m e(sum_{i: m_i = m} r_i pk_i, H(m)),
# so the `group` stage sums the G1 side per message and the Miller loop runs
# over U rows: the very pairs program of a (U, K) bucket. Every set keeps its
# own scalar r_i, and sigs still checks all S signatures. Hash-to-G2 stays
# the S-row program, over the U message rows tiled S/U times, and `group`
# keeps its first U points: on a v5e the program compiled for 64 rows gave
# wrong points for most of the grouped chain's rows, where the 128-row
# program, fed the same messages, was right.

_VERIFY_CHAIN = ("unpack", "keys", "sigs", "h2c", "pairs", "final")
_GROUPED_CHAIN = ("unpack", "keys", "sigs", "h2c", "group", "pairs", "final")
GROUP_MIN_S = 8


@dataclass(frozen=True)
class Chain:
    """The chain of stage programs a batch runs: bucket (S, K), U message
    rows (the grouped chain) or None (the full chain, a row a set), N key
    chunks (the chunked layout) or None (the dense (S, K) layout)."""

    S: int
    K: int
    U: int | None = None
    N: int | None = None

    @staticmethod
    def bucket(S: int, K: int) -> list["Chain"]:
        """The chains bucket (S, K) is built with: its full chain and, where
        S >= GROUP_MIN_S, its grouped chain over S/2 rows, both with the
        bucket's key_chunks(S, K)."""
        N = key_chunks(S, K)
        return [Chain(S, K, None, N)] + ([Chain(S, K, S // 2, N)] if S >= GROUP_MIN_S else [])

    def __lt__(self, other: "Chain") -> bool:  # a total order: None sorts first
        return (self.S, self.K, self.U or 0, self.N or 0) < (
            other.S, other.K, other.U or 0, other.N or 0)

    def stage_keys(self) -> list[tuple]:
        """The key of each stage program, (name, *shapes): unpack (S, K, U,
        N), keys (S, K, N), sigs and h2c (S), group (S, U), pairs its rows
        (U, or S), final none."""
        S, K, U, N = self.S, self.K, self.U, self.N
        shapes = {"unpack": (S, K, U, N), "keys": (S, K, N), "sigs": (S,), "h2c": (S,),
                  "group": (S, U), "pairs": (U or S,), "final": ()}
        names = _VERIFY_CHAIN if U is None else _GROUPED_CHAIN
        return [(name, *shapes[name]) for name in names]

    def staged_fields(self) -> list[tuple[str, tuple, bool]]:
        """(name, shape, is_bool) of each staged array, in the buffer's
        order: the keys in (S, K) slots, or (N, KEY_CHUNK) with each chunk's
        set in chunk_set; u one row a set, or U message rows with each
        set's row in group."""
        S = self.S
        slots = (S, self.K) if self.N is None else (self.N, KEY_CHUNK)
        fields = [
            ("pk_x", (*slots, 32), False),
            ("pk_y", (*slots, 32), False),
            ("pk_inf", slots, True),
            ("sig_x", (S, 2, 32), False),
            ("sig_y", (S, 2, 32), False),
            ("sig_inf", (S,), True),
            ("u", (self.U or S, 2, 2, 32), False),
            ("r_bits", (S, 64), False),
        ]
        if self.N is not None:
            fields.append(("chunk_set", (self.N,), False))
        if self.U is not None:
            fields.append(("group", (S,), False))
        return fields


def verify_stage(key: tuple):
    """(fn, arg specs) of one stage program of the chain, by stage key."""
    from .curve import Proj

    name, *shape = key
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    b = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)  # noqa: E731
    g1 = lambda S: Proj(*[i32(S, 32)] * 3)  # noqa: E731
    g2 = lambda S: Proj(*[i32(S, 2, 32)] * 3)  # noqa: E731
    if name == "final":
        return _final_stage, (i32(2, 3, 2, 32), b(), b())
    S, *rest = shape  # pairs: its rows
    if name == "unpack":
        chain = Chain(S, *rest)
        n = sum(int(np.prod(sh)) for _, sh, _ in chain.staged_fields())

        def _unpack_stage(flat):  # named: traces and profiles show the jit name
            return _unpack_staged(flat, chain)

        return _unpack_stage, (i32(n),)
    if name == "keys":
        K, N = rest
        if N is None:
            return _keys_stage, (i32(S, K, 32), i32(S, K, 32), b(S, K), i32(S, 64))
        C = KEY_CHUNK
        keys = partial(_keys_stage, spans=K // C)
        keys.__name__ = _keys_stage.__name__  # the jit name traces and profiles show
        return keys, (i32(N, C, 32), i32(N, C, 32), b(N, C), i32(S, 64), i32(N))
    if name == "sigs":
        return _sigs_stage, (i32(S, 2, 32), i32(S, 2, 32), b(S), i32(S, 64))
    if name == "h2c":
        from .h2c import hash_to_g2_device

        return hash_to_g2_device, (i32(S, 2, 2, 32),)
    if name == "group":
        (U,) = rest

        def _group_stage(r_pk, group, H):  # named: traces and profiles show the jit name
            # H: the S-row hash of the tiled message rows; its first U are theirs
            return _group_sums(r_pk, group, U), Proj(*(c[:U] for c in H))

        return _group_stage, (g1(S), i32(S), g2(S))
    return _pairs_stage, (g1(S), g2(S), Proj(*[i32(2, 32)] * 3))  # pairs


def _lower_stage(key: tuple):
    fn, specs = verify_stage(key)
    return jax.jit(fn).lower(*specs)


# stage key -> Future of its compiled program. One build per stage per
# process: a caller that meets a stage another thread is compiling waits
# for that compile instead of starting a second one.
_kernels: dict[tuple, Future] = {}
_kernels_lock = threading.Lock()
_kernel_stats = {"hits": 0, "misses": 0}
_chains: dict[Chain, object] = {}
# concurrent stage compiles: each peaks at a few GB of host memory
COMPILE_WORKERS = 4


def _claim_kernel(key: tuple) -> tuple[Future, bool]:
    """(future, owner): owner is True for the one caller that must build."""
    from ....common.metrics import BLS_JIT_BUILDS_TOTAL

    with _kernels_lock:
        fut = _kernels.get(key)
        if fut is not None:
            _kernel_stats["hits"] += 1
            return fut, False
        _kernel_stats["misses"] += 1
        fut = _kernels[key] = Future()
    BLS_JIT_BUILDS_TOTAL.labels(kernel="verify").inc()
    return fut, True


def _fail_kernel(fut: Future, key: tuple, exc: BaseException) -> None:
    """A failed build is not cached: every waiter re-raises `exc`, and the
    next caller of the stage builds again."""
    with _kernels_lock:
        _kernels.pop(key, None)
    fut.set_exception(exc)


def _stage_label(key: tuple) -> str:
    """A stage key as the profiler annotation of its build: keys-128-4."""
    return "-".join(str(p) for p in key if p is not None)


# The persistent-cache result of the compile running in this thread: JAX
# records a cache read as `cache_hits` and a cache write as `cache_misses`,
# in the compiling thread.
_compile_cache = threading.local()
_CACHE_RESULT = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}


def _note_cache_event(event: str, **_) -> None:
    if event in _CACHE_RESULT:
        _compile_cache.result = _CACHE_RESULT[event]


jax.monitoring.register_event_listener(_note_cache_event)


def _build_kernel(fut: Future, key: tuple, lowered) -> None:
    from ....common.metrics import BLS_COMPILE_CACHE_TOTAL
    from ....common.tracing import span

    try:
        _compile_cache.result = "uncached"
        with span("bls_compile", stage=_stage_label(key)):
            compiled = lowered.compile()
        BLS_COMPILE_CACHE_TOTAL.labels(result=_compile_cache.result).inc()
        fut.set_result(compiled)
    except BaseException as exc:
        _fail_kernel(fut, key, exc)


def compile_verify_kernels(buckets) -> dict[tuple[int, int], float]:
    """Build the stage programs of the given (S, K) buckets ahead of their
    first batch: each bucket's full chain and, where S >= GROUP_MIN_S, its
    grouped chain. Tracing holds the GIL, so stages are lowered one at a
    time in this thread, the full chains' in bucket order and then the
    grouped chains' (their own programs load or compile while the last
    full-chain programs still do); XLA compiles release it, so up to
    COMPILE_WORKERS compile concurrently. Returns the seconds from this
    call until each bucket's last stage was ready (a persistent-cache hit
    is a load, not a compile). Raises the first build error.

    Each stage's lowering is a `bls_lower` span in this thread and its XLA
    compile or cache load a `bls_compile` span in a pool thread; the
    profiler annotation of each carries the stage key. Each build counts
    its persistent-cache result (lighthouse_tpu_bls_compile_cache_total)."""
    ready = _compile_chains([chain for S, K in buckets for chain in Chain.bucket(S, K)])
    return {(S, K): max(ready[chain] for chain in Chain.bucket(S, K)) for S, K in buckets}


def _compile_chains(chains) -> dict[Chain, float]:
    """Build the stage programs of the given chains (compile_verify_kernels'
    work): the seconds until each chain's last stage was ready."""
    from ....common.tracing import span

    t0 = time.perf_counter()
    futures: dict[tuple, Future] = {}
    done_at: dict[tuple, float] = {}

    def note(key):
        return lambda _fut: done_at.setdefault(key, time.perf_counter() - t0)

    chains = sorted(chains, key=lambda chain: chain.U is not None)  # stable: full chains first
    with ThreadPoolExecutor(max_workers=COMPILE_WORKERS) as pool:
        for chain in chains:
            for key in chain.stage_keys():
                if key in futures:
                    continue
                fut, owner = _claim_kernel(key)
                futures[key] = fut
                if owner:
                    try:
                        with span("bls_lower", stage=_stage_label(key)):
                            lowered = _lower_stage(key)
                    except BaseException as exc:
                        _fail_kernel(fut, key, exc)
                        raise
                    pool.submit(_build_kernel, fut, key, lowered)
                fut.add_done_callback(note(key))
    ready = {}
    for chain in chains:
        keys = chain.stage_keys()
        for key in keys:
            futures[key].result()
        ready[chain] = max(done_at[key] for key in keys)
    return ready


def compiled_stage(key: tuple):
    """The stage program of a stage key that compile_verify_kernels has
    built. chip_smoke.py checks the device hash-to-G2 of each width with
    it."""
    return _kernels[key].result()


def _verify_kernel(chain: Chain):
    """The batch-verify chain: flat staged buffer (see _pack_staged) ->
    device bool, dispatched stage by stage without a host sync. A bucket's
    own chains are built as compile_verify_kernels builds them; any other
    chain (over budget) alone, on first use."""
    run = _chains.get(chain)
    if run is not None:
        return run
    if chain in Chain.bucket(chain.S, chain.K):
        compile_verify_kernels([(chain.S, chain.K)])
    else:
        _compile_chains([chain])
    prog = {key[0]: _kernels[key].result() for key in chain.stage_keys()}
    fields = [name for name, _, _ in chain.staged_fields()]
    chunked = chain.N is not None

    def run(flat):
        a = dict(zip(fields, prog["unpack"](flat)))
        r_pk, agg_inf = prog["keys"](a["pk_x"], a["pk_y"], a["pk_inf"], a["r_bits"],
                                     *([a["chunk_set"]] if chunked else []))
        sig_acc, sub_ok = prog["sigs"](a["sig_x"], a["sig_y"], a["sig_inf"], a["r_bits"])
        H = prog["h2c"](a["u"])
        if "group" in prog:
            r_pk, H = prog["group"](r_pk, a["group"], H)
        return prog["final"](prog["pairs"](r_pk, H, sig_acc), sub_ok, agg_inf)

    _chains[chain] = run
    return run


_ONE_RNG = "ones"  # sentinel: r_i = 1 (single-set / aggregate-verify paths)


def _scalar_bits(r: int) -> np.ndarray:
    """Per-scalar slow path (tests assert _scalar_bits_batch against it)."""
    return np.array([(r >> (63 - i)) & 1 for i in range(64)], dtype=np.int32)


def _scalar_bits_batch(rs) -> np.ndarray:
    """Bulk `_scalar_bits`: (n, 64) int32 MSB-first bit rows. Big-endian
    byte view + np.unpackbits replaces the n*64 Python shift loop."""
    a = np.asarray(list(rs), dtype=">u8")
    if a.size == 0:
        return np.empty((0, 64), dtype=np.int32)
    return np.unpackbits(a.view(np.uint8)).reshape(-1, 64).astype(np.int32)


@lru_cache(maxsize=1)
def _pad_generator() -> Point:
    """One process-wide generator Point for S-bucket padding rows, so its
    packed limb rows are computed once ever instead of once per staging."""
    return _ref.g1_generator()


def _batched_nonzero_scalars(n: int) -> np.ndarray:
    """n independent nonzero 64-bit scalars from ONE entropy draw
    (re-drawing any zeros), replacing n sequential secrets.randbits calls."""
    out = np.frombuffer(secrets.token_bytes(8 * n), dtype=np.uint64).copy()
    while True:
        zeros = np.flatnonzero(out == 0)
        if zeros.size == 0:
            return out
        out[zeros] = np.frombuffer(secrets.token_bytes(8 * zeros.size), dtype=np.uint64)


def _message_plan(messages: list[bytes], S: int):
    """How a batch of S rows whose sets carry `messages` groups by message:
    None where it takes the full chain (U = S: S < GROUP_MIN_S, or more
    than S/2 distinct messages), else (order, group, rows) for the grouped
    chain's U = S/2 message rows:

    - order (S,): the staged rows' new order, the real sets stably sorted
      by group so each group is contiguous, the padding rows after them;
    - group (S,) int32: each reordered row's group, numbered by first
      appearance; padding rows (r = 0, identity) join the last group and
      take no message row;
    - rows: the U messages, the distinct ones in group order, the unused
      rows repeating the first (their G1 sum is infinity: Miller value 1)."""
    ids: dict[bytes, int] = {}
    for m in messages:
        ids.setdefault(m, len(ids))
    U = S // 2
    if S < GROUP_MIN_S or len(ids) > U:
        return None
    n = len(messages)
    gid = np.fromiter((ids[m] for m in messages), dtype=np.int32, count=n)
    order = np.concatenate([np.argsort(gid, kind="stable"), np.arange(n, S)])
    group = np.full(S, len(ids) - 1, dtype=np.int32)
    group[:n] = gid[order[:n]]
    rows = list(ids) + [messages[0]] * (U - len(ids))
    return order, group, rows


def stage_sets(sets: list[SignatureSet], rng=None, s_floor: int = 4):
    """Host staging for the device kernels, in the dense key layout that
    verify_pipeline_local takes: pad the batch to the S bucket (pow2, >=
    s_floor) with (generator-keyed, r=0) no-op sets and each key list to
    the K bucket with infinity points (additive identity). Returns (pk_x,
    pk_y, pk_inf, sig_x, sig_y, sig_inf, u, r_bits) numpy arrays.

    This is the staging FAST path: point limb rows are gathered from the
    per-point cache (pack.py) with misses bulk-converted, hash-to-field
    runs once per unique message with an LRU in front (h2c.py), and the
    RLC scalars are drawn/bit-expanded in one batched call. Output is
    byte-identical to the per-element slow path (asserted in
    tests/test_staging.py); the whole call is timed as the `bls_stage`
    span / lighthouse_tpu_bls_stage_seconds, and its S rows, key slots,
    key layout and message rows are counted, real and padding
    (lighthouse_tpu_bls_staged_rows_total, ..._staged_key_slots_total,
    ..._keys_layout_total, ..._message_rows_total)."""
    return tuple(_stage(sets, rng, s_floor, lambda sets, S, K: (Chain(S, K), None))[0].values())


def stage_sets_grouped(sets: list[SignatureSet], rng=None, s_floor: int = 4):
    """The dispatch's staging: stage_sets, for the chain _dispatch_chain
    chooses inside the same `bls_stage` span. Returns (staged, group):
    staged is stage_sets' arrays in the plan's row order, its keys
    (N, KEY_CHUNK) slots followed by the (N,) chunk_set after r_bits where
    chunked, u the U message rows where grouped; group the (S,) int32 group
    index of each row, or None where the batch takes the full chain."""
    staged, _ = _stage(sets, rng, s_floor, _dispatch_chain)
    group = staged.pop("group", None)
    return tuple(staged.values()), group


def _dispatch_chain(sets, S: int, K: int):
    """The chain a batch in bucket (S, K) dispatches to, and its message
    plan: grouped where _message_plan groups the sets; the keys in the
    bucket's key_chunks(S, K) chunks where K > KEY_CHUNK, or in the next
    power of two of the chunks they fill where that is more (over budget)."""
    plan = _message_plan([s.message for s in sets], S)
    N = key_chunks(S, K)
    if N is not None:  # a set fills ceil(k / KEY_CHUNK) chunks, a padding row one
        used = S - len(sets) + sum(-(-len(s.signing_keys) // KEY_CHUNK) for s in sets)
        N = max(N, _next_pow2(used))
    return Chain(S, K, None if plan is None else S // 2, N), plan


def _stage(sets, rng, s_floor: int, choose):
    """Stage a batch for the chain, and message plan or None, that
    choose(sets, S, K) gives in its bucket. Returns (staged, chain): staged
    the arrays by name, in the chain's buffer order."""
    from ....common.metrics import (
        BLS_BATCH_PADDED_SIZE,
        BLS_KEYS_LAYOUT_TOTAL,
        BLS_MESSAGE_ROWS_TOTAL,
        BLS_STAGE_SECONDS,
        BLS_STAGED_KEY_SLOTS_TOTAL,
        BLS_STAGED_ROWS_TOTAL,
    )
    from ....common.tracing import span
    from . import h2c
    from .pack import pack_g1_batch, pack_g2_batch

    S, K = batch_bucket(len(sets), max(len(s.signing_keys) for s in sets), s_floor)
    BLS_BATCH_PADDED_SIZE.observe(S)
    n = len(sets)
    msgs = [s.message for s in sets]
    BLS_STAGED_ROWS_TOTAL.labels(row="set").inc(n)
    BLS_STAGED_ROWS_TOTAL.labels(row="pad").inc(S - n)

    with BLS_STAGE_SECONDS.time(), span("bls_stage"):
        chain, plan = choose(sets, S, K)
        # the plan orders the real rows (its padding rows stay last), so a
        # chunked set's chunks follow the grouped row order
        order = None if plan is None else plan[0][:n]
        rows = sets if order is None else [sets[i] for i in order]
        # Only the real keys and the padding rows' generators are packed;
        # the other key slots are infinity (zero limbs, flag set) and are
        # never a Point.
        key_pts: list[Point] = []
        n_row = np.ones(S, dtype=np.int64)  # keys in each row
        for i, s in enumerate(rows):
            key_pts.extend(pk.point for pk in s.signing_keys)
            n_row[i] = len(s.signing_keys)
        sig_pts = [s.signature.point for s in rows]
        if S > n:
            key_pts.extend([_pad_generator()] * (S - n))
            sig_pts.extend([g2_infinity()] * (S - n))
            # r stays 0: the padded set contributes the identity everywhere.
        # Where each row's keys start: row i at slot i*K (dense), or at its
        # first chunk's first slot (chunked: ceil(k / KEY_CHUNK) chunks a
        # row, one for a padding row, the unused chunks last, set index S).
        N, chunk_set = chain.N, None
        if N is None:
            slots, row_slot = (S, K), np.arange(S) * K
        else:
            n_chunk = -(-n_row // KEY_CHUNK)
            used = int(n_chunk.sum())
            slots = (N, KEY_CHUNK)
            row_slot = (np.cumsum(n_chunk) - n_chunk) * KEY_CHUNK
            chunk_set = np.full(N, S, dtype=np.int32)
            chunk_set[:used] = np.repeat(np.arange(S, dtype=np.int32), n_chunk)
        # key g of row i sits at the row's first slot + (g - its first key)
        slot = np.arange(len(key_pts)) + np.repeat(row_slot - (np.cumsum(n_row) - n_row), n_row)

        r_rows = np.zeros((S, 64), dtype=np.int32)
        if n:
            if rng is _ONE_RNG:
                rs = [1] * n
            elif rng is None:
                rs = _batched_nonzero_scalars(n)
            else:
                # seeded-rng seam: per-set draws in submission order, exactly
                # like the slow path, so deterministic tests stay stable
                rs = []
                for _ in range(n):
                    r = 0
                    while r == 0:
                        r = rng(RAND_BITS)
                    rs.append(r)
            bits = _scalar_bits_batch(rs)
            r_rows[:n] = bits if order is None else bits[order]

        with span("bls_pack"):
            x, y, inf = pack_g1_batch(key_pts)
            n_slots = slots[0] * slots[1]
            pk_x = np.zeros((n_slots, x.shape[-1]), dtype=np.int32)
            pk_y = np.zeros(pk_x.shape, dtype=np.int32)
            pk_inf = np.ones(n_slots, dtype=bool)
            pk_x[slot], pk_y[slot], pk_inf[slot] = x, y, inf
            pk_x = pk_x.reshape(*slots, -1)
            pk_y = pk_y.reshape(*slots, -1)
            pk_inf = pk_inf.reshape(slots)
            sig_x, sig_y, sig_inf = pack_g2_batch(sig_pts)
        with span("bls_h2c_host"):
            u = h2c.hash_to_field_limbs(
                msgs + [b""] * (S - n) if plan is None else plan[2])
    arrays = {"pk_x": pk_x, "pk_y": pk_y, "pk_inf": pk_inf, "sig_x": sig_x, "sig_y": sig_y,
              "sig_inf": sig_inf, "u": u, "r_bits": r_rows, "chunk_set": chunk_set,
              "group": None if plan is None else plan[1]}
    n_keys = len(key_pts) - (S - n)
    layout = "dense" if N is None else "chunked" if N == key_chunks(S, K) else "chunked_over_budget"
    BLS_KEYS_LAYOUT_TOTAL.labels(layout=layout).inc()
    BLS_STAGED_KEY_SLOTS_TOTAL.labels(slot="key").inc(n_keys)
    BLS_STAGED_KEY_SLOTS_TOTAL.labels(slot="pad").inc(n_slots - n_keys)
    distinct = len(set(msgs))
    BLS_MESSAGE_ROWS_TOTAL.labels(row="distinct").inc(distinct)
    BLS_MESSAGE_ROWS_TOTAL.labels(row="pad").inc(len(u) - distinct)
    return {name: arrays[name] for name, _, _ in chain.staged_fields()}, chain


def drop_staging_caches(sets) -> None:
    """Test helper: forget every staging cache a batch could hit — the
    process-wide h2c LRU and the per-point limb rows of all referenced
    points — so the next stage_sets runs fully cold. Keeping the
    invalidation next to the caches stops a warm-vs-cold test from
    silently measuring a half-warm baseline when a cache is added."""
    from . import h2c

    h2c.H2C_FIELD_CACHE.clear()
    try:
        # the process-wide padding generator keeps its limb rows across
        # batches; a padded "cold" measurement must not gather them
        del _pad_generator()._limbs
    except AttributeError:
        pass
    for s in sets:
        for pk in s.signing_keys:
            try:
                del pk.point._limbs
            except AttributeError:
                pass
        try:
            del s.signature.point._limbs
        except AttributeError:
            pass


def precompute_pubkey_limbs(pk: PublicKey) -> None:
    """PubkeyCache hook (state_transition/context.py): attach the packed
    limb rows to a freshly resolved validator pubkey so its first staged
    batch is already a pk_limbs cache hit. Computed once per validator
    lifetime — the rows live on the Point the cache retains."""
    from .pack import precompute_limbs

    precompute_limbs(pk.point)


class VerifyFuture:
    """Handle to an in-flight device verification (JAX dispatch is async:
    the kernel call returns before the device finishes; materializing the
    bool synchronizes). Lets callers pipeline batches — stage and submit
    batch i+1 while batch i executes — the double-buffered submission queue
    of SURVEY.md §7 Phase 1 hard part 3."""

    def __init__(self, device_result):
        self._result = device_result

    def wait(self) -> None:
        """Block until the device has computed the verdict, without copying
        it to the host."""
        jax.block_until_ready(self._result)

    def result(self) -> bool:
        return bool(self._result)


_INVALID = VerifyFuture(False)


def _structurally_valid(sets: list[SignatureSet]) -> bool:
    if not sets:
        return False
    for s in sets:
        if not s.signing_keys:
            return False
        if any(pk.point.inf for pk in s.signing_keys):
            return False
    return True


def verify_signature_sets_async(
    sets: list[SignatureSet], rng=None, s_floor: int = 4
) -> VerifyFuture:
    """Submit a batch without waiting for the verdict (see VerifyFuture).
    `s_floor` stages it in a bucket of at least that many sets (the
    coalescer's bisection keeps the failed batch's compiled bucket)."""
    from ....common.tracing import span

    if not _structurally_valid(sets):
        return _INVALID
    staged, chain = _stage(sets, rng, s_floor, _dispatch_chain)
    kernel = _verify_kernel(chain)
    # the host side of a dispatch's fixed cost: one buffer packed, copied
    # to the device, and the chain's programs launched
    with span("bls_dispatch"):
        return VerifyFuture(kernel(jnp.asarray(_pack_staged(staged.values()))))


def verify_signature_sets(sets: list[SignatureSet], rng=None) -> bool:
    """Batch verification by random linear combination, device-executed.

    Mirrors impls/blst.rs:36-119: nonzero 64-bit scalars, n+1 Miller loops,
    one final exponentiation. Returns False (never raises) for structurally
    invalid batches, like the reference."""
    from ....common.metrics import BLS_BATCH_SECONDS, BLS_SETS_TOTAL
    from ....common.tracing import span

    if not _structurally_valid(sets):
        return False  # structurally invalid: no device work, no metrics
    from ..batch_verifier import mark_device_busy

    # the timer spans staging + dispatch + fetch (the full batch cost, as
    # the dashboards expect); staging's bls_pack/bls_h2c_host spans nest
    # under this root, the remainder is device execute + fetch.
    # mark_device_busy tells the coalescer's device-idle flush heuristic
    # that a dedicated batch (e.g. a block import) occupies the device, so
    # concurrent single-set submissions accumulate instead of dispatching
    # alone at the padding floor.
    with mark_device_busy(), BLS_BATCH_SECONDS.time(), span("bls_batch_verify"):
        fut = verify_signature_sets_async(sets, rng=rng)
        with span("bls_device_execute"):
            fut.wait()
            # the verdict's copy to the host, timed apart from the wait for
            # the chain on the device
            with span("bls_fetch"):
                ok = fut.result()
    BLS_SETS_TOTAL.inc(len(sets))
    return ok


# -- pubkey validation (cache-admission path) ----------------------------------


@lru_cache(maxsize=8)
def _pk_validate_kernel(S: int):
    from ....common.metrics import BLS_JIT_BUILDS_TOTAL
    from .curve import FP, from_affine, g1_in_subgroup

    BLS_JIT_BUILDS_TOTAL.labels(kernel="pk_validate").inc()

    def kernel(x, y, inf):
        return g1_in_subgroup(from_affine(FP, x, y, inf)) & ~inf

    return jax.jit(kernel)


def batch_validate_public_keys(keys: list[bytes]) -> list[bool]:
    """Decompress + full subgroup-check a batch of compressed G1 pubkeys on
    device — the ValidatorPubkeyCache admission path
    (/root/reference/beacon_node/beacon_chain/src/validator_pubkey_cache.rs).
    Returns one bool per key; structurally invalid encodings are False."""
    from .pack import pack_g1_batch

    pts = []
    ok_mask = []
    for kb in keys:
        try:
            pts.append(_ref.g1_from_compressed(kb, subgroup_check=False))
            ok_mask.append(True)
        except DecodeError:
            pts.append(g1_infinity())
            ok_mask.append(False)
    S = _next_pow2(len(pts))
    pts += [g1_infinity()] * (S - len(pts))
    x, y, inf = pack_g1_batch(pts)
    res = np.asarray(_pk_validate_kernel(S)(jnp.asarray(x), jnp.asarray(y), jnp.asarray(inf)))
    return [bool(r) and m for r, m in zip(res[: len(keys)], ok_mask)]


# -- interop keypairs ----------------------------------------------------------


def interop_secret_key(validator_index: int) -> SecretKey:
    return SecretKey(_ref.interop_secret_key(validator_index).k)


@lru_cache(maxsize=None)
def interop_keypair(validator_index: int) -> tuple[SecretKey, PublicKey]:
    """Cached: the pure-Python pubkey derivation costs ~0.1 s a key, and a
    node's interop genesis and its harness both derive every key."""
    sk = interop_secret_key(validator_index)
    return sk, sk.public_key()


# -- analyzer registry hooks ---------------------------------------------------
#
# The full per-shard pipeline at representative (S, K) bucket shapes: the
# top of the funnel every registered stage kernel feeds. ~150 s to TRACE
# each on this box, so slow-tier only (`scripts/lint.py --jaxpr
# --all-tiers` / the nightly @slow gate; the fast tier already covers
# every stage individually). The seeds mirror stage_sets' staging
# contract: canonical Montgomery limbs, 0/1 infinity masks, 0/1 scalar-bit
# rows.

from . import registry as _reg


def _verify_pipeline_spec(S: int, K: int):
    from .fp import N_LIMBS

    args = (
        np.zeros((S, K, N_LIMBS), np.int32),  # pk_x
        np.zeros((S, K, N_LIMBS), np.int32),  # pk_y
        np.zeros((S, K), bool),  # pk_inf
        np.zeros((S, 2, N_LIMBS), np.int32),  # sig_x
        np.zeros((S, 2, N_LIMBS), np.int32),  # sig_y
        np.zeros(S, bool),  # sig_inf
        np.zeros((S, 2, 2, N_LIMBS), np.int32),  # u
        np.zeros((S, 64), np.int32),  # r_bits
    )
    ranges = [
        _reg.LIMB, _reg.LIMB, _reg.BOOL,
        _reg.LIMB, _reg.LIMB, _reg.BOOL,
        _reg.LIMB, _reg.BIT,
    ]
    return verify_pipeline_local, args, ranges


@_reg.register("api.group_sums@S128U64")
def _spec_group_sums():
    """The grouped chain's `group` stage at the gossip bucket's widths: S
    keys-stage points, each with its message group in [0, U)."""
    from .curve import FP, from_affine
    from .fp import N_LIMBS

    S, U = 128, 64

    def fn(x, y, inf, group):
        return _group_sums(from_affine(FP, x, y, inf), group, U)

    x = np.zeros((S, N_LIMBS), np.int32)
    args = (x, x.copy(), np.zeros(S, bool), np.zeros(S, np.int32))
    return fn, args, [_reg.LIMB, _reg.LIMB, _reg.BOOL, (0, U - 1)]


@_reg.register("api.verify_pipeline_local@S4K4", tier="slow")
def _spec_verify_s4k4():
    return _verify_pipeline_spec(4, 4)


@_reg.register("api.verify_pipeline_local@S8K2", tier="slow")
def _spec_verify_s8k2():
    return _verify_pipeline_spec(8, 2)
