"""The chunked key layout (api.key_chunks): where a batch's K bucket exceeds
KEY_CHUNK, the dispatch stages each set's keys in chunks of 32 slots of its
own, and the keys stage folds each chunk and sums each set's chunks with a
segmented scan, in place of folding S x K slots.

The keys stage is compiled at one bucket, (8, 128), two ways: dense, and in
the bucket's 16 chunks. Its r_i * aggpk_i must be the dense program's as
affine points, and the host reference's. The whole chain on the CPU
compiles for minutes: its verdicts, and a batch over the bucket's chunk
budget through it, are the slow tier's.
"""

from __future__ import annotations

import random
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest

from lighthouse_tpu.crypto.bls.constants import DST, P
from lighthouse_tpu.crypto.bls.jax_backend import api, pack
from lighthouse_tpu.crypto.bls.ref import api as ref
from lighthouse_tpu.crypto.bls.ref.curves import _B1, Point, g1_generator, g1_infinity
from lighthouse_tpu.crypto.bls.ref.fields import Fp
from lighthouse_tpu.crypto.bls.ref.hash_to_curve import hash_to_g2
from lighthouse_tpu.crypto.bls.ref.pairing import multi_pairing

S, K = 8, 128
N = api.key_chunks(S, K)  # 16
SEED = 2147483659


@pytest.fixture(scope="module")
def keys():
    """128 public keys, key i the point (i + 1) g, so its secret key is i + 1."""
    g, acc, out = g1_generator(), g1_infinity(), []
    for _ in range(K):
        acc = acc + g
        out.append(api.PublicKey(acc))
    return out


@pytest.fixture(scope="module")
def keys_stage():
    """The keys stage at (8, 128): dense, and chunked in the bucket's 16
    chunks, compiled concurrently."""
    stage_keys = [("keys", S, K, None), ("keys", S, K, N)]
    lowered = [jax.jit(fn).lower(*specs) for fn, specs in map(api.verify_stage, stage_keys)]
    with ThreadPoolExecutor(len(lowered)) as pool:
        compiled = list(pool.map(lambda low: low.compile(), lowered))
    return dict(zip([None, N], compiled))


def _sets(keys, lists):
    """One set per key list, each with a message of its own (no signature:
    neither staging nor the keys stage reads it)."""
    sig = api.Signature.infinity()
    return [api.SignatureSet(signature=sig, signing_keys=ks, message=bytes([i]) * 32)
            for i, ks in enumerate(lists)]


def _scalars(n):
    """The scalars staging draws from random.Random(SEED) for n sets."""
    rng, out = random.Random(SEED).getrandbits, []
    for _ in range(n):
        r = 0
        while r == 0:
            r = rng(64)
        out.append(r)
    return out


def _affine(r_pk):
    """The device's projective points (x = X/Z, y = Y/Z) as host reference
    points."""
    out = []
    for x, y, z in zip(*(np.asarray(c) for c in r_pk)):
        z = pack.unpack_fp(z)
        if z == 0:
            out.append(g1_infinity())
            continue
        zi = pow(z, P - 2, P)
        x, y = (Fp(pack.unpack_fp(c) * zi % P) for c in (x, y))
        out.append(Point(x, y, False, _B1))
    return out


def _run(keys_stage, staged, n_chunks):
    fn = keys_stage[n_chunks]
    if n_chunks is None:
        r_pk, agg_inf = fn(*staged[:3], staged[7])
    else:
        r_pk, agg_inf = fn(*staged[:3], staged[7], staged[8])
    return _affine(r_pk), bool(agg_inf)


def _both(keys_stage, sets):
    """(dense, chunked) keys-stage results of one batch of the (8, 128)
    bucket, each (r_pk as affine points, agg_inf), and the chunked staging."""
    rng = lambda: random.Random(SEED).getrandbits  # noqa: E731
    dense = api.stage_sets(sets, rng=rng(), s_floor=S)
    chunked, _ = api.stage_sets_grouped(sets, rng=rng(), s_floor=S)
    assert dense[2].shape == (S, K) and len(chunked[8]) == N  # within the bucket's budget
    return _run(keys_stage, dense, None), _run(keys_stage, chunked, N), chunked


def _pm(keys, i):
    """Key i and its negation: a pair that sums to infinity."""
    return [keys[i], api.PublicKey(-keys[i].point)]


# Each batch has a set of more than 64 keys: the (8, 128) bucket.
CASES = {
    # key counts a set: every chunk boundary, padding rows after them
    "1-31-32-33-128": lambda ks: [ks[:1], ks[:31], ks[:32], ks[:33], ks[:128]],
    "64-128-1": lambda ks: [ks[:64], ks[:128], ks[5:6]],
    # a full batch: the last set's chunks sit just before the unused ones
    "full-8-rows": lambda ks: [ks[:1], ks[:31], ks[:32], ks[:33], ks[:64], ks[:2], ks[:3],
                               ks[:65]],
    "128-last": lambda ks: [ks[:1], ks[1:2], ks[:128]],
    # P + (-P): an aggregate at infinity, which fails the batch
    "sums-to-infinity": lambda ks: [ks[:40], _pm(ks, 3), ks[:2], ks[:70]],
    # P + (-P) across a chunk boundary, and a key repeated (P + P)
    "infinity-across-chunks": lambda ks: [ks[:31] + _pm(ks, 40), ks[:1], ks[:1] * 5,
                                          ks[:100]],
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_chunked_keys_stage_is_the_dense_one_and_the_references(keys, keys_stage, case):
    lists = CASES[case](keys)
    sets = _sets(keys, lists)
    dense, chunked, _ = _both(keys_stage, sets)
    assert dense == chunked
    r_pk, agg_inf = chunked
    rs = _scalars(len(lists))
    want = [ref.aggregate_public_keys(ks).point for ks in lists]
    assert agg_inf == any(p.inf for p in want)
    for i in range(S):  # padding rows: r = 0, so r * g is infinity
        assert r_pk[i] == (want[i].mul(rs[i]) if i < len(lists) else g1_infinity()), i


def test_an_over_budget_batch_takes_the_next_power_of_two_of_chunks(keys):
    """Five sets of up to 128 keys take 18 chunks and the 3 padding rows 3
    more: 21, over the bucket's 16, so the batch takes 32 chunks, the
    unused ones set S."""
    lists = [keys[:128], keys[:128], keys[:100], keys[:97], keys[:33]]
    staged, _ = api.stage_sets_grouped(_sets(keys, lists), s_floor=S)
    assert staged[0].shape == (2 * N, api.KEY_CHUNK, 32) and staged[2].shape == (2 * N, 32)
    assert staged[8].tolist() == [0] * 4 + [1] * 4 + [2] * 4 + [3] * 4 + [4] * 2 + [5, 6, 7] \
        + [S] * 11
    assert staged[2][21:].all() and staged[2][17, 1:].all() and not staged[2][17, 0]


def _signed(keys, lists):
    """One set per key list, each over a message of its own and signed by
    its keys' secret keys (key i's is i + 1)."""
    sets = []
    for i, ks in enumerate(lists):
        msg = bytes([0x40 + i]) * 32
        sk = sum(keys.index(pk) + 1 for pk in ks)
        sets.append(api.SignatureSet(signature=ref.SecretKey(sk).sign(msg), signing_keys=ks,
                                     message=msg))
    return sets


def _signed_block(keys):
    """A block-shaped batch at (8, 128), filling its 16 chunks: proposer and
    randao (1 key), four aggregates (33, 40, 64 and 96 keys) and a sync
    aggregate (128)."""
    return _signed(keys, [keys[7:8], keys[9:10], keys[:33], keys[20:60], keys[64:128],
                          keys[:96], keys])


def _swap_signatures(sets, a, b):
    out = list(sets)
    out[a] = api.SignatureSet(sets[b].signature, sets[a].signing_keys, sets[a].message)
    out[b] = api.SignatureSet(sets[a].signature, sets[b].signing_keys, sets[b].message)
    return out


@pytest.mark.parametrize("swap", [False, True], ids=["valid", "two-aggregates-swapped"])
def test_a_block_verifies_as_the_reference_with_the_chunked_keys(keys, keys_stage, swap):
    """The chunked keys stage's r_i * aggpk_i in the reference's pairing
    check (n + 1 Miller loops, one final exponentiation) give the plain
    reference's verdict on a block, and a block with two aggregates'
    signatures swapped."""
    sets = _signed_block(keys)
    if swap:
        sets = _swap_signatures(sets, 2, 5)
    _, (r_pk, agg_inf), _ = _both(keys_stage, sets)
    rs = _scalars(len(sets))
    sig_acc = sum((s.signature.point.mul(r) for s, r in zip(sets, rs)), api.g2_infinity())
    pairs = [(p, hash_to_g2(s.message, DST)) for p, s in zip(r_pk, sets)]
    pairs.append((-g1_generator(), sig_acc))
    verdict = multi_pairing(pairs).is_one() and not agg_inf
    assert verdict is (not swap)
    assert ref.verify_signature_sets(sets) is verdict


# -- the layout counter and the slot counters -------------------------------------


def _counted(sets, **kw):
    from lighthouse_tpu.common.metrics import BLS_KEYS_LAYOUT_TOTAL, BLS_STAGED_KEY_SLOTS_TOTAL

    layouts = ("dense", "chunked", "chunked_over_budget")

    def read():
        return ({lay: BLS_KEYS_LAYOUT_TOTAL.labels(layout=lay).value for lay in layouts},
                {s: BLS_STAGED_KEY_SLOTS_TOTAL.labels(slot=s).value for s in ("key", "pad")})

    before = read()
    api.stage_sets_grouped(sets, **kw)
    after = read()
    return tuple({k: a[k] - b[k] for k in a if a[k] != b[k]} for b, a in zip(before, after))


@pytest.mark.parametrize(
    "lists,want",
    [
        ([1, 4, 2], ({"dense": 1}, {"key": 7, "pad": 4 * 4 - 7})),
        ([1, 33, 128], ({"chunked": 1}, {"key": 162, "pad": 8 * 32 - 162})),
        ([128, 128, 128, 128, 1], ({"chunked_over_budget": 1},
                                   {"key": 513, "pad": 32 * 32 - 513})),
    ],
    ids=["dense", "chunked", "chunked_over_budget"],
)
def test_each_staged_batch_counts_its_layout_and_the_slots_staged(keys, lists, want):
    """One layout increment a batch; the slots counted are those staged:
    S x K dense, N x 32 chunked."""
    assert _counted(_sets(keys, [keys[:k] for k in lists]), rng=api._ONE_RNG) == want


def test_an_over_budget_batch_dispatches_a_chain_of_its_own(keys, monkeypatch):
    """The dispatch names the chunk count only where a batch passes the
    bucket's, and sends a buffer of the layout that chain unpacks."""
    got = []
    monkeypatch.setattr(api, "_verify_kernel", lambda c: got.append(c) or (lambda flat: flat))
    for lists in ([1, 33, 128], [128, 128, 128, 128, 1]):
        fut = api.verify_signature_sets_async(_sets(keys, [keys[:k] for k in lists]))
        n = sum(int(np.prod(shape)) for _, shape, _ in got[-1].staged_fields())
        assert np.asarray(fut._result).shape == (n,)
    assert got == [api.Chain(4, 128, None, api.key_chunks(4, 128)), api.Chain(8, 128, None, 2 * N)]


class _Lowered:
    def compile(self):
        return lambda *args: True


def test_an_over_budget_chain_builds_only_its_unpack_and_keys(monkeypatch):
    """Built on first use, it shares every other program with the
    bucket's chain."""
    lowered = []
    monkeypatch.setattr(api, "_lower_stage", lambda key: lowered.append(key) or _Lowered())
    monkeypatch.setattr(api, "_kernels", {})
    monkeypatch.setattr(api, "_chains", {})
    api.compile_verify_kernels([(S, K)])
    built = len(lowered)
    assert ("keys", S, K, N) in lowered and ("unpack", S, K, None, N) in lowered
    api._verify_kernel(api.Chain(S, K, None, 2 * N))
    assert lowered[built:] == [("unpack", S, K, None, 2 * N), ("keys", S, K, 2 * N)]


# -- the whole chain (slow tier: minutes of CPU compile) ---------------------------


@pytest.mark.slow
@pytest.mark.parametrize(
    "lists,swap,layout",
    [
        # (4, 128) in its 8 chunks: proposer, randao, two aggregates
        (lambda ks: [ks[7:8], ks[9:10], ks[:40], ks[20:120]], False, "chunked"),
        (lambda ks: [ks[7:8], ks[9:10], ks[:40], ks[20:120]], True, "chunked"),
        # 10 chunks: over the budget, into 16
        (lambda ks: [ks[:128], ks[:120], ks[3:4], ks[5:6]], False, "chunked_over_budget"),
    ],
    ids=["block-valid", "block-two-aggregates-swapped", "over-budget-valid"],
)
def test_the_chunked_chain_verifies_as_the_reference(keys, lists, swap, layout):
    from lighthouse_tpu.common.metrics import BLS_KEYS_LAYOUT_TOTAL

    sets = _signed(keys, lists(keys))
    if swap:
        sets = _swap_signatures(sets, 2, 3)
    before = BLS_KEYS_LAYOUT_TOTAL.labels(layout=layout).value
    assert api.verify_signature_sets(sets) is (not swap)
    assert BLS_KEYS_LAYOUT_TOTAL.labels(layout=layout).value == before + 1
    assert ref.verify_signature_sets(sets) is (not swap)
