"""Grouping a batch's sets by message (jax_backend/api.py, _GROUPED_CHAIN).

A batch whose sets carry at most S/2 distinct messages is staged with S/2
message rows and a group index per set; the device sums r_i * aggpk_i per
group (the `group` stage) and pairs S/2 rows, hashed by the S-row hash-to-G2
program over the message rows tiled. By bilinearity the pairing product is
unchanged, with each set's own scalar r_i.

The host plan and the dispatch's choice of chain are numpy work and run in
the fast tier, with one small compile of the `group` stage; the full
chains at the gossip bucket, with verdicts against the oracle, are slow.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from lighthouse_tpu.crypto.bls.constants import P
from lighthouse_tpu.crypto.bls.jax_backend import api, h2c, pack
from lighthouse_tpu.crypto.bls.ref.curves import g1_generator, g1_infinity

N_KEYS = 8


@pytest.fixture(scope="module")
def keys():
    return [api.interop_keypair(i)[1] for i in range(N_KEYS)]


def _msgs(n_sets: int, n_distinct: int) -> list[bytes]:
    """Messages of n_sets sets, interleaved over n_distinct values: the
    sets of one message are not neighbours."""
    return [bytes([i % n_distinct]) * 32 for i in range(n_sets)]


def _sets(keys, messages):
    """One one-key set per message (any signature: staging does not
    verify)."""
    sig = api.Signature(api.g2_infinity())
    return [api.SignatureSet(signature=sig, signing_keys=[keys[i % len(keys)]], message=m)
            for i, m in enumerate(messages)]


# -- the host plan ---------------------------------------------------------------


@pytest.mark.parametrize(
    "n_sets,distinct,U",
    [(128, 64, 64), (128, 65, 128), (8, 4, 4), (8, 5, 8), (4, 1, 4)],
    ids=["S128-64msgs", "S128-65msgs", "S8-4msgs", "S8-5msgs", "S4-below-min"],
)
def test_a_batch_groups_up_to_half_its_rows_in_messages(keys, n_sets, distinct, U):
    """U = S/2 where the batch has at most S/2 distinct messages and S >=
    GROUP_MIN_S; else U = S and the staging is stage_sets' own."""
    sets = _sets(keys, _msgs(n_sets, distinct))
    staged, group = api.stage_sets_grouped(sets)
    S = staged[2].shape[0]
    assert staged[6].shape == (U, 2, 2, 32)
    if U == S:
        assert group is None
        full = api.stage_sets(sets, rng=api._ONE_RNG)
        again, _ = api.stage_sets_grouped(sets, rng=api._ONE_RNG)
        assert all(np.array_equal(a, b) for a, b in zip(full, again))
    else:
        assert group.shape == (S,) and group.dtype == np.int32
        assert sorted(set(group.tolist())) == list(range(distinct))


def test_grouped_rows_are_the_per_set_rows_reordered(keys):
    """Every array but u is stage_sets' own in the plan's order, so each set
    keeps its keys, signature and scalar r_i; groups are contiguous and
    numbered in row order; each set's message row is its own message's u."""
    msgs = [bytes([(7 * i) % 5]) * 32 for i in range(13)]  # S = 16, U = 8
    sets = _sets(keys, msgs)
    full = api.stage_sets(sets, rng=random.Random(3).getrandbits)
    grouped, group = api.stage_sets_grouped(sets, rng=random.Random(3).getrandbits)
    order, plan_group, rows = api._message_plan(msgs, 16)
    assert np.array_equal(group, plan_group)
    assert sorted(order.tolist()) == list(range(16))
    for k in (0, 1, 2, 3, 4, 5, 7):  # pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, r_bits
        assert np.array_equal(grouped[k], full[k][order]), k
    assert np.all(np.diff(group) >= 0) and group[0] == 0
    assert [msgs[i] for i in order[:13]] == [rows[g] for g in group[:13]]
    u = grouped[6]
    for j in range(13):
        assert np.array_equal(u[group[j]], full[6][order[j]])
    assert len(set(rows[:5])) == 5 and rows[5:] == [msgs[0]] * 3
    assert all(np.array_equal(u[k], u[0]) for k in range(5, 8))


def test_padding_sets_join_the_last_group_and_take_no_message_row(keys):
    msgs = _msgs(5, 2)  # S = 8, U = 4
    order, group, rows = api._message_plan(msgs, 8)
    assert order.tolist() == [0, 2, 4, 1, 3, 5, 6, 7]
    assert group.tolist() == [0, 0, 0, 1, 1, 1, 1, 1]
    assert rows == [msgs[0], msgs[1], msgs[0], msgs[0]]
    staged, _ = api.stage_sets_grouped(_sets(keys, msgs))
    empty = h2c.hash_to_field_limbs([b""])[0]
    assert not any(np.array_equal(row, empty) for row in staged[6])
    assert (staged[7][5:] == 0).all()  # padding rows keep r = 0: the identity


@pytest.mark.parametrize(
    "n_sets,distinct,want",
    [(128, 55, {"distinct": 55, "pad": 9}), (128, 74, {"distinct": 74, "pad": 54}),
     (3, 2, {"distinct": 2, "pad": 2})],
    ids=["grouped", "full-width", "below-min"],
)
def test_staging_counts_message_rows(keys, n_sets, distinct, want):
    from lighthouse_tpu.common.metrics import BLS_MESSAGE_ROWS_TOTAL

    before = {r: BLS_MESSAGE_ROWS_TOTAL.labels(row=r).value for r in want}
    api.stage_sets_grouped(_sets(keys, _msgs(n_sets, distinct)))
    assert {r: BLS_MESSAGE_ROWS_TOTAL.labels(row=r).value - before[r] for r in want} == want


@pytest.mark.parametrize("distinct,chain", [(64, api.Chain(128, 4, 64)), (65, api.Chain(128, 4))])
def test_the_dispatch_takes_the_chain_the_plan_chose(keys, monkeypatch, distinct, chain):
    """The grouped chain gets the U-row layout with the group index last."""
    got = []
    monkeypatch.setattr(api, "_verify_kernel", lambda c: got.append(c) or (lambda flat: flat))
    fut = api.verify_signature_sets_async(_sets(keys, _msgs(128, distinct)))
    assert got == [chain]
    assert chain.staged_fields()[-1][0] == ("group" if chain.U else "r_bits")
    n = sum(int(np.prod(shape)) for _, shape, _ in chain.staged_fields())
    assert np.asarray(fut._result).shape == (n,)


# -- the group stage ---------------------------------------------------------------


def test_group_stage_sums_each_group_and_leaves_empty_groups_at_infinity():
    """...and passes on the first U of hash-to-G2's S rows, the message
    rows' points."""
    import jax
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.bls.jax_backend.curve import FP, Proj, from_affine

    S, U = 8, 4
    rng = random.Random(11)
    g = g1_generator()
    pts = [g.mul(rng.randrange(1, 1 << 64)) for _ in range(S)]
    pts[6] = g1_infinity()  # a padding set's r * pk
    pts[1] = pts[0]  # P + P inside a group
    group = np.array([0, 0, 0, 1, 1, 2, 2, 2], np.int32)  # group 3 has no set
    x, y, inf = pack.pack_g1_batch(pts)
    H = Proj(*(np.arange(S * 2 * 32, dtype=np.int32).reshape(S, 2, 32) + c for c in (0, 1, 2)))
    fn, _ = api.verify_stage(("group", S, U))
    out, HU = jax.jit(fn)(from_affine(FP, jnp.asarray(x), jnp.asarray(y), jnp.asarray(inf)),
                          jnp.asarray(group), Proj(*map(jnp.asarray, H)))
    assert all(np.array_equal(np.asarray(a), b[:U]) for a, b in zip(HU, H))
    ox, oy, oz = (np.asarray(c) for c in out)
    for u in range(U):
        want = g1_infinity()
        for p, gi in zip(pts, group):
            if gi == u:
                want = want + p
        z = pack.unpack_fp(oz[u])
        if want.inf:
            assert z == 0, u
        else:
            assert z != 0, u
            assert pack.unpack_fp(ox[u]) == want.x.n * z % P, u
            assert pack.unpack_fp(oy[u]) == want.y.n * z % P, u


# -- the full chains, against the oracle (slow) ------------------------------------


@pytest.fixture(scope="module")
def gossip():
    """128 one-key sets over 64 messages, each signed by its own key,
    interleaved: sets i and i + 64 share a message."""
    from lighthouse_tpu.crypto.bls.constants import DST
    from lighthouse_tpu.crypto.bls.ref.hash_to_curve import hash_to_g2

    rng = random.Random(24)
    msgs = [rng.randbytes(32) for _ in range(65)]
    points = [hash_to_g2(m, DST) for m in msgs]
    pairs = [api.interop_keypair(i) for i in range(128)]

    def batch(n_msgs):
        return [api.SignatureSet(signature=api.Signature(points[i % n_msgs].mul(sk.k)),
                                 signing_keys=[pk], message=msgs[i % n_msgs])
                for i, (sk, pk) in enumerate(pairs)]

    return batch


def _ref_verdict(sets) -> bool:
    from lighthouse_tpu.crypto import bls

    r = bls.backend("ref")
    return r.verify_signature_sets([
        r.SignatureSet(signature=r.Signature(s.signature.point),
                       signing_keys=[r.PublicKey(pk.point) for pk in s.signing_keys],
                       message=s.message)
        for s in sets
    ])


def _swap(sets, a, b):
    out = list(sets)
    out[a] = api.SignatureSet(sets[b].signature, sets[a].signing_keys, sets[a].message)
    out[b] = api.SignatureSet(sets[a].signature, sets[b].signing_keys, sets[b].message)
    return out


@pytest.mark.slow
@pytest.mark.parametrize("n_msgs,chain", [(64, api.Chain(128, 4, 64)), (65, api.Chain(128, 4))],
                         ids=["grouped", "full-width"])
def test_gossip_batch_verifies_true_with_ref_parity(gossip, n_msgs, chain):
    sets = gossip(n_msgs)
    assert api.verify_signature_sets(sets) is True
    assert chain in api._chains
    assert _ref_verdict(sets) is True


@pytest.mark.slow
def test_same_message_swap_fails_and_bisection_blames_the_pair(gossip):
    """Two sets over one message swap signatures: their sum is unchanged,
    so only independent per-set scalars catch it, grouped or not."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls.batch_verifier import BatchVerifier

    bad = _swap(gossip(64), 5, 69)
    assert bad[5].message == bad[69].message
    assert api.verify_signature_sets(bad) is False
    assert _ref_verdict(bad) is False
    verdicts = BatchVerifier(bls.backend("jax"))._bisect(bad, s_floor=128)
    assert [i for i, v in enumerate(verdicts) if not v] == [5, 69]
