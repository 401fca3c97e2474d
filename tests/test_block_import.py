"""A block's signature sets through the jax backend's verify_signature_sets,
the call BlockSignatureVerifier.verify makes for a block, against the plain
reference (crypto/bls/ref) on the CPU.

The block is Altair-shaped at the smallest bucket that holds its shapes:
the proposer and randao sets (one key each), three attestation aggregates
over distinct messages, and a sync aggregate whose eight keys set K for
every set, in the (8, 8) bucket with two padding rows. Its six distinct
messages are more than S/2, so it takes the full chain, as a real block's
131 do. One compile of the bucket's programs (about 4 min on a CPU host)
serves every case.
"""

from __future__ import annotations

import pytest

from lighthouse_tpu.crypto.bls.jax_backend import api
from lighthouse_tpu.crypto.bls.ref import api as ref

# (secret keys, message) per set: proposer, randao, three aggregates, sync
BLOCK = [
    ([1], b"proposal"),
    ([2], b"randao"),
    ([3, 4, 5], b"attestation-0"),
    ([6, 7], b"attestation-1"),
    ([8, 9, 10, 11], b"attestation-2"),
    (list(range(12, 20)), b"sync-committee"),
]
SWAPPED = (2, 4)  # two aggregates that swap signatures


def _sets(bls, swap: bool):
    """The block's sets in `bls`'s types: each aggregate signature is the
    sum of its members' keys times H(m), as aggregating their signatures
    gives."""
    sigs = [bls.SecretKey(sum(ks)).sign(m) for ks, m in BLOCK]
    if swap:
        a, b = SWAPPED
        sigs[a], sigs[b] = sigs[b], sigs[a]
    return [bls.SignatureSet(signature=sig, signing_keys=[bls.SecretKey(k).public_key() for k in ks],
                             message=m) for (ks, m), sig in zip(BLOCK, sigs)]


@pytest.fixture(scope="module")
def valid_verdict():
    """The valid block through the jax backend, its programs built on the
    way."""
    return api.verify_signature_sets(_sets(api, swap=False))


def test_the_block_fills_the_smallest_bucket_and_takes_the_full_chain():
    staged, group = api.stage_sets_grouped(_sets(api, swap=False))
    assert api.batch_bucket(len(BLOCK), 8) == (8, 8) == staged[0].shape[:2]
    assert group is None and len(staged[6]) == 8


@pytest.mark.parametrize("swap", [False, True], ids=["valid", "two-aggregates-swapped"])
def test_block_verdict_matches_the_reference(valid_verdict, swap):
    want = ref.verify_signature_sets(_sets(ref, swap))
    assert want is (not swap)
    got = valid_verdict if not swap else api.verify_signature_sets(_sets(api, swap))
    assert got is want
    per_set = [ref.verify_signature_set(s) for s in _sets(ref, swap)]
    assert [i for i, ok in enumerate(per_set) if not ok] == (sorted(SWAPPED) if swap else [])
