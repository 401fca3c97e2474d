"""Host staging fast path (crypto/bls/jax_backend stage_sets + caches).

The fast path's contract is BYTE-IDENTITY: packed-limb caching, hash-to-
curve dedup/LRU and the vectorized bulk conversions must produce exactly
the buffer the per-element slow path produced, cold caches or warm. These
tests pin that contract (arrays compared with dtype + exact equality),
prove the cache-hit/miss metrics move as designed, and prove stale limb
rows cannot be served after a validator's pubkey bytes change.

Everything here is host-side numpy work (no kernels compile), so the
module runs in the fast tier; the device-verify parity check for a
duplicated-message batch carries @pytest.mark.slow.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from lighthouse_tpu.crypto import bls
from lighthouse_tpu.crypto.bls.constants import DST, G1_GENERATOR_X, P
from lighthouse_tpu.crypto.bls.jax_backend import api as japi, fp, h2c, pack
from lighthouse_tpu.crypto.bls.ref import hash_to_curve as ref_h2c
from lighthouse_tpu.crypto.bls.ref.curves import g1_infinity


def _chill(sets) -> None:
    """Drop every staging cache a batch could hit: the h2c LRU and the
    per-point limb rows of all referenced points."""
    japi.drop_staging_caches(sets)


@pytest.fixture(scope="module")
def jax_bls():
    return bls.backend("jax")


@pytest.fixture(scope="module")
def sets(jax_bls):
    """11 sets: 8 single-key with 3 distinct messages (heavy message
    duplication), one 3-key aggregate (K padding), S padded 11 -> 16."""
    b = jax_bls
    pairs = [b.interop_keypair(i) for i in range(8)]
    out = []
    for i in range(8):
        sk, pk = pairs[i]
        msg = bytes([i % 3]) * 32
        out.append(b.SignatureSet(signature=sk.sign(msg), signing_keys=[pk], message=msg))
    msg = b"\x07" * 32
    agg = b.aggregate_signatures([sk.sign(msg) for sk, _ in pairs[:3]])
    out.append(
        b.SignatureSet(
            signature=agg, signing_keys=[pk for _, pk in pairs[:3]], message=msg
        )
    )
    sk, pk = pairs[5]
    out.append(b.SignatureSet(signature=sk.sign(msg), signing_keys=[pk], message=msg))
    sk, pk = pairs[6]
    out.append(b.SignatureSet(signature=sk.sign(msg), signing_keys=[pk], message=msg))
    return out


# -- bulk conversion primitives == per-element slow path -----------------------


def test_ints_to_limbs_matches_per_int():
    rng = random.Random(0xBEEF)
    xs = [0, 1, P - 1, (1 << 384) - 1] + [rng.randrange(1 << 384) for _ in range(20)]
    bulk = fp.ints_to_limbs(xs)
    ref = np.stack([fp.int_to_limbs(x) for x in xs])
    assert bulk.dtype == ref.dtype == np.int32
    assert np.array_equal(bulk, ref)
    assert fp.ints_to_limbs([]).shape == (0, fp.N_LIMBS)
    with pytest.raises(ValueError):
        fp.ints_to_limbs([1 << 384])
    with pytest.raises(ValueError):
        fp.ints_to_limbs([-1])


def test_to_mont_host_bulk_matches_per_int():
    rng = random.Random(0xCAFE)
    xs = [0, 1, P - 1] + [rng.randrange(P) for _ in range(8)]
    bulk = fp.to_mont_host_bulk(xs)
    ref = np.stack([fp.to_mont_host(x) for x in xs])
    assert np.array_equal(bulk, ref)


def test_scalar_bits_batch_matches_per_scalar():
    rng = random.Random(0xD00D)
    rs = [0, 1, 2**64 - 1, 0x8000000000000001] + [rng.getrandbits(64) for _ in range(16)]
    bulk = japi._scalar_bits_batch(rs)
    ref = np.stack([japi._scalar_bits(r) for r in rs])
    assert bulk.dtype == ref.dtype == np.int32
    assert np.array_equal(bulk, ref)


def test_batched_nonzero_scalars_are_nonzero_64bit():
    rs = japi._batched_nonzero_scalars(256)
    assert rs.shape == (256,)
    assert (rs != 0).all()
    # and they round-trip through the bit expansion
    bits = japi._scalar_bits_batch(rs)
    assert bits.shape == (256, 64)
    assert np.array_equal(bits[:, 0], (rs >> np.uint64(63)).astype(np.int32))


# -- hash-to-curve dedup + LRU -------------------------------------------------


def _h2c_row_slow(msg: bytes, dst: bytes) -> np.ndarray:
    """The pre-dedup per-message computation, straight off the oracle."""
    u0, u1 = ref_h2c.hash_to_field_fp2(msg, dst, 2)
    row = np.empty((2, 2, fp.N_LIMBS), dtype=np.int32)
    row[0, 0] = fp.to_mont_host(u0.c0.n)
    row[0, 1] = fp.to_mont_host(u0.c1.n)
    row[1, 0] = fp.to_mont_host(u1.c0.n)
    row[1, 1] = fp.to_mont_host(u1.c1.n)
    return row


def test_hash_to_field_limbs_dedup_matches_slow_path():
    msgs = [b"a" * 32, b"b" * 32, b"a" * 32, b"", b"b" * 32, b"a" * 32]
    h2c.H2C_FIELD_CACHE.clear()
    fast = h2c.hash_to_field_limbs(msgs)
    slow = np.stack([_h2c_row_slow(m, DST) for m in msgs])
    assert fast.dtype == slow.dtype == np.int32
    assert np.array_equal(fast, slow)
    # second call is served entirely from the LRU — still identical
    again = h2c.hash_to_field_limbs(msgs)
    assert np.array_equal(again, slow)
    # distinct dst must not collide with the DST-keyed entries
    other = h2c.hash_to_field_limbs([b"a" * 32], dst=b"other-dst")
    assert not np.array_equal(other[0], slow[0])
    assert np.array_equal(other[0], _h2c_row_slow(b"a" * 32, b"other-dst"))


def test_h2c_lru_bounded():
    cache = h2c._H2CFieldCache(maxsize=4)
    for i in range(10):
        cache.put((bytes([i]), DST), np.zeros((2, 2, fp.N_LIMBS), np.int32))
    assert len(cache) == 4
    assert cache.get((bytes([0]), DST)) is None  # evicted, oldest first
    assert cache.get((bytes([9]), DST)) is not None


# -- stage_sets: fast path byte-identical, warm or cold ------------------------


def test_stage_sets_cached_vs_uncached_byte_identical(sets):
    _chill(sets)
    cold = japi.stage_sets(sets, rng=random.Random(42).getrandbits)
    warm = japi.stage_sets(sets, rng=random.Random(42).getrandbits)
    hot = japi.stage_sets(sets, rng=random.Random(42).getrandbits)
    names = ("pk_x", "pk_y", "pk_inf", "sig_x", "sig_y", "sig_inf", "u", "r_bits")
    for name, c, w, h in zip(names, cold, warm, hot):
        assert c.dtype == w.dtype == h.dtype, name
        assert np.array_equal(c, w), f"{name}: cold != warm"
        assert np.array_equal(w, h), f"{name}: warm != hot"
    # padding rows: sets 11..15 are (generator, r=0, empty-message) no-ops
    pk_x, _, pk_inf, _, _, sig_inf, u, r_bits = cold
    gen_x = pack.pack_fp(G1_GENERATOR_X)
    for i in range(len(sets), 16):
        assert np.array_equal(pk_x[i, 0], gen_x)
        assert not pk_inf[i, 0] and pk_inf[i, 1:].all()
        assert sig_inf[i]
        assert (r_bits[i] == 0).all()
        assert np.array_equal(u[i], _h2c_row_slow(b"", DST))


def test_stage_sets_metrics_move_cold_to_warm(sets):
    from lighthouse_tpu.common.metrics import (
        BLS_STAGE_SECONDS,
        BLS_STAGING_CACHE_HITS_TOTAL,
        BLS_STAGING_CACHE_MISSES_TOTAL,
    )

    caches = ("pk_limbs", "sig_limbs", "h2c")

    def snap():
        return {
            c: (
                BLS_STAGING_CACHE_HITS_TOTAL.labels(cache=c).value,
                BLS_STAGING_CACHE_MISSES_TOTAL.labels(cache=c).value,
            )
            for c in caches
        }

    _chill(sets)
    n_stage = BLS_STAGE_SECONDS.count
    before = snap()
    japi.stage_sets(sets, rng=japi._ONE_RNG)
    after_cold = snap()
    japi.stage_sets(sets, rng=japi._ONE_RNG)
    after_warm = snap()

    for c in caches:
        assert after_cold[c][1] > before[c][1], f"{c}: cold run must record misses"
    # warm run: zero new misses, every gather a hit
    for c in caches:
        assert after_warm[c][1] == after_cold[c][1], f"{c}: warm run recorded misses"
        assert after_warm[c][0] > after_cold[c][0], f"{c}: warm run recorded no hits"
    # the duplicated messages dedup inside even the cold batch: 5 unique
    # (3 distinct single-key msgs + the aggregate msg shared with sets
    # 9/10 + the b"" padding msg) for 16 rows
    cold_h2c_hits = after_cold["h2c"][0] - before["h2c"][0]
    cold_h2c_miss = after_cold["h2c"][1] - before["h2c"][1]
    assert cold_h2c_miss == 5
    assert cold_h2c_hits == 11
    assert BLS_STAGE_SECONDS.count == n_stage + 2  # every staging is timed


def _limb_rows(pt):
    """A point's memoized (x, y) limb rows (pack.py keeps their bytes), or
    None where it has none."""
    rows = getattr(pt, "_limbs", None)
    return None if rows is None else np.frombuffer(rows, dtype=np.int32).reshape(2, fp.N_LIMBS)


def test_mutated_pubkey_bytes_cannot_serve_stale_limbs(jax_bls):
    """The PubkeyCache keys on (index, pubkey-bytes): mutate a validator's
    pubkey in the state and the resolver must hand back a fresh point whose
    limb rows pack the NEW key — never the cached rows of the old one."""
    from lighthouse_tpu.state_transition.context import PubkeyCache

    b = jax_bls

    class _Validator:
        def __init__(self, pubkey):
            self.pubkey = pubkey

    class _State:
        def __init__(self, pubkeys):
            self.validators = [_Validator(pk) for pk in pubkeys]

    _, pk_a = b.interop_keypair(100)
    _, pk_b = b.interop_keypair(101)
    state = _State([pk_a.to_bytes()])
    cache = PubkeyCache(b)

    first = cache.resolver(state)(0)
    assert first is not None
    rows_a = _limb_rows(first.point)
    assert rows_a is not None, "resolver must precompute limb rows (jax backend)"
    assert np.array_equal(rows_a[0], pack.pack_fp(pk_a.point.x.n))

    # memoized: same bytes -> same object, rows intact
    assert cache.resolver(state)(0) is first

    state.validators[0].pubkey = pk_b.to_bytes()
    second = cache.resolver(state)(0)
    assert second is not None and second is not first
    rows_b = _limb_rows(second.point)
    assert rows_b is not None
    assert np.array_equal(rows_b[0], pack.pack_fp(pk_b.point.x.n))
    assert not np.array_equal(rows_b[0], rows_a[0])

    # staging a set signed by the new key uses the new rows
    staged = japi.stage_sets(
        [b.SignatureSet(signature=b.Signature.infinity(), signing_keys=[second], message=b"m")],
        rng=japi._ONE_RNG,
    )
    assert np.array_equal(staged[0][0, 0], pack.pack_fp(pk_b.point.x.n))


def test_pubkey_cache_precompute_is_optional(jax_bls):
    """Backends without the staging hook (ref/fake) resolve unchanged."""
    from lighthouse_tpu.state_transition.context import PubkeyCache

    r = bls.backend("ref")
    assert PubkeyCache(r)._precompute is None
    assert PubkeyCache(bls.backend("fake"))._precompute is None
    assert PubkeyCache(jax_bls)._precompute is not None


def test_sync_committee_resolution_goes_through_cache():
    """altair.get_next_sync_committee must resolve pubkeys via the
    PubkeyCache — the second rotation decompresses nothing."""
    import dataclasses

    from lighthouse_tpu.state_transition import TransitionContext, interop_genesis_state
    from lighthouse_tpu.state_transition.altair import get_next_sync_committee
    from lighthouse_tpu.types import MINIMAL_SPEC
    from lighthouse_tpu.types.containers import minimal_types

    ctx = TransitionContext(
        minimal_types(),
        dataclasses.replace(MINIMAL_SPEC, altair_fork_epoch=0),
        bls.backend("fake"),
    )
    state = interop_genesis_state(8, 1_600_000_000, ctx)
    committee = get_next_sync_committee(state, ctx)
    assert len(ctx.pubkeys._cache) > 0, "committee resolution must populate the cache"

    calls = {"n": 0}
    orig = ctx.bls.PublicKey.from_bytes

    def counting(data):
        calls["n"] += 1
        return orig(data)

    ctx.bls.PublicKey.from_bytes = counting
    try:
        again = get_next_sync_committee(state, ctx)
    finally:
        ctx.bls.PublicKey.from_bytes = orig
    assert calls["n"] == 0, "second rotation must be served from the PubkeyCache"
    assert bytes(again.aggregate_pubkey) == bytes(committee.aggregate_pubkey)


# -- verify-kernel builds ----------------------------------------------------------


class _FakeLowered:
    """Stands in for a lowered stage program: compile() blocks until the
    test releases it (a TPU compile takes minutes) and counts itself."""

    def __init__(self, gate, builds, fail=False):
        self._gate, self._builds, self._fail = gate, builds, fail

    def compile(self):
        self._gate.wait(10.0)
        self._builds.append(1)
        if self._fail:
            raise RuntimeError("compile refused")
        return lambda flat: True


def test_verify_stages_build_once_across_threads_and_buckets(monkeypatch):
    """Callers that meet a stage while another thread compiles it wait for
    that compile; buckets share the stages their shapes allow (the final
    exponentiation always, the S-only stages per S); a failed build raises
    in every waiter and is not cached."""
    import threading
    import time

    gate, builds, lowered = threading.Event(), [], []

    def lower(key):
        lowered.append(key)
        return _FakeLowered(gate, builds, fail=key == ("keys", 8, 2, None))

    monkeypatch.setattr(japi, "_lower_stage", lower)
    monkeypatch.setattr(japi, "_kernels", {})
    monkeypatch.setattr(japi, "_chains", {})
    got = []
    waiter = threading.Thread(target=lambda: got.append(japi._verify_kernel(japi.Chain(4, 1))))
    waiter.start()
    deadline = time.monotonic() + 10.0
    while not lowered and time.monotonic() < deadline:
        time.sleep(0.001)
    again = threading.Thread(target=lambda: got.append(japi._verify_kernel(japi.Chain(4, 1))))
    again.start()
    gate.set()
    waiter.join(10.0)
    again.join(10.0)
    assert len(got) == 2 and got[1] is japi._verify_kernel(japi.Chain(4, 1))
    assert len(builds) == len(japi._VERIFY_CHAIN)

    # (4, 64) shares the S-only and shape-free stages; (16, 4) final only,
    # and its grouped chain (U = 8) adds unpack, group and pairs
    ready = japi.compile_verify_kernels([(4, 64), (16, 4)])
    assert set(ready) == {(4, 64), (16, 4)}
    assert len(builds) == len(japi._VERIFY_CHAIN) + 2 + 5 + 3
    assert lowered.index(("pairs", 16)) < lowered.index(("unpack", 16, 4, 8, None))
    with pytest.raises(RuntimeError, match="compile refused"):
        japi.compile_verify_kernels([(8, 2)])
    assert ("keys", 8, 2, None) not in japi._kernels


def _chain_stages(S, K, U, N, rows):
    """A chain's stage keys: the shared ones, and between h2c and pairs the
    grouped chain's group stage."""
    group = [("group", S, U)] if U else []
    return [("unpack", S, K, U, N), ("keys", S, K, N), ("sigs", S), ("h2c", S), *group,
            ("pairs", rows), ("final",)]


# (chain, its stage keys, its place among all of them sorted)
CHAINS = {
    "16x64-full": (japi.Chain(16, 64, None, 32), _chain_stages(16, 64, None, 32, 16), 0),
    "16x64-over-budget": (japi.Chain(16, 64, None, 64), _chain_stages(16, 64, None, 64, 16), 1),
    "16x64-grouped": (japi.Chain(16, 64, 8, 32), _chain_stages(16, 64, 8, 32, 8), 2),
    "128x4-full": (japi.Chain(128, 4), _chain_stages(128, 4, None, None, 128), 3),
    "128x4-grouped": (japi.Chain(128, 4, 64), _chain_stages(128, 4, 64, None, 64), 4),
    "256x512-full": (japi.Chain(256, 512, None, 1024),
                     _chain_stages(256, 512, None, 1024, 256), 5),
    "256x512-over-budget": (japi.Chain(256, 512, None, 2048),
                            _chain_stages(256, 512, None, 2048, 256), 6),
    "256x512-grouped": (japi.Chain(256, 512, 128, 1024),
                        _chain_stages(256, 512, 128, 1024, 128), 7),
}


@pytest.mark.parametrize("name", CHAINS)
def test_a_chain_plans_its_stage_keys_and_staged_layout(monkeypatch, name):
    """Each chain's stage keys; its staged fields fill the unpack program's
    input exactly and are its outputs (u at S rows: the grouped chain tiles
    its U message rows); a bucket is built with its full and grouped chains
    only; and the chains dispatched sort (perfbench logs them sorted)."""
    import jax

    chain, keys, rank = CHAINS[name]
    assert chain.stage_keys() == keys
    assert (chain in japi.Chain.bucket(chain.S, chain.K)) == ("over-budget" not in name)
    fields = chain.staged_fields()
    fn, (flat,) = japi.verify_stage(keys[0])
    assert flat.shape == (sum(int(np.prod(shape)) for _, shape, _ in fields),)
    out = jax.eval_shape(fn, flat)
    assert [(a.shape, a.dtype == bool) for a in out] == [
        ((chain.S, *shape[1:]) if field == "u" else shape, is_bool)
        for field, shape, is_bool in fields]
    monkeypatch.setattr(japi, "_chains", {c: None for c, _, _ in CHAINS.values()})
    assert sorted(japi._chains).index(chain) == rank


def test_ragged_batch_packs_only_real_keys_as_the_slow_path(jax_bls, monkeypatch):
    """A block-shaped batch (one large aggregate sets K, the rest pad) packs
    a point for each real key and each padding row's generator, never one
    per infinity slot, and stages the very key arrays of the per-point slow
    path (pack_g1 per slot, infinity in every unused one)."""
    b = jax_bls
    keys = [b.interop_keypair(i)[1] for i in range(12)]
    sig = b.Signature.infinity()
    lists = [keys[:1], keys[1:4], keys[:12], keys[4:6], keys[7:8]]
    sets_ = [b.SignatureSet(signature=sig, signing_keys=ks, message=bytes([i]) * 32)
             for i, ks in enumerate(lists)]
    packed = []
    real_pack = pack.pack_g1_batch

    def spy(pts):
        packed.append(len(pts))
        return real_pack(pts)

    monkeypatch.setattr(pack, "pack_g1_batch", spy)
    pk_x, pk_y, pk_inf = japi.stage_sets(sets_, rng=japi._ONE_RNG)[:3]
    S, K = pk_inf.shape
    assert (S, K) == japi.batch_bucket(len(sets_), 12) == (8, 16)
    assert packed == [sum(map(len, lists)) + S - len(sets_)]
    gen = japi._pad_generator()
    for i in range(S):
        row = [pk.point for pk in lists[i]] if i < len(sets_) else [gen]
        for j in range(K):
            x, y, inf = pack.pack_g1(row[j]) if j < len(row) else pack.pack_g1(g1_infinity())
            assert np.array_equal(pk_x[i, j], x) and np.array_equal(pk_y[i, j], y), (i, j)
            assert pk_inf[i, j] == inf, (i, j)


def test_dispatch_buffer_is_the_staged_arrays_as_int32_in_order(sets):
    """_pack_staged writes each staged array once into the one buffer the
    device unpacks: the int32 concatenation of their flattened values, bool
    flags as 0/1, a non-contiguous array as its values."""
    staged = japi.stage_sets(sets, rng=random.Random(3).getrandbits)
    staged = (*staged[:3], staged[3][:, ::-1].copy()[:, ::-1], *staged[4:])  # strided sig_x
    flat = japi._pack_staged(staged)
    want = np.concatenate([np.ravel(np.asarray(a)).astype(np.int32) for a in staged])
    assert flat.dtype == np.int32 and np.array_equal(flat, want)
    assert flat.size == sum(int(np.prod(s)) for _, s, _ in japi.Chain(16, 4).staged_fields())


def test_staging_floor_rounds_to_a_pow2_bucket(sets):
    """s_floor (bisection keeps its batch's bucket) never makes a non-pow2
    S: 11 sets with a floor of 100 stage into the 128 bucket."""
    staged = japi.stage_sets(sets, s_floor=100)
    assert staged[2].shape == japi.batch_bucket(len(sets), 3, 100) == (128, 4)


# -- the chunked key layout (K > KEY_CHUNK) --------------------------------------


@pytest.fixture(scope="module")
def keys64(jax_bls):
    return [jax_bls.interop_keypair(i)[1] for i in range(64)]


def _key_sets(b, keys, lists, msgs=None):
    sig = b.Signature.infinity()
    msgs = msgs or [bytes([i]) * 32 for i in range(len(lists))]
    return [b.SignatureSet(signature=sig, signing_keys=[keys[j] for j in ks], message=m)
            for ks, m in zip(lists, msgs)]


def _assert_chunked_rows(staged, rows):
    """Row i's keys fill the slots of the chunks whose set index is i, in
    order, each as pack_g1 packs it, infinity after them; the unused
    chunks hold infinity and set index S."""
    pk_x, pk_y, pk_inf, chunk_set = staged[0], staged[1], staged[2], staged[8]
    C, S = japi.KEY_CHUNK, len(staged[7])
    assert pk_x.shape == (len(chunk_set), C, 32) and pk_inf.shape == (len(chunk_set), C)
    assert chunk_set.dtype == np.int32 and np.all(np.diff(chunk_set) >= 0)
    for i, pts in enumerate(rows):
        chunks = np.flatnonzero(chunk_set == i)
        assert len(chunks) == -(-len(pts) // C), i
        assert np.array_equal(chunks, np.arange(chunks[0], chunks[0] + len(chunks)))
        slots = len(chunks) * C
        xs, ys, infs = (a[chunks].reshape(slots, *a.shape[2:]) for a in (pk_x, pk_y, pk_inf))
        for j in range(slots):
            x, y, inf = pack.pack_g1(pts[j] if j < len(pts) else g1_infinity())
            assert np.array_equal(xs[j], x) and np.array_equal(ys[j], y) and infs[j] == inf
    unused = chunk_set == S
    assert pk_inf[unused].all() and not pk_x[unused].any() and not pk_y[unused].any()


def test_chunked_staging_puts_each_key_in_its_sets_chunks_in_order(jax_bls, keys64):
    """A ragged batch with K > 32: (8, 64) in the bucket's 16 chunks of 32
    slots, the padding rows' generator in a chunk of their own."""
    lists = [[0], list(range(33)), list(range(64)), list(range(5, 17)), list(range(31))]
    sets_ = _key_sets(jax_bls, keys64, lists)
    staged, group = japi.stage_sets_grouped(sets_, rng=random.Random(5).getrandbits)
    assert group is None and japi.key_chunks(8, 64) == 16 == len(staged[8])
    gen = japi._pad_generator()
    _assert_chunked_rows(staged, [[s.point for s in st.signing_keys] for st in sets_]
                         + [[gen]] * 3)
    assert staged[8].tolist() == [0, 1, 1, 2, 2, 3, 4, 5, 6, 7] + [8] * 6
    # every array but the keys' is stage_sets' own
    dense = japi.stage_sets(sets_, rng=random.Random(5).getrandbits)
    for k in range(3, 8):
        assert np.array_equal(staged[k], dense[k]), k


def test_chunked_staging_follows_the_message_plan_order(jax_bls, keys64):
    """In a grouped batch the plan orders the sets first, and their chunks
    follow that order: the chunk->set index stays sorted."""
    lists = [list(range(40)), [1], list(range(64)), [2, 3], list(range(33))]
    msgs = [b"a" * 32, b"b" * 32, b"a" * 32, b"b" * 32, b"c" * 32]
    sets_ = _key_sets(jax_bls, keys64, lists, msgs)
    seeded = lambda: random.Random(9).getrandbits  # noqa: E731
    staged, group = japi.stage_sets_grouped(sets_, rng=seeded())
    order, plan_group, _ = japi._message_plan(msgs, 8)
    assert np.array_equal(group, plan_group) and order[:5].tolist() == [0, 2, 1, 3, 4]
    gen = japi._pad_generator()
    _assert_chunked_rows(staged, [[pk.point for pk in sets_[i].signing_keys] for i in order[:5]]
                         + [[gen]] * 3)
    dense = japi.stage_sets(sets_, rng=seeded())
    for k in (3, 4, 5, 7):  # sig_x, sig_y, sig_inf, r_bits: each set's own row
        assert np.array_equal(staged[k], dense[k][order]), k


@pytest.mark.parametrize(
    "batch,digest",
    [
        ("sets", "03a188449a2939d6b2b3074e29751e1042dfccd1a41dca7c304996665ec0cdcf"),
        ("k32", "f92831273664b5e25741174253c4fd0e4e583b0f682bf12037d45ecdb1c6b0c8"),
    ],
    ids=["grouped-16x4", "full-8x32"],
)
def test_a_batch_of_at_most_32_keys_stages_its_dense_buffer_unchanged(
        jax_bls, sets, keys64, batch, digest):
    """Where K <= KEY_CHUNK the dispatch stages the dense (S, K) layout,
    byte for byte the buffer it staged before the chunked layout existed
    (its SHA-256 then): this module's batch (grouped) and a 32-key one."""
    import hashlib

    if batch == "k32":
        sets = _key_sets(jax_bls, keys64, [list(range(k)) for k in (32, 1, 5, 17, 2)])
    staged, group = japi.stage_sets_grouped(sets, rng=random.Random(42).getrandbits)
    assert japi.key_chunks(*staged[2].shape) is None and len(staged) == 8
    if group is not None:
        staged = (*staged, group)
    assert hashlib.sha256(japi._pack_staged(staged).tobytes()).hexdigest() == digest


# -- the coalescer's staging stage ---------------------------------------------


def test_stager_fault_fails_batch_and_counts(jax_bls):
    """A backend whose async staging raises must still resolve every
    future (all-False via bisection) and count the fault."""
    from lighthouse_tpu.common.metrics import BLS_COALESCER_INTERNAL_ERRORS_TOTAL
    from lighthouse_tpu.crypto.bls.batch_verifier import BatchVerifier

    class ExplodingBackend:
        def verify_signature_sets(self, sets, rng=None):
            raise RuntimeError("boom")

        def verify_signature_sets_async(self, sets, rng=None):
            raise RuntimeError("boom")

    e0 = BLS_COALESCER_INTERNAL_ERRORS_TOTAL.value
    svc = BatchVerifier(ExplodingBackend(), max_wait=0.01).start()
    try:
        futs = [svc.submit([object()]) for _ in range(3)]
        for f in futs:
            assert f.result(timeout=10.0) == [False]
    finally:
        svc.stop()
    assert BLS_COALESCER_INTERNAL_ERRORS_TOTAL.value > e0


# -- device parity (slow tier) -------------------------------------------------


@pytest.mark.slow
def test_duplicated_message_batch_verifies_with_ref_parity(jax_bls, sets):
    """The deduped staging path feeds the device kernel a batch with heavy
    message duplication; the verdict must match the pure-Python oracle's,
    valid and tampered."""
    b = jax_bls
    r = bls.backend("ref")

    def to_ref(ss):
        return [
            r.SignatureSet(
                signature=r.Signature(s.signature.point),
                signing_keys=[r.PublicKey(pk.point) for pk in s.signing_keys],
                message=s.message,
            )
            for s in ss
        ]

    _chill(sets)
    subset = sets[:4]  # 2 distinct messages across 4 sets
    seeded = random.Random(7).getrandbits
    assert b.verify_signature_sets(subset, rng=seeded) is True
    assert r.verify_signature_sets(to_ref(subset), rng=seeded) is True

    tampered = subset[:3] + [
        b.SignatureSet(
            signature=subset[0].signature,
            signing_keys=subset[1].signing_keys,
            message=subset[0].message,
        )
    ]
    assert b.verify_signature_sets(tampered, rng=seeded) is False
    assert r.verify_signature_sets(to_ref(tampered), rng=seeded) is False
