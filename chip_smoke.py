"""Prove that the BLS verifier runs on one TPU chip through the node's path.

    python chip_smoke.py [--seed S]

One process owns the chip and starts no child. Phases, in order:

1. Device check: jax must report a TPU. There is no CPU path: anything
   else exits non-zero and prints no result.
2. Node path: a mainnet-preset node built as `beacon-node --bls-backend jax`
   builds it (`Client(ClientConfig(...))`), with the gossip coalescer
   running over the jax backend. The harness drives it for a few slots:
   blocks import through BlockSignatureVerifier (VERIFY_BULK), and the
   slots' unaggregated attestations go BeaconProcessor -> BatchVerifier ->
   jax as one full 128-set batch. The head must advance and every gossip
   verdict must be True.
3. Mainnet-shaped batches, through the backend calls the node makes, with
   keys drawn (seeded, distinct within a set) from the node's interop keys:
   a block (128 committee aggregates x 128 keys + proposer and randao sets,
   staged as S=256, K=128), a gossip batch (128 one-key sets over 64
   messages, submitted from several threads so the coalescer forms one
   S=128 batch; staging pads K to 4 and groups the sets by message, so the
   device pairs 64 rows) and a 512-key sync aggregate. Valid
   batches must verify True, the same batches with one set tampered False,
   and gossip blame must name exactly the tampered set; two gossip sets
   over one message that swap signatures must be blamed, and no other.
   The device hash-to-G2 program of each row width compiled is checked
   against the oracle's map, row by row.
4. Report: device, per-bucket ready/warm seconds (smoke timings, not
   benchmark numbers), kernel builds, peak device bytes, and whether
   fp.mul_mxu equals fp.mul bit for bit on the chip.

The buckets' stage programs are compiled up front and concurrently
(`compile_verify_kernels`) while the host derives keys and signs: cold,
compilation is most of the run. The last stdout line
is the JSON result; any failure exits non-zero before it.
"""

from __future__ import annotations

import argparse
import json
import random
import resource
import sys
import threading
import time

T0 = time.perf_counter()
# Mainnet shapes (BASELINE.md): 4 node slots x 32-member committees (1,024
# validators) = one 128-set gossip batch; a block of 128 aggregates x 128
# keys; gossip batches of 128 one-key sets; a 512-key sync aggregate.
VALIDATORS = 1024
NODE_GOSSIP_SLOTS = 4
BLOCK_SETS = BLOCK_KEYS = GOSSIP_SETS = 128
GOSSIP_MESSAGES = 64  # a slot's committees: one AttestationData each
SYNC_KEYS = 512


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def _sign_aggregate(bls, keys, members, message):
    """One signature over `message` by every key in `members`: the host
    signs once with (sum sk mod r), which equals the aggregate of the
    members' signatures."""
    from lighthouse_tpu.crypto.bls.constants import R

    sk = bls.SecretKey(sum(keys[i][0].k for i in members) % R)
    return bls.SignatureSet(
        signature=sk.sign(message),
        signing_keys=[keys[i][1] for i in members],
        message=message,
    )


def _tamper(sets, index):
    """The same batch with set `index` carrying a neighbour's signature."""
    bad = list(sets)
    other = sets[(index + 1) % len(sets)]
    bad[index] = type(sets[index])(
        signature=other.signature,
        signing_keys=sets[index].signing_keys,
        message=sets[index].message,
    )
    return bad


def _swap(sets, a, b):
    """The same batch with sets `a` and `b` carrying each other's
    signature: their sum is unchanged, so only per-set scalars catch it."""
    bad = list(sets)
    for i, j in ((a, b), (b, a)):
        bad[i] = type(sets[i])(
            signature=sets[j].signature,
            signing_keys=sets[i].signing_keys,
            message=sets[i].message,
        )
    return bad


def _timed_verify(bls, sets) -> tuple[bool, float]:
    t = time.perf_counter()
    ok = bool(bls.verify_signature_sets(sets))
    return ok, time.perf_counter() - t


# -- phase 2: the node path ----------------------------------------------------


def node_phase(n_validators: int, gossip_slots: int = NODE_GOSSIP_SLOTS):
    """Drive a jax-backed mainnet node; returns (client, harness keys)."""
    from lighthouse_tpu.chain.harness import BeaconChainHarness
    from lighthouse_tpu.client import Client, ClientConfig
    from lighthouse_tpu.crypto import bls as bls_pkg

    client = Client(
        ClientConfig(
            preset="mainnet",
            bls_backend="jax",
            interop_validators=n_validators,
            genesis_time=int(time.time()),
        )
    )
    bls = client.ctx.bls
    svc = client.coalescer
    assert bls is bls_pkg.backend("jax"), bls
    assert svc is not None and svc.running and svc.backend is bls, "coalescer not on jax"
    log(f"node up: mainnet, {n_validators} validators, coalescer running over jax")
    chain = client.chain
    h = BeaconChainHarness.for_chain(chain, n_validators)
    gossip = []
    votes = h.committee_votes(chain.head_state(), chain.head_root, 0)
    for slot in range(1, gossip_slots + 2):
        aggregates = [
            client.ctx.types.Attestation(
                aggregation_bits=[True] * len(committee),
                data=data,
                signature=bls.aggregate_signatures(sigs).to_bytes(),
            )
            for committee, data, sigs in votes
        ]
        t = time.perf_counter()
        root, _ = h.add_block_at_slot(slot, attestations=aggregates)
        assert chain.head_root == root, f"head did not move to the slot-{slot} block"
        log(
            f"node: block {slot} imported (VERIFY_BULK, {len(aggregates)} aggregate(s) "
            f"of {max(len(c) for c, _, _ in votes)} keys) in {time.perf_counter() - t:.2f}s"
        )
        if slot > gossip_slots:
            break
        votes = h.committee_votes(chain.head_state(), root, slot)
        for committee, data, sigs in votes:
            for i, sig in enumerate(sigs):
                bits = [False] * len(committee)
                bits[i] = True
                gossip.append(
                    client.ctx.types.Attestation(
                        aggregation_bits=bits, data=data, signature=sig.to_bytes()
                    )
                )

    accepted = []  # block import reports its votes here too: register after it
    chain.attestation_observers.append(lambda vi, epoch: accepted.append(vi))
    d0 = svc.dispatches
    for att in gossip:
        assert client.submit_gossip_attestation(att), "processor dropped a vote"
    t = time.perf_counter()
    client.per_slot_task(chain.slot())
    assert len(accepted) == len(gossip), (
        f"{len(gossip) - len(accepted)} of {len(gossip)} gossip verdicts not True"
    )
    log(
        f"node: {len(gossip)} gossip attestations -> BeaconProcessor -> BatchVerifier "
        f"-> jax: all True in {svc.dispatches - d0} dispatch(es), "
        f"{time.perf_counter() - t:.2f}s; head slot {chain.head_state().slot}"
    )
    return client, h.keypairs


# -- phase 3: mainnet-shaped batches ------------------------------------------


def block_phase(bls, keys, rng, n_sets: int, k: int):
    """128 committee aggregates x 128 keys + proposer and randao sets."""
    sets = [
        _sign_aggregate(bls, keys, rng.sample(range(len(keys)), k), rng.randbytes(32))
        for _ in range(n_sets)
    ]
    sets += [_sign_aggregate(bls, keys, [rng.randrange(len(keys))], rng.randbytes(32)) for _ in range(2)]
    assert bls.verify_signature_sets(sets), "valid block batch verified False"
    ok, warm = _timed_verify(bls, sets)
    assert ok
    assert not bls.verify_signature_sets(_tamper(sets, n_sets // 3)), "tampered block verified True"
    log(f"block: {len(sets)} sets (K<={k}) True; one tampered set -> False")
    return warm


def gossip_phase(bls, svc, keys, rng, n_sets: int, n_messages: int, threads: int = 8):
    """One-key sets from several threads through the running coalescer,
    formed into ONE batch: the device-idle and deadline flushes are held
    off until every set is queued, then a kick flushes the full bucket.
    Set i carries message i % n_messages: with at most n_sets/2 messages
    the batch is staged with n_sets/2 message rows."""
    from lighthouse_tpu.common.metrics import BLS_MESSAGE_ROWS_TOTAL
    from lighthouse_tpu.crypto.bls.batch_verifier import mark_device_busy

    def message_rows():
        return sum(BLS_MESSAGE_ROWS_TOTAL.labels(row=r).value for r in ("distinct", "pad"))

    messages = [rng.randbytes(32) for _ in range(n_messages)]
    members = rng.sample(range(len(keys)), n_sets)
    sets = [_sign_aggregate(bls, keys, [m], messages[i % len(messages)]) for i, m in enumerate(members)]

    def submit_all(batch):
        d0, wait = svc.dispatches, svc.max_wait
        futures = [None] * len(batch)

        def worker(lo):
            for i in range(lo, len(batch), threads):
                futures[i] = svc.submit([batch[i]])

        svc.max_wait = 3600.0
        try:
            with mark_device_busy():
                workers = [threading.Thread(target=worker, args=(t,)) for t in range(threads)]
                for w in workers:
                    w.start()
                for w in workers:
                    w.join()
                svc.kick()
                verdicts = [f.result(timeout=900)[0] for f in futures]
        finally:
            svc.max_wait = wait
        return verdicts, svc.dispatches - d0

    rows = message_rows()
    verdicts, dispatches = submit_all(sets)
    rows = message_rows() - rows
    assert dispatches == 1, f"gossip split into {dispatches} coalesced batches"
    assert all(verdicts), "valid gossip set verified False"
    want_rows = n_sets // 2 if n_messages <= n_sets // 2 else n_sets
    assert rows == want_rows, f"gossip batch staged {rows} message rows, not {want_rows}"
    bad = n_sets // 2 + 1
    verdicts, _ = submit_all(_tamper(sets, bad))
    blamed = [i for i, v in enumerate(verdicts) if not v]
    assert blamed == [bad], f"blame {blamed}, tampered {bad}"
    pair = [3, 3 + n_messages]
    verdicts, _ = submit_all(_swap(sets, *pair))
    blamed = [i for i, v in enumerate(verdicts) if not v]
    assert blamed == pair, f"blame {blamed}, same-message swap {pair}"
    ok, warm = _timed_verify(bls, sets)
    assert ok
    log(f"gossip: {n_sets} sets over {n_messages} messages in 1 coalesced batch of {rows} "
        f"message rows True; tampered set {bad} blamed alone; same-message swap {pair} "
        f"blamed, no other set")
    return warm


def node_bucket_warm(bls, keys, rng, committee: int) -> float:
    """A node-block-shaped batch (proposer, randao, one committee
    aggregate): one warm call in the node phase's bucket."""
    sets = [_sign_aggregate(bls, keys, [rng.randrange(len(keys))], rng.randbytes(32)) for _ in range(2)]
    sets.append(_sign_aggregate(bls, keys, rng.sample(range(len(keys)), committee), rng.randbytes(32)))
    ok, warm = _timed_verify(bls, sets)
    assert ok, "node-shaped batch verified False"
    return warm


def sync_phase(bls, keys, rng, k: int):
    members = rng.sample(range(len(keys)), k)
    sets = [_sign_aggregate(bls, keys, members, rng.randbytes(32))]
    assert bls.verify_signature_sets(sets), "valid sync aggregate verified False"
    ok, warm = _timed_verify(bls, sets)
    assert ok
    wrong = [bls.SignatureSet(sets[0].signature, sets[0].signing_keys, rng.randbytes(32))]
    assert not bls.verify_signature_sets(wrong), "sync aggregate over a wrong root verified True"
    log(f"sync: one {k}-key aggregate True; wrong message -> False")
    return warm


def h2c_phase(rng, n_messages: int = 64) -> None:
    """The device hash-to-G2 program of every row width the chains
    compiled (each bucket's S; the grouped chains hash at S too), against
    the oracle's map: `n_messages` seeded messages tiled over the rows.
    Fused differently, the TPU compiler once got this map wrong where the
    CPU was right (h2c.map_to_g2)."""
    import jax.numpy as jnp
    import numpy as np

    from lighthouse_tpu.crypto.bls.constants import DST
    from lighthouse_tpu.crypto.bls.jax_backend import api, h2c
    from lighthouse_tpu.crypto.bls.jax_backend.pack import unpack_fp2_el
    from lighthouse_tpu.crypto.bls.ref.hash_to_curve import hash_to_g2

    messages = [rng.randbytes(32) for _ in range(n_messages)]
    want = [hash_to_g2(m, DST) for m in messages]
    for S in sorted(key[1] for key in list(api._kernels) if key[0] == "h2c"):
        rows = [messages[i % n_messages] for i in range(S)]
        prog = api.compiled_stage(("h2c", S))
        x, y, z = (np.asarray(c) for c in prog(jnp.asarray(h2c.hash_to_field_limbs(rows))))
        bad = []
        for i in range(S):
            pt, zi = want[i % n_messages], unpack_fp2_el(z[i])
            if unpack_fp2_el(x[i]) != pt.x * zi or unpack_fp2_el(y[i]) != pt.y * zi:
                bad.append(i)
        assert not bad, f"device hash-to-G2 at S={S} differs from the oracle in rows {bad[:8]}"
        log(f"h2c: device map at S={S} equals the oracle's on all {S} rows")


# -- phase 4: report -----------------------------------------------------------


def mxu_matches(seed: int, n: int = 4096) -> bool:
    """fp.mul_mxu against fp.mul, bit for bit, on seeded field elements."""
    import jax
    import numpy as np

    from lighthouse_tpu.crypto.bls.constants import P
    from lighthouse_tpu.crypto.bls.jax_backend import fp

    r = random.Random(seed)
    a, b = (np.stack([fp.int_to_limbs(r.randrange(P)) for _ in range(n)]) for _ in range(2))
    want = np.asarray(jax.jit(fp.mul)(a, b))
    got = np.asarray(jax.jit(fp.mul_mxu)(a, b))
    return bool(np.array_equal(want, got))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax found platform {dev.platform!r}", file=sys.stderr)
        return 2
    from lighthouse_tpu.common.metrics import BLS_JIT_BUILDS_TOTAL
    from lighthouse_tpu.crypto import bls as bls_pkg
    from lighthouse_tpu.crypto.bls.jax_backend import api

    log(f"device: {dev.device_kind} x{len(devices)} ({dev.platform})")

    bls = bls_pkg.backend("jax")
    # mainnet, under 4,096 validators: one committee a slot, of N/32 members
    committee = VALIDATORS // 32
    node_bucket = api.batch_bucket(3, committee)  # proposer, randao, 1 aggregate
    block_bucket = api.batch_bucket(BLOCK_SETS + 2, BLOCK_KEYS)
    gossip_bucket = api.batch_bucket(GOSSIP_SETS, 1)
    sync_bucket = api.batch_bucket(1, SYNC_KEYS)
    buckets = [node_bucket, gossip_bucket, block_bucket, sync_bucket]  # as phases need them
    ready: dict = {}
    errors: list = []

    def compile_all():
        try:
            ready.update(api.compile_verify_kernels(buckets))
        except BaseException as exc:  # surfaced by the main thread
            errors.append(exc)

    compiler = threading.Thread(target=compile_all, name="compile-buckets", daemon=True)
    compiler.start()
    log(f"compiling the stage programs of buckets {buckets}")

    rng = random.Random(args.seed)
    client, keys = node_phase(VALIDATORS)
    try:
        warm = {}
        warm[node_bucket] = node_bucket_warm(bls, keys, rng, committee)
        warm[gossip_bucket] = gossip_phase(
            bls, client.coalescer, keys, rng, GOSSIP_SETS, GOSSIP_MESSAGES
        )
        warm[block_bucket] = block_phase(bls, keys, rng, BLOCK_SETS, BLOCK_KEYS)
        warm[sync_bucket] = sync_phase(bls, keys, rng, SYNC_KEYS)
        compiler.join()
        if errors:
            raise errors[0]
        h2c_phase(rng)
        mxu = mxu_matches(args.seed)
    finally:
        client.shutdown()

    log(f"device: kind={dev.device_kind} count={len(devices)}")
    for b, t in sorted(ready.items()):
        log(
            f"bucket S={b[0]} K={b[1]}: ready {t:.1f}s after start of compile "
            f"(compile or cache load), warm call {warm[b]:.3f}s  [smoke timings, not benchmarks]"
        )
    log(f"BLS_JIT_BUILDS_TOTAL{{kernel=verify}} = {BLS_JIT_BUILDS_TOTAL.labels(kernel='verify').value:g}")
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use = {stats.get('peak_bytes_in_use', 'not reported')}")
    log(f"host peak RSS = {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024} bytes")
    log(f"fp.mul_mxu == fp.mul bit for bit: {mxu}")
    if not mxu:
        print("chip_smoke: fp.mul_mxu differs from fp.mul on the chip", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
