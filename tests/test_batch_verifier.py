"""Cross-caller BLS batch coalescing (crypto/bls/batch_verifier.py).

Covers the BatchVerifier service contract: coalescing under concurrent
submitters (the >=8x-fewer-dispatches acceptance bar), deadline flush with
pipelined submission while a batch executes, bisection blaming exactly the
invalid sets in mixed batches, synchronous single-set fallback when the
service is stopped, and verdict parity with direct `verify_signature_sets`
(rng-seeded, on the real jax backend — slow tier).

Fast-tier tests drive the service with stub backends (the coalescer is
backend-agnostic by design) so the scheduling/bisection logic is exercised
without kernel compiles; the fake backend provides real structural-rule
semantics; the jax parity test carries @pytest.mark.slow.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass

import pytest

from lighthouse_tpu.crypto.bls.batch_verifier import (
    BatchVerifier,
    active_for,
    ensure_running,
    release,
    verify_sets,
)


@dataclass
class StubSet:
    valid: bool = True


class StubBackend:
    """Synchronous backend: verdict = AND of the sets' validity flags (the
    all-or-nothing RLC semantics), with an optional per-call latency that
    stands in for device execution time."""

    def __init__(self, latency: float = 0.0):
        self.latency = latency
        self.calls: list[int] = []
        self._lock = threading.Lock()

    def verify_signature_sets(self, sets, rng=None):
        with self._lock:
            self.calls.append(len(sets))
        if self.latency:
            time.sleep(self.latency)
        return bool(sets) and all(s.valid for s in sets)


class _GatedFuture:
    def __init__(self, backend, ok):
        self._backend = backend
        self._ok = ok

    def result(self):
        self._backend.gate.wait(10.0)
        return self._ok


class GatedBackend(StubBackend):
    """Async backend whose in-flight batches block until the gate opens —
    lets tests hold the 'device' busy and watch pipelined submission."""

    def __init__(self):
        super().__init__()
        self.gate = threading.Event()

    def verify_signature_sets_async(self, sets, rng=None):
        with self._lock:
            self.calls.append(len(sets))
        return _GatedFuture(self, bool(sets) and all(s.valid for s in sets))


def test_concurrent_submitters_coalesce_into_few_dispatches():
    """64 concurrent single-set callers must share device batches: >= 8x
    fewer dispatches than the per-caller path (the acceptance bar),
    asserted via the service's dispatch counter and the metric family."""
    from lighthouse_tpu.common.metrics import BLS_COALESCED_DISPATCHES_TOTAL

    backend = StubBackend(latency=0.03)
    svc = BatchVerifier(backend, s_bucket=128, max_wait=0.1).start()
    d0 = BLS_COALESCED_DISPATCHES_TOTAL.value
    try:
        results = [None] * 64
        barrier = threading.Barrier(64)

        def caller(i):
            barrier.wait()
            results[i] = svc.submit([StubSet()]).result(timeout=10.0)

        threads = [threading.Thread(target=caller, args=(i,)) for i in range(64)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
        assert results == [[True]] * 64
        # per-caller path = 64 dispatches; the coalescer must do <= 8
        assert svc.dispatches <= 8, f"{svc.dispatches} dispatches for 64 callers"
        assert sum(backend.calls) == 64  # every set verified exactly once
        assert BLS_COALESCED_DISPATCHES_TOTAL.value - d0 == svc.dispatches
    finally:
        svc.stop()


def test_bisection_blames_exactly_the_invalid_sets():
    """A mixed coalesced batch with k invalid sets rejects exactly those k
    while every honest set still verifies true."""
    from lighthouse_tpu.common.metrics import (
        BLS_BISECTION_BATCHES_TOTAL,
        BLS_BISECTION_BLAMED_SETS_TOTAL,
    )

    backend = StubBackend(latency=0.01)
    svc = BatchVerifier(backend, s_bucket=128, max_wait=0.1).start()
    b0 = BLS_BISECTION_BATCHES_TOTAL.value
    k0 = BLS_BISECTION_BLAMED_SETS_TOTAL.value
    try:
        valid = [i % 5 != 0 for i in range(64)]  # 13 invalid, scattered
        futures = [None] * 64
        barrier = threading.Barrier(64)

        def caller(i):
            barrier.wait()
            futures[i] = svc.submit([StubSet(valid=valid[i])])

        threads = [threading.Thread(target=caller, args=(i,)) for i in range(64)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
        verdicts = [f.result(timeout=10.0)[0] for f in futures]
        assert verdicts == valid  # blame exactly the invalid ones
        assert BLS_BISECTION_BATCHES_TOTAL.value > b0
        assert BLS_BISECTION_BLAMED_SETS_TOTAL.value - k0 == valid.count(False)
    finally:
        svc.stop()


class _RaisingFuture:
    def __init__(self, exc):
        self._exc = exc

    def result(self):
        raise self._exc


class DeviceFailingBackend(StubBackend):
    """Async backend whose device fails: the JAX runtime error is raised at
    dispatch (a compile error, an HBM OOM at allocation) or when the
    verdict is fetched (an execution error)."""

    def __init__(self, where: str):
        super().__init__()
        self.where = where

    def verify_signature_sets_async(self, sets, rng=None):
        import jax

        with self._lock:
            self.calls.append(len(sets))
        exc = jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of HBM")
        if self.where == "dispatch":
            raise exc
        return _RaisingFuture(exc)


@pytest.mark.parametrize("where", ["dispatch", "result"])
def test_device_error_fails_futures_without_bisection(where):
    """A JAX runtime error is no verdict: every future of the batch raises
    it, the device-error counter counts it, and no bisection dispatch is
    sent (the failed batch is the only call the backend sees)."""
    import jax

    from lighthouse_tpu.common.metrics import (
        BLS_BISECTION_DISPATCHES_TOTAL,
        BLS_DEVICE_ERRORS_TOTAL,
    )

    backend = DeviceFailingBackend(where)
    svc = BatchVerifier(backend, s_bucket=4, max_wait=1.0)
    e0 = BLS_DEVICE_ERRORS_TOTAL.value
    d0 = BLS_BISECTION_DISPATCHES_TOTAL.value
    # queue all four before the collector starts: one full S=4 batch
    svc._running = True
    futures = [svc.submit([StubSet(valid=i != 2)]) for i in range(4)]
    svc._running = False
    svc.start()
    try:
        for f in futures:
            with pytest.raises(jax.errors.JaxRuntimeError, match="out of HBM"):
                f.result(timeout=10.0)
        assert backend.calls == [4]
        assert BLS_DEVICE_ERRORS_TOTAL.value - e0 == 1
        assert BLS_BISECTION_DISPATCHES_TOTAL.value == d0
    finally:
        svc.stop()


def test_stopped_service_raises_device_error_to_the_caller():
    import jax

    class SyncDeviceFailure(StubBackend):
        def verify_signature_sets(self, sets, rng=None):
            self.calls.append(len(sets))
            raise jax.errors.JaxRuntimeError("INTERNAL: device lost")

    backend = SyncDeviceFailure()
    fut = BatchVerifier(backend).submit([StubSet(), StubSet()])
    with pytest.raises(jax.errors.JaxRuntimeError, match="device lost"):
        fut.result(timeout=1.0)
    assert backend.calls == [2]  # no per-set retries


def test_decode_error_set_still_gets_a_false_verdict():
    """Hostile input stays a verdict: a set whose staging raises the
    backend's DecodeError is blamed alone (False) by bisection while its
    batchmates verify True, and no device error is counted."""
    from lighthouse_tpu.common.metrics import BLS_DEVICE_ERRORS_TOTAL
    from lighthouse_tpu.crypto.bls.ref.api import DecodeError

    @dataclass
    class Undecodable:
        valid: bool = False

    class StagingBackend(StubBackend):
        def verify_signature_sets_async(self, sets, rng=None, s_floor=None):
            if any(isinstance(s, Undecodable) for s in sets):
                raise DecodeError("signature is not a curve point")
            return _Verdict(self.verify_signature_sets(sets))

    class _Verdict:
        def __init__(self, ok):
            self._ok = ok

        def result(self):
            return self._ok

    e0 = BLS_DEVICE_ERRORS_TOTAL.value
    svc = BatchVerifier(StagingBackend(), max_wait=0.01).start()
    try:
        sets = [StubSet(), Undecodable(), StubSet(), StubSet()]
        assert svc.submit(sets).result(timeout=10.0) == [True, False, True, True]
    finally:
        svc.stop()
    assert BLS_DEVICE_ERRORS_TOTAL.value == e0


def test_processor_isolation_reraises_device_errors():
    """The drain's hostile-input boundary retries per item and drops
    offenders — but a device error is no offender: it propagates at once,
    with no per-item retries."""
    import jax

    from lighthouse_tpu.scheduler import BeaconProcessor

    seen = []

    def handler(items):
        seen.append(len(items))
        raise jax.errors.JaxRuntimeError("INTERNAL: device lost")

    with pytest.raises(jax.errors.JaxRuntimeError):
        BeaconProcessor.isolated(handler)([1, 2, 3])
    assert seen == [3]


def test_bisection_halves_keep_the_failed_batch_bucket():
    """Blame dispatches are staged at the failed batch's size (s_floor),
    so on the device they reuse its compiled bucket instead of compiling
    every smaller one on the way down."""

    class _Verdict:
        def __init__(self, ok):
            self._ok = ok

        def result(self):
            return self._ok

    class FloorRecordingBackend(StubBackend):
        def __init__(self):
            super().__init__()
            self.floors = []

        def verify_signature_sets_async(self, sets, rng=None, s_floor=None):
            self.floors.append((len(sets), s_floor))
            return _Verdict(all(s.valid for s in sets))

    backend = FloorRecordingBackend()
    svc = BatchVerifier(backend, max_wait=0.01).start()
    try:
        sets = [StubSet(valid=i != 5) for i in range(8)]
        assert svc.submit(sets).result(timeout=10.0) == [i != 5 for i in range(8)]
    finally:
        svc.stop()
    assert backend.floors[0] == (8, None)
    assert [n for n, _ in backend.floors[1:]] == [4, 4, 2, 2, 1, 1]
    assert {f for _, f in backend.floors[1:]} == {8}


def test_multi_set_submission_gets_per_set_verdicts():
    backend = StubBackend()
    svc = BatchVerifier(backend, max_wait=0.01).start()
    try:
        sets = [StubSet(), StubSet(valid=False), StubSet(), StubSet(valid=False)]
        assert svc.submit(sets).result(timeout=10.0) == [True, False, True, False]
        assert svc.submit([]).result(timeout=10.0) == []
    finally:
        svc.stop()


def test_deadline_flush_pipelines_while_device_busy():
    """While batch i executes (gate closed), later submissions must still
    dispatch at the max-latency deadline — batch i+1 is staged and
    submitted before batch i's verdict is awaited (double buffering)."""
    backend = GatedBackend()
    svc = BatchVerifier(backend, s_bucket=128, max_wait=0.05).start()
    try:
        f1 = svc.submit([StubSet()])  # device idle -> dispatched immediately
        deadline = time.monotonic() + 5.0
        while len(backend.calls) < 1 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert backend.calls == [1]
        f2 = svc.submit([StubSet()])
        f3 = svc.submit([StubSet()])
        # batch 1 is still executing (gate closed): the deadline must flush
        # the two new sets as ONE pipelined batch
        deadline = time.monotonic() + 5.0
        while len(backend.calls) < 2 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert backend.calls == [1, 2]
        assert not f1.done()  # nothing resolved while the gate is closed
        backend.gate.set()
        assert f1.result(timeout=10.0) == [True]
        assert f2.result(timeout=10.0) == [True]
        assert f3.result(timeout=10.0) == [True]
    finally:
        svc.stop()


def test_stopped_service_falls_back_to_direct_verification():
    backend = StubBackend()
    svc = BatchVerifier(backend)
    assert not svc.running
    assert svc.submit([StubSet()]).result(timeout=1.0) == [True]
    assert svc.submit([StubSet(valid=False)]).result(timeout=1.0) == [False]
    assert svc.submit([StubSet(), StubSet(valid=False)]).result(timeout=1.0) == [
        True,
        False,
    ]
    started = BatchVerifier(backend).start()
    started.stop()
    assert started.submit([StubSet()]).result(timeout=1.0) == [True]


def test_kick_flushes_a_partial_batch_before_its_deadline():
    backend = GatedBackend()
    svc = BatchVerifier(backend, s_bucket=128, max_wait=30.0).start()
    try:
        svc.submit([StubSet()])  # idle -> dispatched, gate holds it
        deadline = time.monotonic() + 5.0
        while len(backend.calls) < 1 and time.monotonic() < deadline:
            time.sleep(0.001)
        f2 = svc.submit([StubSet()])  # device busy + 30 s deadline: parked
        time.sleep(0.05)
        assert len(backend.calls) == 1
        svc.kick()  # the BeaconProcessor's end-of-drain device-idle hint
        deadline = time.monotonic() + 5.0
        while len(backend.calls) < 2 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert len(backend.calls) == 2
        backend.gate.set()
        assert f2.result(timeout=10.0) == [True]
    finally:
        svc.stop()


def test_kick_with_nothing_pending_does_not_flush_later_submissions():
    """The end-of-drain kick is a flush of what is pending at that moment;
    a kick that found nothing must not make the next set dispatch alone
    while the device is busy."""
    backend = GatedBackend()
    svc = BatchVerifier(backend, s_bucket=128, max_wait=30.0).start()
    try:
        svc.submit([StubSet()])  # idle -> dispatched, gate holds it
        deadline = time.monotonic() + 5.0
        while len(backend.calls) < 1 and time.monotonic() < deadline:
            time.sleep(0.001)
        svc.kick()  # nothing pending
        time.sleep(0.05)
        f2 = svc.submit([StubSet()])  # device busy + 30 s deadline: parked
        time.sleep(0.05)
        assert backend.calls == [1]
        svc.kick()
        deadline = time.monotonic() + 5.0
        while len(backend.calls) < 2 and time.monotonic() < deadline:
            time.sleep(0.001)
        assert backend.calls == [1, 1]
        backend.gate.set()
        assert f2.result(timeout=10.0) == [True]
    finally:
        svc.stop()


def test_processor_drain_kicks_the_coalescer():
    from lighthouse_tpu.scheduler import BeaconProcessor

    class KickSpy:
        def __init__(self):
            self.kicks = 0

        def kick(self):
            self.kicks += 1

    spy = KickSpy()
    p = BeaconProcessor(coalescer=spy)
    p.drain({})
    assert spy.kicks == 1


def test_verify_sets_routes_through_the_installed_service():
    """The routing helper uses the process-wide service only for ITS
    backend module; other backends keep the direct path."""
    from lighthouse_tpu.crypto import bls

    fake = bls.backend("fake")
    svc = ensure_running(fake, max_wait=0.005)
    try:
        assert active_for(fake) is svc
        assert active_for(object()) is None
        sk, pk = fake.interop_keypair(0)
        msg = b"\x11" * 32
        good = fake.SignatureSet(signature=sk.sign(msg), signing_keys=[pk], message=msg)
        # structurally invalid (empty keys): the fake backend fails the
        # whole batch; bisection must blame only the offender
        bad = fake.SignatureSet(signature=sk.sign(msg), signing_keys=[], message=msg)
        d0 = svc.dispatches
        assert verify_sets(fake, [good, bad, good]) == [True, False, True]
        assert svc.dispatches > d0  # it DID go through the service
    finally:
        release(svc)
    assert active_for(fake) is None
    # with the service released, verify_sets falls back to the direct path
    assert verify_sets(fake, [good, bad, good]) == [True, False, True]


def test_gossip_attestations_verify_through_coalescer():
    """Integration: the chain's gossip attestation path yields identical
    verdicts with the coalescer installed, dispatching through it."""
    from lighthouse_tpu.chain import BeaconChainHarness
    from lighthouse_tpu.chain.attestation_processing import (
        batch_verify_gossip_attestations,
    )
    from lighthouse_tpu.state_transition import TransitionContext

    h = BeaconChainHarness(16, TransitionContext.minimal("fake"))
    h.extend_chain(2)
    head = h.chain.head_root
    state = h.chain.store.get_state(head)
    atts = h.attestations_for_slot(state, head, int(state.slot))
    svc = ensure_running(h.ctx.bls, max_wait=0.005)
    try:
        d0 = svc.dispatches
        results = batch_verify_gossip_attestations(h.chain, atts)
        assert all(r is True for r in results)
        assert svc.dispatches > d0
    finally:
        release(svc)


def test_verdict_parity_with_direct_verify_oracle():
    """rng-seeded parity on REAL crypto (the pure-Python oracle, whose
    per-verify cost is sub-second at these sizes): per-set verdicts from
    the coalescer — including bisection blame — equal direct single-set
    `verify_signature_sets` verdicts for a mixed batch (an honest set, a
    tampered message, a wrong key)."""
    import random

    from lighthouse_tpu.crypto import bls

    r = bls.backend("ref")
    sks, pks = zip(*(r.interop_keypair(i) for i in range(2)))
    msg = b"\xab" * 32
    sets = [
        r.SignatureSet(signature=sks[0].sign(msg), signing_keys=[pks[0]], message=msg),
        # tampered message
        r.SignatureSet(
            signature=sks[1].sign(msg), signing_keys=[pks[1]], message=b"\x00" * 32
        ),
        # wrong key
        r.SignatureSet(signature=sks[1].sign(msg), signing_keys=[pks[0]], message=msg),
    ]
    direct = [r.verify_signature_sets([s]) for s in sets]
    rng = random.Random(0xC0A1E5CE)
    svc = BatchVerifier(r, max_wait=0.005, rng=rng.getrandbits).start()
    try:
        assert svc.submit(sets).result(timeout=120.0) == direct == [True, False, False]
    finally:
        svc.stop()


def test_jax_entry_points_route_through_installed_service(monkeypatch):
    """Signature.verify / fast_aggregate_verify consult the process-wide
    service installed for the jax backend module (device work stubbed out:
    the dispatch itself is covered by the slow-tier parity test)."""
    from lighthouse_tpu.crypto.bls.jax_backend import api as japi

    calls = []

    def fake_verify(sets, rng=None):
        calls.append(len(sets))
        return True

    monkeypatch.setattr(japi, "verify_signature_sets", fake_verify)
    monkeypatch.setattr(japi, "verify_signature_sets_async", None)
    sk, pk = japi.interop_keypair(0)
    msg = b"\x2f" * 32
    sig = sk.sign(msg)
    svc = ensure_running(japi, max_wait=0.005)
    try:
        d0 = svc.dispatches
        assert sig.verify(pk, msg)
        assert sig.fast_aggregate_verify([pk], msg)
        assert svc.dispatches - d0 == 2  # both rode the coalescer
        assert calls == [1, 1]
    finally:
        release(svc)
    calls.clear()
    assert sig.verify(pk, msg)  # service released: direct path again
    assert calls == [1]


@pytest.mark.slow
def test_verdict_parity_with_direct_verify_jax():
    """rng-seeded parity on the accelerated backend (nightly tier: the
    fused verify kernel compiles in-process): coalesced verdicts with
    bisection equal direct single-set verdicts for a mixed batch."""
    import random

    from lighthouse_tpu.crypto import bls

    b = bls.backend("jax")
    sks, pks = zip(*(b.interop_keypair(i) for i in range(2)))
    msg = b"\xab" * 32
    sets = [
        b.SignatureSet(signature=sks[0].sign(msg), signing_keys=[pks[0]], message=msg),
        # tampered message
        b.SignatureSet(
            signature=sks[1].sign(msg), signing_keys=[pks[1]], message=b"\x00" * 32
        ),
    ]
    direct = [b.verify_signature_sets([s]) for s in sets]
    rng = random.Random(0xC0A1E5CE)
    svc = BatchVerifier(b, max_wait=0.005, rng=rng.getrandbits).start()
    try:
        assert svc.submit(sets).result(timeout=600.0) == direct == [True, False]
    finally:
        svc.stop()


@pytest.mark.slow
def test_jax_single_set_entry_points_route_through_coalescer():
    """Signature.verify / fast_aggregate_verify ride the shared batch when
    the service is installed for the jax backend, with unchanged verdicts."""
    from lighthouse_tpu.crypto import bls

    b = bls.backend("jax")
    sk, pk = b.interop_keypair(0)
    msg = b"\x3c" * 32
    sig = sk.sign(msg)
    svc = ensure_running(b, max_wait=0.005)
    try:
        d0 = svc.dispatches
        assert sig.verify(pk, msg)
        assert not sig.verify(pk, b"\x00" * 32)
        assert sig.fast_aggregate_verify([pk], msg)
        assert svc.dispatches - d0 == 3
    finally:
        release(svc)
