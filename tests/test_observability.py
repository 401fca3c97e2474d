"""The observability layer: labeled metric families, tracing spans, the
ValidatorMonitor's epoch attribution, the monitor HTTP surface, the VC
metrics server, and the lockfile/finalized-root fixes that ride along.
"""

import json
import urllib.request

import pytest

from lighthouse_tpu.common.metrics import (
    HistogramVec,
    REGISTRY,
    Registry,
)
from lighthouse_tpu.common.tracing import TRACER, Tracer, span


def _get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}") as r:
        body = r.read()
        if r.headers.get("Content-Type", "").startswith("application/json"):
            return r.status, json.loads(body)
        return r.status, body.decode()


# -- labeled families ----------------------------------------------------------


def test_counter_vec_labels_cached_and_escaped():
    r = Registry()
    c = r.counter_vec("c_total", "a labeled counter", ("op", "ok"))
    child = c.labels(op="read", ok=True)
    child.inc(2)
    assert c.labels(op="read", ok=True) is child  # cached per label set
    c.labels(op='we"ird\\v\nal', ok=False).inc()
    text = r.gather()
    assert '# TYPE c_total counter' in text
    assert 'c_total{op="read",ok="True"} 2.0' in text
    # backslash, quote, and newline are escaped per the text format
    assert 'c_total{op="we\\"ird\\\\v\\nal",ok="False"} 1.0' in text
    with pytest.raises(ValueError):
        c.labels(op="read")  # missing label name
    with pytest.raises(ValueError):
        c.labels(op="read", ok=True, extra=1)


def test_histogram_vec_le_buckets_cumulative_per_child():
    r = Registry()
    h = r.histogram_vec("h_seconds", "latency", ("stage",), buckets=(0.1, 1.0))
    h.labels(stage="pack").observe(0.05)
    h.labels(stage="pack").observe(0.5)
    h.labels(stage="pack").observe(5.0)
    h.labels(stage="h2c").observe(0.2)
    text = r.gather()
    # each child carries its OWN cumulative le series
    assert 'h_seconds_bucket{stage="pack",le="0.1"} 1' in text
    assert 'h_seconds_bucket{stage="pack",le="1.0"} 2' in text
    assert 'h_seconds_bucket{stage="pack",le="+Inf"} 3' in text
    assert 'h_seconds_count{stage="pack"} 3' in text
    assert 'h_seconds_bucket{stage="h2c",le="0.1"} 0' in text
    assert 'h_seconds_bucket{stage="h2c",le="+Inf"} 1' in text
    # ONE family header, not one per child
    assert text.count("# TYPE h_seconds histogram") == 1


def test_duplicate_registration_type_conflicts():
    r = Registry()
    r.counter("a_total")
    with pytest.raises(ValueError):
        r.counter_vec("a_total", label_names=("x",))  # scalar vs vec
    v = r.gauge_vec("g", label_names=("x",))
    with pytest.raises(ValueError):
        r.gauge("g")  # vec vs scalar
    with pytest.raises(ValueError):
        r.histogram_vec("g", label_names=("x",))  # vec vs other-vec
    with pytest.raises(ValueError):
        r.gauge_vec("g", label_names=("y",))  # same vec, different labels
    assert r.gauge_vec("g", label_names=("x",)) is v  # idempotent


# -- tracing -------------------------------------------------------------------


def _totals(stages) -> dict[str, float]:
    return {k[0]: v.sum for k, v in stages.children().items()}


def test_span_tree_nesting_and_stage_histogram():
    """Nesting shows in the self times: a parent's self time is its
    duration less its children's, never their sum."""
    import time as _t

    stages = HistogramVec("t_seconds", "", ("stage",))
    tr = Tracer(stage_histogram=stages)
    with tr.span("root"):
        with tr.span("child_a"):
            _t.sleep(0.002)
        with tr.span("child_b"):
            with tr.span("grandchild"):
                _t.sleep(0.005)
    # every span fed the per-stage histogram once
    by_stage = {k[0]: v.count for k, v in stages.children().items()}
    assert by_stage == {"root": 1, "child_a": 1, "child_b": 1, "grandchild": 1}
    total, own = _totals(stages), tr.self_time_report()
    assert own["root"] == pytest.approx(total["root"] - total["child_a"] - total["child_b"])
    assert own["child_b"] == pytest.approx(total["child_b"] - total["grandchild"])
    assert own["child_a"] == pytest.approx(total["child_a"])
    assert own["grandchild"] == pytest.approx(total["grandchild"]) and own["grandchild"] >= 0.005
    assert own["root"] < 0.005 <= total["root"]
    assert sum(own.values()) == pytest.approx(total["root"])


def test_tracer_keeps_slowest_roots_and_survives_exceptions():
    """A span that raises still closes and records; the stack unwinds, so
    the next span is a root whose self time is its whole duration."""
    import time as _t

    stages = HistogramVec("t_seconds", "", ("stage",))
    tr = Tracer(stage_histogram=stages)
    for i, sleep in enumerate((0.0, 0.02, 0.001)):
        with pytest.raises(RuntimeError):
            with tr.span(f"r{i}"):
                with tr.span(f"inner{i}"):
                    _t.sleep(sleep)
                    raise RuntimeError("boom")
    total, own = _totals(stages), tr.self_time_report()
    assert total["inner1"] >= 0.02 and total["r1"] >= total["inner1"]
    for i in range(3):
        assert own[f"r{i}"] == pytest.approx(total[f"r{i}"] - total[f"inner{i}"])
    assert tr._stack() == []
    with tr.span("fresh"):
        _t.sleep(0.001)
    assert {k[0] for k in stages.children()} >= {"r0", "r1", "r2", "fresh"}
    total, own = _totals(stages), tr.self_time_report()
    assert own["fresh"] == pytest.approx(total["fresh"])  # not a child of a dead span
    assert own["r2"] == pytest.approx(total["r2"] - total["inner2"])  # r2 took nothing of it


# -- processor queue-wait / handle metrics -------------------------------------


def test_processor_queue_wait_and_handle_metrics():
    from lighthouse_tpu.common.metrics import (
        PROCESSOR_HANDLE_SECONDS,
        PROCESSOR_QUEUE_WAIT_SECONDS,
    )
    from lighthouse_tpu.scheduler import BeaconProcessor, WorkType

    wait_att = PROCESSOR_QUEUE_WAIT_SECONDS.labels(kind="gossip_attestation")
    wait_blk = PROCESSOR_QUEUE_WAIT_SECONDS.labels(kind="gossip_block")
    handle_att = PROCESSOR_HANDLE_SECONDS.labels(kind="gossip_attestation")
    w0, b0, h0 = wait_att.count, wait_blk.count, handle_att.count

    p = BeaconProcessor()
    for i in range(5):
        p.submit(WorkType.GOSSIP_ATTESTATION, i)
    p.submit(WorkType.GOSSIP_BLOCK, "blk")
    seen = []
    p.drain(
        {
            WorkType.GOSSIP_ATTESTATION: seen.extend,
            WorkType.GOSSIP_BLOCK: seen.extend,
        }
    )
    assert len(seen) == 6  # handlers still receive raw items
    assert wait_att.count == w0 + 5  # one wait sample per drained item
    assert wait_blk.count == b0 + 1
    assert handle_att.count == h0 + 1  # one handle sample per batch


# -- BLS host-pipeline stages (device kernels are slow-marked below) -----------


def test_bls_staging_emits_pack_and_h2c_stages_and_padded_size():
    from lighthouse_tpu.common.metrics import BLS_BATCH_PADDED_SIZE
    from lighthouse_tpu.common.tracing import STAGE_SECONDS
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls.jax_backend import api as japi

    pack_h = STAGE_SECONDS.labels(stage="bls_pack")
    h2c_h = STAGE_SECONDS.labels(stage="bls_h2c_host")
    p0, h0, s0 = pack_h.count, h2c_h.count, BLS_BATCH_PADDED_SIZE.count

    b = bls.backend("jax")
    sk, pk = b.interop_keypair(0)
    msg = b"\x01" * 32
    sets = [japi.SignatureSet(signature=sk.sign(msg), signing_keys=[pk], message=msg)]
    staged = japi.stage_sets(sets * 3)
    assert staged[2].shape == (4, 4)  # 3 sets pad to the (S=4, K=4) bucket
    assert pack_h.count == p0 + 1 and h2c_h.count == h0 + 1
    assert BLS_BATCH_PADDED_SIZE.count == s0 + 1


@pytest.mark.slow
def test_bls_device_verify_emits_execute_span_and_jit_counter():
    from lighthouse_tpu.common.metrics import BLS_JIT_BUILDS_TOTAL
    from lighthouse_tpu.common.tracing import STAGE_SECONDS
    from lighthouse_tpu.crypto import bls

    b = bls.backend("jax")
    sk, pk = b.interop_keypair(0)
    msg = b"\x02" * 32
    exec_h = STAGE_SECONDS.labels(stage="bls_device_execute")
    root_h = STAGE_SECONDS.labels(stage="bls_batch_verify")
    e0, r0 = exec_h.count, root_h.count
    assert b.verify_signature_sets(
        [b.SignatureSet(signature=sk.sign(msg), signing_keys=[pk], message=msg)]
    )
    assert exec_h.count == e0 + 1 and root_h.count == r0 + 1
    assert BLS_JIT_BUILDS_TOTAL.labels(kernel="verify").value >= 1


# -- validator monitor: chain-driven attribution + HTTP surfaces ---------------


@pytest.fixture(scope="module")
def monitored_chain():
    """A 16-validator chain driven past an epoch boundary with full
    attestation participation, its monitor logging into a capture."""
    from lighthouse_tpu.chain import BeaconChainHarness
    from lighthouse_tpu.common.logging import test_logger
    from lighthouse_tpu.http_api import HttpApiServer
    from lighthouse_tpu.state_transition import TransitionContext
    from lighthouse_tpu.validator_client import BeaconNodeApi

    ctx = TransitionContext.minimal("fake")
    h = BeaconChainHarness(16, ctx)
    log, records = test_logger()
    h.chain.validator_monitor.log = log
    for i in range(16):
        assert h.chain.validator_monitor.register(i)
    # 25 slots (minimal: 8/epoch): summaries lag ONE epoch behind the head
    # (late-but-legal inclusions through the end of e+1 must not read as
    # misses), so entering epoch 2 (slot-16 block) summarizes epoch 0 and
    # entering epoch 3 (slot-24 block) summarizes epoch 1. Epoch 1 is FULLY
    # attested (every slot has a block and the next block packs its
    # attestations); epoch 0's slot-0 committee never got to attest (the
    # chain starts at slot 1), so its members are misses.
    h.extend_chain(25)
    srv = HttpApiServer(BeaconNodeApi(h.chain)).start()
    yield h, records, srv
    srv.stop()


def test_monitor_epoch_summary_in_log_capture(monitored_chain):
    h, records, _ = monitored_chain
    summaries = [r for r in records if "validator epoch summary" in r]
    assert len(summaries) == 32  # one line per monitored validator per epoch
    epoch1 = [r for r in summaries if "epoch=1 " in r]
    assert len(epoch1) == 16
    for line in epoch1:
        assert "attestation_hit=True" in line
        assert "inclusion_delay=1" in line  # packed in the very next block
        assert "head_hit=True" in line and "target_hit=True" in line
    assert any("proposals=1" in line for line in epoch1)
    # epoch 0: the slot-0 committee never attested — real misses are
    # reported, not papered over
    epoch0 = [r for r in summaries if "epoch=0 " in r]
    assert any("attestation_hit=False" in line for line in epoch0)
    assert sum("attestation_hit=True" in line for line in epoch0) >= 10


def test_monitor_ui_validator_metrics_route(monitored_chain):
    h, _, srv = monitored_chain
    status, resp = _get(srv.port, "/lighthouse/ui/validator_metrics")
    assert status == 200
    validators = resp["data"]["validators"]
    assert len(validators) == 16
    for v in validators.values():
        assert v["attestation_hits"] >= 1  # epoch 1 was fully attested
        assert v["attestation_misses"] <= 1  # at worst the epoch-0 slot-0 miss
        assert v["average_inclusion_delay"] == 1.0
        assert v["head_hits"] >= 1 and v["target_hits"] >= 1
    assert sum(v["attestation_misses"] for v in validators.values()) >= 1
    assert sum(v["blocks_proposed"] for v in validators.values()) == 25


def test_monitor_labeled_metrics_and_stage_histograms_on_scrape(monitored_chain):
    """The acceptance surface: the BN /metrics scrape carries labeled
    per-stage histograms for block import, processor queue-wait, and the
    BLS pipeline, plus the monitor's per-validator families."""
    _, _, srv = monitored_chain
    status, text = _get(srv.port, "/metrics")
    assert status == 200
    # block-import pipeline stages (spans from process_block/_post_import)
    for stage in ("block_import", "state_transition", "fork_choice", "store_write"):
        assert f'lighthouse_tpu_stage_seconds_bucket{{stage="{stage}"' in text, stage
    # processor queue-wait/handle (driven by the processor test above; same
    # process registry — drive it here too so this test stands alone)
    from lighthouse_tpu.scheduler import BeaconProcessor, WorkType

    p = BeaconProcessor()
    p.submit(WorkType.GOSSIP_BLOCK, "x")
    p.drain({WorkType.GOSSIP_BLOCK: lambda items: None})
    _, text = _get(srv.port, "/metrics")
    assert 'lighthouse_tpu_processor_queue_wait_seconds_bucket{kind="gossip_block"' in text
    assert 'lighthouse_tpu_processor_handle_seconds_bucket{kind="gossip_block"' in text
    # BLS pipeline stages (host half; device half is the slow-marked test)
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls.jax_backend import api as japi

    b = bls.backend("jax")
    sk, pk = b.interop_keypair(0)
    japi.stage_sets(
        [japi.SignatureSet(signature=sk.sign(b"m" * 32), signing_keys=[pk], message=b"m" * 32)]
    )
    _, text = _get(srv.port, "/metrics")
    assert 'lighthouse_tpu_stage_seconds_bucket{stage="bls_pack"' in text
    assert 'lighthouse_tpu_stage_seconds_bucket{stage="bls_h2c_host"' in text
    assert "lighthouse_tpu_bls_batch_padded_size_bucket" in text
    # monitor families, labeled per validator
    assert 'lighthouse_tpu_validator_monitor_attestation_hits_total{validator="0"}' in text
    assert 'lighthouse_tpu_validator_monitor_inclusion_delay_slots_count{validator="0"}' in text
    assert 'lighthouse_tpu_validator_monitor_proposals_total{validator=' in text


def test_monitor_registration_cap():
    from lighthouse_tpu.chain.validator_monitor import (
        MAX_MONITORED_VALIDATORS,
        ValidatorMonitor,
    )

    m = ValidatorMonitor(slots_per_epoch=8)
    for i in range(MAX_MONITORED_VALIDATORS):
        assert m.register(i)
    assert not m.register(MAX_MONITORED_VALIDATORS)  # refused past the cap
    assert m.register(0)  # re-registering a monitored index stays fine
    assert len(m.monitored) == MAX_MONITORED_VALIDATORS


def test_monitor_counts_misses():
    from lighthouse_tpu.common.logging import test_logger
    from lighthouse_tpu.chain.validator_monitor import ValidatorMonitor

    log, records = test_logger()
    m = ValidatorMonitor(slots_per_epoch=8, log=log)
    m.register(3)
    m.note_slot(1)  # first observation baselines the monitor at epoch 0
    m.on_attestation_included(3, 2, inclusion_delay=1, head_hit=True, target_hit=True)
    m.note_slot(17)  # epoch 2: summaries lag one epoch — only epoch 0 (hit)
    assert m.ui_payload()["validators"]["3"]["attestation_hits"] == 1
    assert m.ui_payload()["validators"]["3"]["attestation_misses"] == 0
    m.note_slot(25)  # epoch 3: epoch 1 (miss) now summarizes
    assert m.ui_payload()["validators"]["3"]["attestation_misses"] == 1
    assert any("attestation_hit=False" in r for r in records)


def test_monitor_late_inclusion_is_not_a_miss():
    """An attestation for the last slot of epoch e included early in epoch
    e+1 (legal: process_attestation's window runs to slot+slots_per_epoch)
    must count as a hit — summaries lag one epoch for exactly this."""
    from lighthouse_tpu.chain.validator_monitor import ValidatorMonitor

    m = ValidatorMonitor(slots_per_epoch=8)
    m.register(5)
    m.note_slot(1)
    m.note_slot(8)  # entered epoch 1: epoch 0 must NOT summarize yet
    m.on_attestation_included(5, 7, inclusion_delay=2, head_hit=True, target_hit=True)
    m.note_slot(16)  # entered epoch 2: epoch 0 summarizes WITH the late hit
    v = m.ui_payload()["validators"]["5"]
    assert v["attestation_hits"] == 1 and v["attestation_misses"] == 0


def test_monitor_baselines_at_first_observed_epoch():
    """A chain first observed mid-history (checkpoint start) must not charge
    every validator a burst of misses for epochs before monitoring began."""
    from lighthouse_tpu.chain.validator_monitor import ValidatorMonitor

    m = ValidatorMonitor(slots_per_epoch=8)
    m.register(1)
    m.note_slot(80)  # first observation at epoch 10
    m.note_slot(96)  # epoch 12: only epoch 10 summarizes
    v = m.ui_payload()["validators"]["1"]
    assert v["attestation_hits"] + v["attestation_misses"] == 1


def test_monitor_mid_run_registration_not_charged_past_misses():
    """A validator registered while the chain is running must not accrue
    misses for epochs before its registration — those epochs are
    unknowable, not failures."""
    from lighthouse_tpu.chain.validator_monitor import ValidatorMonitor

    m = ValidatorMonitor(slots_per_epoch=8)
    m.register(1)  # monitored from the start
    m.note_slot(1)
    m.note_slot(80)  # epoch 10: epochs 0-8 summarize (v1 charged misses)
    m.register(7)  # registered mid-run, partway through epoch 10
    m.note_slot(96)  # epoch 12: epochs 9-10 summarize
    v7 = m.ui_payload()["validators"]["7"]
    # neither epoch 9 (before registration) nor epoch 10 (only partially
    # observed) may charge the newcomer
    assert v7["attestation_hits"] + v7["attestation_misses"] == 0
    m.note_slot(104)  # epoch 13: epoch 11, v7's first FULL epoch, summarizes
    v1 = m.ui_payload()["validators"]["1"]
    v7 = m.ui_payload()["validators"]["7"]
    assert v1["attestation_misses"] == 12  # epochs 0-11, all unattested
    assert v7["attestation_hits"] + v7["attestation_misses"] == 1  # epoch 11 only


# -- VC metrics server ---------------------------------------------------------


def test_vc_metrics_server_serves_metrics_and_health():
    from lighthouse_tpu.chain import BeaconChainHarness
    from lighthouse_tpu.state_transition import TransitionContext
    from lighthouse_tpu.validator_client import (
        BeaconNodeApi,
        MetricsServer,
        ValidatorClient,
        ValidatorStore,
    )

    ctx = TransitionContext.minimal("fake")
    h = BeaconChainHarness(8, ctx)
    store = ValidatorStore(ctx)
    for i in range(8):
        store.add_validator(ctx.bls.interop_keypair(i)[0])
    vc = ValidatorClient(BeaconNodeApi(h.chain), store)
    srv = MetricsServer(vc=vc).start()
    try:
        h.chain.slot_clock.set_slot(1)
        vc.on_slot(1)
        status, text = _get(srv.port, "/metrics")
        assert status == 200
        assert 'lighthouse_tpu_vc_duties_total{duty="attested"}' in text
        status, health = _get(srv.port, "/health")
        assert status == 200
        assert health["status"] == "ok"
        assert health["keys"] == 8
        assert health["last_duty_slot"] == 1
        assert health["duties"]["attested"] > 0
        status, _ = _get(srv.port, "/metrics?x=1")  # query strings ignored
        assert status == 200
    finally:
        srv.stop()


# -- satellite fixes -----------------------------------------------------------


def test_finalized_block_id_resolves_to_genesis_before_finalization():
    from lighthouse_tpu.chain import BeaconChain
    from lighthouse_tpu.http_api import HttpApiServer
    from lighthouse_tpu.state_transition import TransitionContext, interop_genesis_state
    from lighthouse_tpu.validator_client import BeaconNodeApi

    ctx = TransitionContext.minimal("fake")
    chain = BeaconChain(interop_genesis_state(8, 1_600_000_000, ctx), ctx)
    srv = HttpApiServer(BeaconNodeApi(chain)).start()
    try:
        status, resp = _get(srv.port, "/eth/v1/beacon/headers/finalized")
        assert status == 200
        # pre-finalization the checkpoint root is zero: the API maps it to
        # genesis instead of serving the genesis header under 0x00…00
        assert resp["data"]["root"] == "0x" + chain.genesis_block_root.hex()
        status, resp = _get(srv.port, "/eth/v1/beacon/blocks/finalized/root")
        assert resp["data"]["root"] == "0x" + chain.genesis_block_root.hex()
    finally:
        srv.stop()


def test_lockfile_release_never_unlinks_and_relocks(tmp_path):
    from lighthouse_tpu.validator_client.lockfile import Lockfile, LockfileError

    path = tmp_path / "ks.json.lock"
    a = Lockfile(path).acquire()
    with pytest.raises(LockfileError):
        Lockfile(path).acquire()  # held: second holder refused
    a.release()
    assert path.exists()  # the path is NEVER unlinked (anti-slashing race)
    b = Lockfile(path).acquire()  # still lockable after release
    with pytest.raises(LockfileError):
        Lockfile(path).acquire()
    b.release()


# -- slot-SLO ledger + flight recorder + provenance (ISSUE 17) -----------------


def test_flight_recorder_ring_bounded_and_filterable():
    from lighthouse_tpu.common.flight_recorder import FlightRecorder

    rec = FlightRecorder(capacity=4, key_capacity=2)
    a = rec.mint("attestation", node="n0")
    b = rec.mint("aggregate")
    assert a == "attestation-000000" and b == "aggregate-000001"  # deterministic
    for i in range(4):
        rec.record(a, f"e{i}")
    # 6 events total through a 4-slot ring: the two oldest dropped, counted
    assert len(rec.events()) == 4
    assert rec.dropped == 2
    assert all(r["corr_id"] == a for r in rec.events(a))
    assert rec.events(b) == []  # b's "admitted" was evicted
    # key map bounded too: oldest binding evicts first
    rec.bind(b"k1", a)
    rec.bind(b"k2", b)
    rec.bind(b"k3", a)
    assert rec.lookup(b"k1") is None
    assert rec.lookup(b"k3") == a
    dump = rec.dump(a)
    assert dump["count"] == 4 and dump["dropped"] == 2


def test_slot_ledger_attribution_sums_to_wall_time():
    """The acceptance bar: per-stage attributions (including the residual)
    sum to within 5% of the slot's measured wall time."""
    import time as _t

    from lighthouse_tpu.common.slot_ledger import SlotLedger

    tr = Tracer(stage_histogram=HistogramVec("sl_seconds", "", ("stage",)))
    led = SlotLedger(seconds_per_slot=0.5, tracer=tr)
    led.on_slot(1)
    with tr.span("state_transition"):
        _t.sleep(0.02)
    with tr.span("gossip_attestation_verify"):
        _t.sleep(0.01)
    _t.sleep(0.01)  # un-spanned time -> the "unattributed" residual
    led.on_slot(2)
    led.on_slot(2)  # re-announcing the open slot is not a boundary
    [rec] = led.records()
    assert rec["slot"] == 1 and not rec["deadline_missed"]
    total = sum(rec["stages"].values())
    assert abs(total - rec["wall_seconds"]) <= 0.05 * rec["wall_seconds"]
    assert rec["stages"]["state_transition"] >= 0.02
    assert rec["stages"]["gossip_admission"] >= 0.01
    assert rec["stages"]["unattributed"] >= 0.009
    assert led.last_record()["slot"] == 1
    # the shape Tracer.stage_report() emits too
    report = led.stage_report()
    assert report["state_transition"]["count"] == 1
    assert report["state_transition"]["total_s"] >= 0.02


def test_deadline_miss_auto_dumps_correlated_path(tmp_path):
    """A missed deadline must produce exactly ONE dump file carrying the
    full correlated path of a signature set plus the missed slot record."""
    import os as _os

    from lighthouse_tpu.common.flight_recorder import FlightRecorder
    from lighthouse_tpu.common.slot_ledger import SlotLedger

    tr = Tracer(stage_histogram=HistogramVec("dm_seconds", "", ("stage",)))
    rec = FlightRecorder()
    cid = rec.mint("attestation", node="n0")
    rec.record(cid, "staged", sets=1)
    rec.record(cid, "batch_formed", batch_sets=1)
    rec.record(cid, "device_dispatch", batch_sets=1)
    rec.record(cid, "set_verdict", ok=True)
    rec.record(cid, "verdict", ok=True)

    led = SlotLedger(
        seconds_per_slot=0.0, recorder=rec, dump_dir=str(tmp_path), tracer=tr
    )
    led.on_slot(1)
    led.on_slot(2)  # closes slot 1: wall > 0 = budget -> miss
    files = sorted(_os.listdir(tmp_path))
    assert len(files) == 1  # exactly one dump per miss
    assert led.deadline_misses == 1
    with open(tmp_path / files[0]) as f:
        payload = json.load(f)
    assert payload["slot_record"]["slot"] == 1
    assert payload["slot_record"]["deadline_missed"]
    path = [
        e["event"]
        for e in payload["flight_recorder"]["events"]
        if e["corr_id"] == cid
    ]
    assert path == [
        "admitted", "staged", "batch_formed", "device_dispatch",
        "set_verdict", "verdict",
    ]
    assert led.last_record()["dump_path"] == str(tmp_path / files[0])
    led.on_slot(3)  # a second miss dumps a second file
    assert len(_os.listdir(tmp_path)) == 2
    assert led.deadline_misses == 2


def test_batch_verifier_correlates_dispatch_and_bisection_blame():
    """Correlation ids survive the coalescer: batch formation, device
    dispatch, bisection blame on the one bad set, per-set verdicts."""
    from lighthouse_tpu.common.flight_recorder import FlightRecorder
    from lighthouse_tpu.crypto.bls.batch_verifier import BatchVerifier

    class StubBackend:
        def verify_signature_sets(self, sets):
            return all(s == "good" for s in sets)

    rec = FlightRecorder()
    cids = [rec.mint("attestation") for _ in range(3)]
    svc = BatchVerifier(StubBackend(), max_wait=0.001).start()
    try:
        meta = [(rec, c) for c in cids]
        verdicts = svc.submit(["good", "bad", "good"], corr_meta=meta).result(
            timeout=10.0
        )
        # misaligned metadata is dropped, never misattributed
        assert svc.submit(["good"], corr_meta=meta).result(timeout=10.0) == [True]
    finally:
        svc.stop()
    assert verdicts == [True, False, True]
    bad_path = [e["event"] for e in rec.events(cids[1])]
    for hop in ("admitted", "batch_formed", "device_dispatch", "bisect_blame",
                "set_verdict"):
        assert hop in bad_path, hop
    for good in (cids[0], cids[2]):
        events = [e["event"] for e in rec.events(good)]
        assert "bisect_blame" not in events
        assert "set_verdict" in events
    # a verdict event carries the per-set outcome
    [v_bad] = [e for e in rec.events(cids[1]) if e["event"] == "set_verdict"]
    assert v_bad["ok"] is False


def test_sim_gossip_correlation_reaches_verdict():
    """End-to-end over the in-process testnet: an id minted at gossip
    admission shows up with staging and a final verdict on some node."""
    from lighthouse_tpu.sim import SimConfig, Simulation

    sim = Simulation(SimConfig(n_nodes=2, n_validators=8, net="local", seed=3))
    try:
        sim.run_slots(4)
    finally:
        sim.close()
    complete = []
    for node in sim.nodes:
        by_cid = {}
        for e in node.chain.flight_recorder.events():
            by_cid.setdefault(e["corr_id"], set()).add(e["event"])
        for cid, events in by_cid.items():
            if cid.startswith("attestation") and {
                "admitted", "staged", "verdict"
            } <= events:
                complete.append(cid)
    assert complete, "no attestation completed the admitted->staged->verdict path"


def test_device_provenance_fingerprint_matches_backend():
    import jax

    from lighthouse_tpu.common.metrics import DEVICE_PROVENANCE_INFO
    from lighthouse_tpu.crypto.bls.jax_backend import api as japi

    prov = japi.device_fingerprint()
    dev = jax.devices()[0]
    assert prov["platform"] == dev.platform
    assert prov["chip_count"] == len(jax.devices())
    assert prov["backend"] == jax.default_backend()
    assert set(prov["jit_cache"]) == {"verify_kernels_cached", "hits", "misses"}
    assert set(prov["coalescer"]) == {"running", "s_bucket", "max_wait"}
    child = DEVICE_PROVENANCE_INFO.labels(
        platform=prov["platform"],
        device_kind=prov["device_kind"],
        chip_count=str(prov["chip_count"]),
    )
    assert child.value == 1.0


def test_ui_slot_ledger_and_flight_recorder_routes(monitored_chain):
    h, _, srv = monitored_chain
    status, resp = _get(srv.port, "/lighthouse/ui/slot_ledger")
    assert status == 200
    ledger = resp["data"]
    assert ledger["seconds_per_slot"] == h.chain.slot_ledger.seconds_per_slot
    # extend_chain(25) ticked the slot clock through 24 boundaries
    assert len(ledger["slots"]) >= 20
    for rec in ledger["slots"]:
        assert set(rec["stages"]) >= {"state_transition", "unattributed"}
    cid = h.chain.flight_recorder.mint("test", node="ui-test")
    status, resp = _get(srv.port, "/lighthouse/ui/flight_recorder")
    assert status == 200
    assert cid in {e["corr_id"] for e in resp["data"]["events"]}
    status, resp = _get(srv.port, f"/lighthouse/ui/flight_recorder?corr_id={cid}")
    assert status == 200
    assert {e["corr_id"] for e in resp["data"]["events"]} == {cid}


def test_sim_event_log_reproducible_with_observability_excluded():
    """Wall clocks live only in the observability payload: two same-seed
    runs produce byte-identical event logs, and no t_wall leaks into one."""
    from lighthouse_tpu.sim import SimConfig, Simulation

    def run():
        sim = Simulation(SimConfig(n_nodes=2, n_validators=8, net="local", seed=11))
        try:
            sim.run_slots(6)
        finally:
            sim.close()
        return sim.event_log_json(), sim.observability()

    log1, obs1 = run()
    log2, _ = run()
    assert log1 == log2
    assert '"t_wall"' not in log1 and '"t_mono"' not in log1
    assert len(obs1) == 2
    for node_obs in obs1:
        assert node_obs["slot_ledger"]["slots"], node_obs["node"]
        assert any(
            "t_wall" in e for e in node_obs["flight_recorder"]["events"]
        ), node_obs["node"]


@pytest.mark.parametrize("script,why", [("chip_smoke.py", "needs a TPU")])
def test_chip_scripts_exit_nonzero_without_a_chip(script, why):
    """On a CPU-only host the chip's entry points exit nonzero and print
    nothing on stdout: no CPU fallback, no number under a device metric,
    no result line."""
    import os as _os
    import pathlib
    import subprocess
    import sys as _sys

    path = pathlib.Path(__file__).resolve().parent.parent / script
    env = dict(_os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [_sys.executable, str(path)],
        capture_output=True, text=True, timeout=240, env=env,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert why in proc.stderr


def test_lockfile_acquire_retries_replaced_inode(tmp_path, monkeypatch):
    """If the file at the path is replaced after flock, the lock sits on an
    orphaned inode and protects nothing — acquire must detect the swap and
    relock the LIVE file. Simulated by replacing the path right after the
    first flock succeeds."""
    import os

    from lighthouse_tpu.validator_client import lockfile as lf

    path = tmp_path / "ks.json.lock"
    new_path = tmp_path / "ks.json.lock.new"
    new_path.write_bytes(b"")
    real_flock = lf.fcntl.flock
    swapped = {"done": False}

    def swapping_flock(fd, op):
        real_flock(fd, op)
        if not swapped["done"]:
            swapped["done"] = True
            os.replace(new_path, path)  # yank the locked inode off the path

    monkeypatch.setattr(lf.fcntl, "flock", swapping_flock)
    lock = lf.Lockfile(path).acquire()
    # the held fd IS the file now at the path (the retry relocked it)
    st_fd = os.fstat(lock._fd)
    st_path = os.stat(path)
    assert (st_fd.st_ino, st_fd.st_dev) == (st_path.st_ino, st_path.st_dev)
    lock.release()
