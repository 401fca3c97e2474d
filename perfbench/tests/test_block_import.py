"""The block-import cell on the CPU: its driver over the plain backend at a
tiny size and at full shape, a broken timed path and a control, and the
readers of the key-slot and set-row shares."""

import contextlib
import copy
import time
from types import SimpleNamespace as NS

import pytest

from lighthouse_tpu.common import metrics
from lighthouse_tpu.common.metrics import CounterVec
from perfbench import generate, harness
from perfbench.tests.tiny import BENCH, broken, ref_backend, run_tiny

SEED = 2**32 + 2525  # wider than 32 signed bits, as the driver's are


def tiny_block_import():
    """block-import as BENCHMARK.json defines it, with its own traffic file
    shrunk as tiny.py shrinks a cell: one 16-member committee a slot, two
    blocks of the two single-key sets, 6 aggregates and an 8-key sync
    aggregate, every 2nd block tampered."""
    cell = harness.load_cell(BENCH, "block-import")
    cell.config = {**cell.config, "active_validators": 512, "materialized_validators": 32}
    t = cell.traffic = copy.deepcopy(cell.traffic)
    t.update(check_sets=4, slots=2, warmup_requests=1, tamper={**t["tamper"], "every": 2, "at": 0})
    for g in t["groups"]:
        if g["kind"] == "committee":
            g["count"] = 6
        if g["kind"] == "sample":
            g["keys"] = 8
    return cell


def test_the_cell_sends_altair_blocks_with_sets_as_units():
    cell = harness.load_cell(BENCH, "block-import")
    t = cell.traffic
    assert (t["driver"], t["unit"], t["request_per"], t["slots"]) == ("block_import", "sets", "slot", 8)
    assert t["groups"] == [
        {"kind": "single", "count": 2},
        {"kind": "committee", "count": "MAX_ATTESTATIONS", "participation": 0.97},
        {"kind": "sample", "keys": "SYNC_COMMITTEE_SIZE", "participation": 0.97}]
    assert t["tamper"] == {"every": 8, "at": 5, "group": "committee"}
    assert (t["warmup_requests"], t["trace_seconds"], t["check_sets"]) == (2, 3, 12)
    assert [m["name"] for m in cell.end_to_end] == ["sets_per_s", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "keys_stage_ms.block", "device_ms_per_batch.block", "device_idle_share.block",
        "stage_ms_per_batch.block", "key_slot_share.block", "program_lower_s.block", "program_compile_s.block", "message_row_share.block",
        "staged_row_share.block"}


def test_full_shape_units_are_the_sets_of_completed_blocks():
    """At full shape, with each verdict taken from the generator's labels:
    131 distinct messages a block, one (256, 512) bucket, and a window's
    units 131 for each block completed in it."""
    cell = harness.load_cell(BENCH, "block-import")
    stream = generate.generate(cell.config, cell.traffic, SEED)
    driver = harness.load_driver(cell.traffic["driver"])
    assert driver.PER_SET is False
    assert driver.buckets(stream) == [(256, 512)]
    assert all(len({s.msg for s in req}) == 131 for req in stream.base)

    def verify(sets):
        time.sleep(0.002)
        return all(s.valid for s in sets)

    edges = []
    ctx = NS(bls=NS(verify_signature_sets=verify), traffic=cell.traffic, seconds=0.05,
             answer_timeout=1.0, make_sets=stream.request,
             annotate=lambda name: contextlib.nullcontext(),
             window_opens=lambda: edges.append("open"), window_closes=lambda: edges.append("close"))
    result = driver.drive(ctx)
    blocks = len(result["notes"]["request_ms"])
    assert edges == ["open", "close"]
    assert blocks >= 2 and result["submitted"] == blocks + cell.traffic["warmup_requests"]
    assert result["units"] == 131 * blocks
    assert result["t_end"] - result["t0"] >= 0.05
    assert result["answers"] == {i: all(stream.expected(i)) for i in range(result["submitted"])}
    assert result["answers"][5] is False  # every 8th block, at position 5, is tampered
    assert {len(v) for v in result["notes"].values()} == {blocks}  # one log entry a call
    assert min(result["notes"]["request_ms"]) >= 2.0


def test_tiny_run_is_correct():
    line = run_tiny("block-import", cell=tiny_block_import(), seed=SEED)
    assert line["correct"], line
    assert set(line["metrics"]) == {"sets_per_s", "setup_s"}
    assert line["metrics"]["sets_per_s"]["unit"] == "sets/s"


@pytest.mark.parametrize("fault", ["flip", "half"])
def test_a_broken_timed_path_is_not_correct(fault):
    """Every block tampered, the pair in alternate halves: the window's
    first block (request 1) has it in the second half, however few blocks
    a slow host completes in the window."""
    cell = tiny_block_import()
    cell.traffic["tamper"] = {**cell.traffic["tamper"], "every": 1, "at": 0}
    line = run_tiny("block-import", cell=cell, seed=SEED, bls=broken(ref_backend(), fault))
    assert not line["correct"]
    assert line["checks"]["wrong"]["value"] > 0


def test_the_unit_scalars_control_is_not_correct():
    line = run_tiny("block-import", cell=tiny_block_import(), seed=SEED, control="unit_scalars")
    assert not line["correct"]
    assert line["checks"]["wrong"]["value"] > 0


def test_each_call_logs_the_program_spans_inside_it():
    """The per-call log takes each call's staging, dispatch, device-wait and
    verdict-copy seconds from the program's spans."""
    from lighthouse_tpu.common.tracing import span

    cell = tiny_block_import()
    stream = generate.generate(cell.config, cell.traffic, SEED)
    waits = iter([0.03, 0.002] * 50)

    def verify(sets):
        with span("bls_batch_verify"):
            with span("bls_stage"):
                time.sleep(0.001)
            with span("bls_device_execute"):
                time.sleep(next(waits))
                with span("bls_fetch"):
                    pass
        return True

    ctx = NS(bls=NS(verify_signature_sets=verify), traffic=cell.traffic, seconds=0.1,
             answer_timeout=1.0, make_sets=stream.request,
             annotate=lambda name: contextlib.nullcontext(),
             window_opens=lambda: None, window_closes=lambda: None)
    notes = harness.load_driver("block_import").drive(ctx)["notes"]
    long = [w > 25.0 for w in notes["wait_ms"]]  # the calls alternate long and short waits
    assert len(long) >= 3 and all(a != b for a, b in zip(long, long[1:]))
    assert all(r >= w for r, w in zip(notes["request_ms"], notes["wait_ms"]))
    assert set(notes) == {"request_ms", "stage_ms", "dispatch_ms", "wait_ms", "fetch_ms"}
    assert all(s >= 1.0 for s in notes["stage_ms"])
    assert notes["dispatch_ms"] == [0.0] * len(long)
    assert all(0.0 <= f < 5.0 for f in notes["fetch_ms"])


def test_key_slot_share_reads_key_slots_over_all_slots(monkeypatch):
    slots = CounterVec("ks_slots_total", "", ("slot",))
    monkeypatch.setattr(metrics, "BLS_STAGED_KEY_SLOTS_TOTAL", slots)
    read = harness.load_reader("key_slot_share.block").read
    assert read(NS()) is None  # nothing staged
    slots.labels(slot="key").inc(18_690)
    slots.labels(slot="pad").inc(256 * 512 - 18_690)
    assert read(NS()) == pytest.approx(18_690 / 131_072)
    monkeypatch.delattr(metrics, "BLS_STAGED_KEY_SLOTS_TOTAL")
    assert read(NS()) is None  # a program without the counter


def test_staged_row_share_reads_set_rows_over_all_rows(monkeypatch):
    rows = CounterVec("sr_rows_total", "", ("row",))
    monkeypatch.setattr(metrics, "BLS_STAGED_ROWS_TOTAL", rows)
    read = harness.load_reader("staged_row_share.block").read
    assert read(NS()) is None  # nothing staged
    rows.labels(row="set").inc(3 * 131)
    rows.labels(row="pad").inc(3 * (256 - 131))
    assert read(NS()) == pytest.approx(131 / 256)
    monkeypatch.delattr(metrics, "BLS_STAGED_ROWS_TOTAL")
    assert read(NS()) is None  # a program without the counter
