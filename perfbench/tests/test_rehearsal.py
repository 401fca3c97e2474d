"""CPU rehearsal of the harness: generation, labels, discovery by name, and
whole runs of tiny cells with the timed path intact, broken, or replaced
by a control."""

import json
import shutil

import pytest

from perfbench import generate, harness, keys
from perfbench.ref import verify as refv
from perfbench.ref.curves import g1_generator
from perfbench.tests.tiny import BENCH, CELLS, broken, full_cell, run_tiny, tiny_cell

SEED = 2**31 + 4242  # seeds may be wider than 32 signed bits


def test_keys_are_the_reference_multiples():
    g = g1_generator()
    pks = keys.g1_chain(1, 40)
    for k in (1, 2, 3, 40):
        r = g.mul(k)
        assert pks[k - 1] == (r.x.n, r.y.n)
    _, pool = generate.load_pool()
    h = refv.g2_point(pool[3])
    sigs = keys.g2_chain(pool[3], 1, 5) + keys.g2_muls([(pool[3], 1234567)])
    for k, sig in [(1, sigs[0]), (2, sigs[1]), (5, sigs[4]), (1234567, sigs[5])]:
        r = h.mul(k)
        assert sig == (r.x.c0.n, r.x.c1.n, r.y.c0.n, r.y.c1.n)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_each_cell_generates_its_traffic_at_full_size(workload):
    cell = full_cell(workload)
    stream = generate.generate(cell.config, cell.traffic, SEED)
    again = generate.generate(cell.config, cell.traffic, SEED)
    assert [[s.sig for s in r] for r in stream.base[:4]] == [[s.sig for s in r] for r in again.base[:4]]
    if cell.traffic["driver"] == "coalescer":
        assert len(stream.base) == cell.traffic["slots"] * 9375
        assert all(len(r) == 1 and len(r[0].keys) == 1 for r in stream.base)
        first = stream.base[:64]
        assert len({r[0].msg for r in first}) > 32  # the slot's committees interleave
    else:
        sizes = [len(s.keys) for s in stream.base[0]]
        assert len(sizes) == 131 and sizes[:2] == [1, 1] and sizes[-1] == 497
        assert all(142 <= k <= 143 for k in sizes[2:130])
        assert 18_600 <= sum(sizes) <= 18_800


def test_aggregates_per_committee():
    """Open question 1's mix (3-set aggregate entries, 16 per committee) is
    data for the same generator."""
    cfg = full_cell("block-batch").config
    traffic = {"request_per": "committee", "per_committee": "TARGET_AGGREGATORS_PER_COMMITTEE",
               "groups": [{"kind": "single", "count": 2},
                          {"kind": "committee", "participation": 0.97}], "slots": 1}
    stream = generate.generate(cfg, traffic, SEED)
    assert len(stream.base) == 64 * 16
    assert all([len(s.keys) for s in r][:2] == [1, 1] and 142 <= len(r[2].keys) <= 143
               for r in stream.base)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_tiny_labels_agree_with_the_reference(workload):
    cell = tiny_cell(workload)
    stream = generate.generate(cell.config, cell.traffic, SEED)
    pks = [refv.g1_point(xy) for xy in keys.g1_chain(1, stream.validators)]
    msgs, _ = generate.load_pool()
    checked = 0
    for i in range(4):
        for s in stream.request(i):
            got = refv.verify_set([pks[v] for v in s.keys], msgs[s.msg], refv.g2_point(s.sig))
            assert got == s.valid
            checked += not s.valid
    # block: requests 0 and 2 are tampered, two sets each; gossip: the
    # swapped pair 0 and 1 (6 and 7 lie past the requests checked here)
    assert checked == (4 if cell.traffic["driver"] == "direct" else 2)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_tiny_run_is_correct(workload):
    line = run_tiny(workload)
    assert line["correct"], line
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {m["name"] for m in full_cell(workload).end_to_end}


@pytest.mark.parametrize("workload,fault", [
    ("gossip-burst", "flip"),
    ("gossip-burst", "half"),
    ("block-batch", "flip"),
    ("block-batch", "half"),
])
def test_a_broken_timed_path_is_not_correct(workload, fault):
    from perfbench.tests.tiny import ref_backend

    line = run_tiny(workload, bls=broken(ref_backend(), fault))
    assert not line["correct"]
    assert line["checks"]["wrong"]["value"] > 0


@pytest.mark.parametrize("workload,control", [
    ("gossip-burst", "uncleared_h2c"),
    ("block-batch", "unit_scalars"),
    ("block-batch", "uncleared_h2c"),
])
def test_the_control_is_not_correct(workload, control):
    line = run_tiny(workload, control=control)
    assert not line["correct"]
    assert line["checks"]["wrong"]["value"] > 0


PACED_DRIVER = """
import time

from lighthouse_tpu.crypto.bls import batch_verifier as bv

PER_SET = True


def buckets(stream):
    return [(4, 4)]


def drive(ctx):
    svc = bv.ensure_running(ctx.bls)
    try:
        ctx.window_opens()
        t0 = time.perf_counter()
        sent = []
        while time.perf_counter() - t0 < ctx.seconds or len(sent) % svc.s_bucket:
            sent.append((time.perf_counter(), svc.submit(ctx.make_sets(len(sent)))))
            time.sleep(ctx.traffic["interval_s"])
        answers, latencies = {}, []
        for i, (t, f) in enumerate(sent):
            answers[i] = f.result(timeout=ctx.answer_timeout)
            latencies.append(time.perf_counter() - t)
        t_end = time.perf_counter()
        ctx.window_closes()
        return {"t0": t0, "t_end": t_end, "units": len(sent), "answers": answers,
                "submitted": len(sent), "missing": 0, "latencies": latencies}
    finally:
        bv.release(svc)
"""

P95_READER = """
def read(m):
    lat = sorted(m.result["latencies"])
    return 1000.0 * lat[int(0.95 * (len(lat) - 1))]
"""


@pytest.mark.parametrize("new_driver", [False, True], ids=["direct-driver", "new-coalescer-driver"])
def test_added_files_are_found_by_name(tmp_path, monkeypatch, new_driver):
    """A new configuration, traffic mix and per-layer metric, and with them
    a new driver over the coalescer and a new end-to-end metric, added as
    files and BENCHMARK.json entries alone, run with no edit to the
    harness."""
    here = tmp_path / "perfbench"
    shutil.copytree(harness.HERE, here, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    base = tiny_cell("gossip-burst" if new_driver else "block-batch")
    cfg = base.config
    (here / "configs" / "dummy.json").write_text(json.dumps(cfg))
    traffic = base.traffic
    if new_driver:
        traffic.update(driver="dummy_paced", interval_s=0.002)
        (here / "drivers" / "dummy_paced.py").write_text(PACED_DRIVER)
        (here / "metrics" / "verdict_p95_ms.py").write_text(P95_READER)
        metric = "verdict_p95_ms"
    else:
        traffic["groups"] = [{"kind": "single", "count": 2}, {"kind": "committee", "count": 4}]
        traffic.pop("tamper")
        metric = "block_verify_ms"
    (here / "traffic" / "dummy-mix.json").write_text(json.dumps(traffic))
    (here / "metrics" / "dummy_rows.py").write_text(
        "def read(m):\n    return m.counters['padded_rows'] + m.result['units']\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "dummy", "source": "test", "file": "perfbench/configs/dummy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy", "traffic": "dummy-mix",
                               "chips": 1, "why": "test"})
    if metric not in {m["name"] for m in bench["end_to_end"]}:
        bench["end_to_end"].append({"name": metric, "unit": "ms", "better": "lower", "bound": 0.25,
                                    "source": "host_clock", "workloads": []})
    next(m for m in bench["end_to_end"] if m["name"] == metric).setdefault("workloads", []).append(
        "dummy-cell")
    bench["per_layer"].append({"name": "dummy_rows.x", "unit": "rows", "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": metric, "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "HERE", here)
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / ".perfbench_trace")
    cell = harness.load_cell(harness.load_benchmark(tmp_path / "BENCHMARK.json"), "dummy-cell")
    assert cell.config == cfg and cell.traffic == traffic
    line = run_tiny("dummy-cell", cell=cell)
    assert line["correct"], line
    assert set(line["metrics"]) == {metric, "setup_s"}
    assert line["metrics"][metric]["value"] > 0
    line = run_tiny("dummy-cell", cell=cell, trace=True)
    assert line["correct"], line
    assert set(line["metrics"]) == {"dummy_rows.x"}
