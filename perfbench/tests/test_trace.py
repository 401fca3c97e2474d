"""The trace reduction (perfbench/trace.py) on a synthetic trace shaped
like a TPU's: a host plane with the harness's spans, a device plane with
"XLA Modules" and "XLA Ops" lines. (A recorded chip trace of two gossip
batches is 242 MB, too large to keep.)"""

from types import SimpleNamespace as NS

import pytest

from perfbench import trace


def _ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def _line(name, events):
    return NS(name=name, events=[_ev(*e) for e in events])


def _synthetic():
    """Window 0-100 ns on the host; on the device two keys runs and one
    final, with ops covering 10-30, 25-40 and 70-90 (busy 50)."""
    host = NS(name="/host:CPU", lines=[
        _line("python", [("window", 0, 100), ("submit", 0, 12), ("wait", 40, 95)]),
        _line("other", [("unrelated", 40, 70)]),
    ])
    device = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [("jit__keys_stage(1)", 10, 30), ("jit__keys_stage(1)", 25, 40),
                              ("jit__final_stage(2)", 70, 90), ("jit_something(3)", 95, 120)]),
        _line("XLA Ops", [("fusion.1", 10, 30), ("fusion.2", 25, 40), ("fusion.3", 70, 90)]),
    ])
    return NS(planes=[NS(name="/host:metadata", lines=[]), host, device])


def test_busy_is_the_union_and_idle_the_rest():
    r = trace.reduce(_synthetic())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["idle_share"] == pytest.approx(0.5)


def test_programs_map_to_stages_by_name():
    r = trace.reduce(_synthetic())
    assert r["stage_s"]["keys"] == pytest.approx(35e-9)
    assert r["stage_s"]["final"] == pytest.approx(20e-9)
    assert r["stage_s"]["other"] == pytest.approx(5e-9)  # clipped to the window
    assert r["stage_runs"] == {"keys": 2, "final": 1, "other": 1}  # started inside the window
    assert trace.stage_of("jit__lambda_(7)", trace.stage_patterns()) == "unpack"
    assert trace.stage_of("jit_hash_to_g2_device", trace.stage_patterns()) == "h2c"


def test_gaps_are_labelled_by_the_harness_span_that_covers_them():
    r = trace.reduce(_synthetic())
    # gaps: 40-70 (wait), 0-10 (submit), 90-100 (wait)
    assert r["idle_gaps"][0] == ["wait", pytest.approx(30e-9)]
    assert sorted(label for label, _ in r["idle_gaps"]) == ["submit", "wait", "wait"]


def test_no_window_or_no_device_reads_nothing():
    pd = _synthetic()
    pd.planes[1].lines[0].events = [e for e in pd.planes[1].lines[0].events if e.name != "window"]
    assert trace.reduce(pd) == {}


def test_only_whole_batches_count():
    """Three chains of unpack..final: one begun before the window, one
    inside it, one cut by its end; only the middle one is a whole batch."""
    chain = ["jit__lambda_(1)", "jit__keys_stage(2)", "jit__sigs_stage(3)",
             "jit_hash_to_g2_device(4)", "jit__pairs_stage(5)", "jit__final_stage(6)"]
    events = [(name, t0 + 10 * k, t0 + 10 * k + 8) for t0 in (-30, 100, 300) for k, name in enumerate(chain)]
    pd = NS(planes=[
        NS(name="/host:CPU", lines=[_line("python", [("window", 0, 340)])]),
        NS(name="/device:TPU:0", lines=[_line("XLA Modules", events)]),
    ])
    r = trace.reduce(pd)
    assert r["batches"] == [{s: pytest.approx(8e-9) for s in ("unpack", "keys", "sigs", "h2c", "pairs", "final")}]
    assert r["device_span"] == [pytest.approx(0.0), pytest.approx(2e-9)]  # idle 338-340
    m = NS(trace=r)
    from perfbench.harness import load_reader

    assert load_reader("device_ms_per_batch.gossip").read(m) == pytest.approx(48e-6)
    assert load_reader("keys_stage_ms.gossip").read(m) == pytest.approx(8e-6)
