"""The verifier's own spans and counters: spans reach the profiler's host
plane when jax is loaded, the BLS path opens them where its work happens
(lowering, compiling, dispatch, bisection), and staging counts its real and
padding rows and key slots."""

from __future__ import annotations

import pathlib
import sys
import types

import pytest

from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import (
    BLS_STAGED_KEY_SLOTS_TOTAL,
    BLS_STAGED_ROWS_TOTAL,
    HistogramVec,
)


def _counts(tr):
    return {k: v["count"] for k, v in tr.stage_report().items()}


# -- spans on the profiler's clock ---------------------------------------------


def test_span_is_a_host_event_of_a_running_profiler_trace(tmp_path):
    import threading

    import jax
    from jax.profiler import ProfileData

    tr = tracing.Tracer(stage_histogram=HistogramVec("pt_seconds", "", ("stage",)))

    def staged():
        with tr.span("probe_outer"):
            with tr.span("probe_build", stage="keys-4-4"):
                jax.numpy.ones(3).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        worker = threading.Thread(target=staged, name="probe-stager")
        worker.start()
        worker.join()
    finally:
        jax.profiler.stop_trace()
    [path] = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("probe_"):
                        events[e.name] = e
    assert set(events) == {"probe_outer", "probe_build"}
    outer, build = events["probe_outer"], events["probe_build"]
    assert outer.start_ns <= build.start_ns
    assert build.start_ns + build.duration_ns <= outer.start_ns + outer.duration_ns
    assert ("stage", "keys-4-4") in list(build.stats)
    assert _counts(tr) == {"probe_build": 1, "probe_outer": 1}


class _Mark:
    def __init__(self, log, name, **kw):
        self.log, self.name, self.kw = log, name, kw

    def __enter__(self):
        self.log.append(("enter", self.name, self.kw))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_span_opens_an_annotation_only_when_jax_is_loaded(monkeypatch):
    log = []
    fake = types.SimpleNamespace(profiler=types.SimpleNamespace(
        TraceAnnotation=lambda name, **kw: _Mark(log, name, **kw)))
    tr = tracing.Tracer(stage_histogram=HistogramVec("pa_seconds", "", ("stage",)))
    monkeypatch.setitem(sys.modules, "jax", fake)
    with pytest.raises(RuntimeError):
        with tr.span("loaded", stage="final"):
            raise RuntimeError("the annotation still closes")
    assert log == [("enter", "loaded", {"stage": "final"}), ("exit", "loaded")]
    monkeypatch.delitem(sys.modules, "jax")
    with tr.span("unloaded"):
        pass
    assert len(log) == 2
    assert _counts(tr) == {"loaded": 1, "unloaded": 1}


def test_the_tracer_does_not_load_jax():
    import subprocess

    code = ("import sys; from lighthouse_tpu.common.tracing import span\n"
            "with span('x'):\n    pass\n"
            "assert 'jax' not in sys.modules, 'tracing loaded jax'\n")
    root = pathlib.Path(__file__).resolve().parents[1]
    subprocess.run([sys.executable, "-c", code], check=True, cwd=root)


# -- the verifier's spans --------------------------------------------------------


@pytest.fixture
def fresh_tracer(monkeypatch):
    """The process tracer swapped for one with its own histogram, so span
    counts read exactly what a test did."""
    tr = tracing.Tracer(stage_histogram=HistogramVec("ps_seconds", "", ("stage",)))
    monkeypatch.setattr(tracing, "TRACER", tr)
    monkeypatch.setattr(tracing, "span", tr.span)
    return tr


class _Lowered:
    def compile(self):
        return lambda *args: True


def test_each_stage_build_is_a_lower_and_a_compile_span(monkeypatch, fresh_tracer):
    from lighthouse_tpu.crypto.bls.jax_backend import api

    monkeypatch.setattr(api, "_lower_stage", lambda key: _Lowered())
    monkeypatch.setattr(api, "_kernels", {})
    api.compile_verify_kernels([(4, 4), (8, 4)])
    # (8, 4) shares only the shape-free final stage with (4, 4); its grouped
    # chain (U = 4) adds its own unpack and group programs, and reuses
    # (4, 4)'s pairs and its own full chain's h2c
    n = 2 * len(api._VERIFY_CHAIN) - 1 + 2
    assert _counts(fresh_tracer) == {"bls_compile": n, "bls_lower": n}
    api.compile_verify_kernels([(4, 4)])  # built already: no span
    assert _counts(fresh_tracer) == {"bls_compile": n, "bls_lower": n}


class _CacheLowered:
    """A program whose compile records what JAX's persistent cache records
    in the compiling thread: a read (hit), a write (miss), or nothing."""

    def __init__(self, event):
        self.event = event

    def compile(self):
        import jax

        if self.event:
            jax.monitoring.record_event(f"/jax/compilation_cache/{self.event}")
        return lambda *args: True


def test_each_stage_build_counts_its_cache_result(monkeypatch, fresh_tracer):
    from lighthouse_tpu.common.metrics import BLS_COMPILE_CACHE_TOTAL
    from lighthouse_tpu.crypto.bls.jax_backend import api

    event = {"unpack": None, "keys": "cache_misses"}
    monkeypatch.setattr(api, "_lower_stage",
                        lambda key: _CacheLowered(event.get(key[0], "cache_hits")))
    monkeypatch.setattr(api, "_kernels", {})
    results = ("hit", "miss", "uncached")
    before = {r: BLS_COMPILE_CACHE_TOTAL.labels(result=r).value for r in results}
    api.compile_verify_kernels([(4, 4)])
    after = {r: BLS_COMPILE_CACHE_TOTAL.labels(result=r).value - before[r] for r in results}
    assert after == {"hit": len(api._VERIFY_CHAIN) - 2, "miss": 1, "uncached": 1}


def test_the_unpack_program_has_a_stable_name():
    import jax

    from lighthouse_tpu.crypto.bls.jax_backend import api

    fn, specs = api.verify_stage(api.Chain(4, 1).stage_keys()[0])
    assert "_unpack_stage" in jax.jit(fn).lower(*specs).as_text().split("\n", 1)[0]


def _sets(n_keys):
    """One signature set per entry of `n_keys`, with that many keys (any
    signature: staging does not verify)."""
    from lighthouse_tpu.crypto.bls.jax_backend import api

    pks = [api.interop_keypair(i)[1] for i in range(max(n_keys))]
    sig = api.Signature(api.g2_infinity())
    return [api.SignatureSet(signature=sig, signing_keys=pks[:k], message=bytes([i]) * 32)
            for i, k in enumerate(n_keys)]


def _staged():
    rows = {r: BLS_STAGED_ROWS_TOTAL.labels(row=r).value for r in ("set", "pad")}
    slots = {s: BLS_STAGED_KEY_SLOTS_TOTAL.labels(slot=s).value for s in ("key", "pad")}
    return rows, slots


def _delta(before, after):
    return tuple({k: a[k] - b[k] for k in a} for b, a in zip(before, after))


def test_staging_counts_rows_and_key_slots():
    from lighthouse_tpu.crypto.bls.jax_backend import api

    before = _staged()
    staged = api.stage_sets(_sets([1, 2, 1]))
    assert staged[2].shape == (4, 4)  # S and K both floored at 4
    assert _delta(before, _staged()) == ({"set": 3, "pad": 1}, {"key": 4, "pad": 12})


def test_bisection_halves_are_staged_counted_and_spanned(monkeypatch, fresh_tracer):
    """A failed batch of 4 with one bad set: the resolver bisects into
    halves of 2 and then 1, each staged in the failed batch's bucket
    through the backend's dispatch, under one bls_bisect span."""
    from lighthouse_tpu.crypto.bls.batch_verifier import BatchVerifier, _Ready
    from lighthouse_tpu.crypto.bls.jax_backend import api

    monkeypatch.setattr(api, "_verify_kernel", lambda chain: (lambda flat: True))
    sets = _sets([1, 1, 1, 1])
    bad = sets[2]

    class Backend:
        def verify_signature_sets_async(self, batch, s_floor=4):
            api.verify_signature_sets_async(batch, s_floor=s_floor)
            return _Ready(bad not in batch)

    entry = types.SimpleNamespace(sets=sets, meta=None, future=types.SimpleNamespace(
        _resolve=lambda verdicts: got.extend(verdicts)))
    got = []
    before = _staged()
    BatchVerifier(Backend())._resolve_one([entry], sets, _Ready(False), 0.0)
    assert got == [True, True, False, True]
    # halves [0,1] [2,3], then [2] [3]: 6 sets in 4 rows-of-4 batches
    assert _delta(before, _staged()) == ({"set": 6, "pad": 10}, {"key": 6, "pad": 58})
    counts = _counts(fresh_tracer)
    assert counts["bls_bisect"] == 1 and counts["bls_dispatch"] == 4
    assert counts["bls_device_execute"] == 1


def test_a_direct_batch_times_the_verdict_copy_apart_from_the_device_wait(monkeypatch,
                                                                          fresh_tracer):
    """verify_signature_sets waits for the chain on the device, then copies
    the verdict to the host under bls_fetch, inside bls_device_execute."""
    from lighthouse_tpu.common.slot_ledger import STAGE_OF_SPAN
    from lighthouse_tpu.crypto.bls.jax_backend import api

    calls = []

    class Verdict:
        def block_until_ready(self):
            calls.append("ready")
            return self

        def __bool__(self):
            calls.append("copy")
            return True

    monkeypatch.setattr(api, "_verify_kernel", lambda chain: (lambda flat: Verdict()))
    assert api.verify_signature_sets(_sets([1, 2])) is True
    assert calls == ["ready", "copy"]
    counts = _counts(fresh_tracer)
    assert counts["bls_device_execute"] == counts["bls_fetch"] == 1
    own, total = fresh_tracer.self_time_report(), fresh_tracer.stage_report()
    assert own["bls_device_execute"] == pytest.approx(  # bls_fetch is its child
        total["bls_device_execute"]["total_s"] - total["bls_fetch"]["total_s"])
    assert STAGE_OF_SPAN["bls_fetch"] == "device_execute"


def test_the_slot_ledger_counts_dispatch_and_bisection_as_device_execution():
    """bls_dispatch's self time stays where it was counted as part of
    bls_batch_verify's; the stage-program builds fall to other_traced."""
    import time as _t

    from lighthouse_tpu.common.slot_ledger import SlotLedger

    tr = tracing.Tracer(stage_histogram=HistogramVec("pl_seconds", "", ("stage",)))
    led = SlotLedger(seconds_per_slot=5.0, tracer=tr)
    led.on_slot(1)
    with tr.span("bls_batch_verify"):
        with tr.span("bls_stage"):
            _t.sleep(0.004)
        with tr.span("bls_dispatch"):
            _t.sleep(0.006)
    with tr.span("bls_bisect"):
        _t.sleep(0.005)
    with tr.span("bls_lower"):
        _t.sleep(0.003)
    led.on_slot(2)
    [rec] = led.records()
    own = tr.self_time_report()
    assert rec["stages"]["device_execute"] == pytest.approx(
        own["bls_batch_verify"] + own["bls_dispatch"] + own["bls_bisect"])
    assert rec["stages"]["device_execute"] >= 0.011
    assert rec["stages"]["staging"] == pytest.approx(own["bls_stage"])
    assert rec["stages"]["other_traced"] == pytest.approx(own["bls_lower"])
