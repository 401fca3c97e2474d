"""The readers of the program's own set-up spans, on a tracer filled by
hand: each sums its span over a warm load, and returns None where the
program has no such span (a program without these spans, or the plain
backend), where the load was cold, or where set-up's share cannot be told
apart (a program built in the window)."""

from types import SimpleNamespace as NS

import pytest

from lighthouse_tpu.common import metrics, tracing
from lighthouse_tpu.common.metrics import CounterVec, HistogramVec
from perfbench.harness import load_reader


@pytest.fixture
def tracer(monkeypatch):
    tr = tracing.Tracer(stage_histogram=HistogramVec("rd_seconds", "", ("stage",)))
    monkeypatch.setattr(tracing, "TRACER", tr)
    return tr


@pytest.fixture
def cache(monkeypatch):
    vec = CounterVec("rd_cache_total", "", ("result",))
    monkeypatch.setattr(metrics, "BLS_COMPILE_CACHE_TOTAL", vec, raising=False)
    return vec


def _observe(tr, stage, *seconds):
    for s in seconds:
        tr._stage_histogram.labels(stage=stage).observe(s)


@pytest.mark.parametrize("name,span", [("program_lower_s.gossip", "bls_lower"),
                                       ("program_compile_s.gossip", "bls_compile")])
def test_set_up_spans_are_summed(tracer, cache, name, span):
    read = load_reader(name).read
    warm = NS(counters={"jit_builds": 0})
    cache.labels(result="hit").inc(5)
    cache.labels(result="uncached").inc()
    assert read(warm) is None  # no such span
    _observe(tracer, span, 1.5, 2.25, 0.25)
    _observe(tracer, "bls_dispatch", 9.0)
    assert read(warm) == pytest.approx(4.0)
    assert read(NS(counters={"jit_builds": 1})) is None  # a build in the window


@pytest.mark.parametrize("name", ["program_lower_s.gossip", "program_compile_s.gossip"])
def test_set_up_spans_are_held_to_a_warm_load(tracer, cache, monkeypatch, name):
    read = load_reader(name).read
    warm = NS(counters={"jit_builds": 0})
    _observe(tracer, "bls_lower", 80.0)
    _observe(tracer, "bls_compile", 130.0)
    assert read(warm) is None  # no program came from the cache: cache off
    cache.labels(result="hit").inc(5)
    cache.labels(result="miss").inc()
    assert read(warm) is None  # one program compiled and written: cold
    monkeypatch.delattr(metrics, "BLS_COMPILE_CACHE_TOTAL")
    assert read(warm) is None  # a program that does not count its loads
