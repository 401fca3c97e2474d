"""Pipeline tracing: timed spans, per-stage histograms and self times, on
the profiler's clock.

Every pipeline stage (block import, processor dispatch, BLS device funnel)
runs under `span("stage_name")`. A span times itself, nests under whatever
span is open on ITS thread, and on completion feeds
`lighthouse_tpu_stage_seconds{stage=...}` and the tracer's cumulative self
times (duration minus children), so the Prometheus scrape and the slot
ledger (common/slot_ledger.py) report from the same measurements.

When jax is loaded, a span also opens a profiler annotation of its name
(`jax.profiler.TraceAnnotation`). Under a running profiler trace that is an
event on the span's own thread line of the trace's host plane, on the same
clock as the device's program and op events; with no trace running it costs
well under a microsecond. This module never imports jax: nodes on the plain
backend never load it.

Design constraints:
  - thread-local stacks: the HTTP server, socket receivers, and the drain
    loop each trace independently; spans never cross threads.
  - exceptions propagate; the span still closes and records its time.
"""

from __future__ import annotations

import sys
import threading
import time
from contextlib import contextmanager

from .metrics import REGISTRY

STAGE_SECONDS = REGISTRY.histogram_vec(
    "lighthouse_tpu_stage_seconds",
    "Wall time per traced pipeline stage (fed by common.tracing spans)",
    ("stage",),
)


class Span:
    __slots__ = ("name", "started_at", "duration", "child_s")

    def __init__(self, name: str):
        self.name = name
        self.started_at = time.perf_counter()
        self.duration: float | None = None  # None while still open
        self.child_s = 0.0  # summed durations of the closed child spans


class Tracer:
    def __init__(self, stage_histogram=STAGE_SECONDS):
        self._local = threading.local()
        self._stage_histogram = stage_histogram
        self._lock = threading.Lock()
        # cumulative EXCLUSIVE (self) time per stage: a span's duration
        # minus its children's — non-overlapping within a thread, so
        # windowed deltas sum to at most wall time. The slot-SLO ledger
        # (common/slot_ledger.py) diffs this dict at slot boundaries, so it
        # only ever grows.
        self._self_times: dict[str, float] = {}

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextmanager
    def span(self, name: str, **annotation):
        """Time the block as stage `name`. `annotation` (key=value) rides on
        the profiler event alone, e.g. which program a span built."""
        stack = self._stack()
        s = Span(name)
        stack.append(s)
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        mark = profiler.TraceAnnotation(name, **annotation) if profiler is not None else None
        if mark is not None:
            mark.__enter__()
        try:
            yield s
        finally:
            if mark is not None:
                mark.__exit__(None, None, None)
            s.duration = time.perf_counter() - s.started_at
            stack.pop()
            if stack:
                stack[-1].child_s += s.duration
            self._stage_histogram.labels(stage=name).observe(s.duration)
            with self._lock:
                self._self_times[name] = self._self_times.get(name, 0.0) + max(
                    0.0, s.duration - s.child_s
                )

    def stage_report(self) -> dict[str, dict]:
        """{stage: {count, total_s, mean_s}} from the stage histogram — the
        shape SlotLedger.stage_report() emits too."""
        out = {}
        for (stage,), child in sorted(self._stage_histogram.children().items()):
            n = child.count
            out[stage] = {
                "count": n,
                "total_s": child.sum,
                "mean_s": (child.sum / n) if n else 0.0,
            }
        return out

    def self_time_report(self) -> dict[str, float]:
        """{stage: cumulative exclusive seconds} — duration minus children,
        so summing stages never double-counts nested spans. Monotonic: the
        slot ledger attributes a slot by diffing two snapshots."""
        with self._lock:
            return dict(self._self_times)


# The process-global tracer; `span("x")` is the instrumentation one-liner.
TRACER = Tracer()
span = TRACER.span
