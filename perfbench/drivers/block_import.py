"""Block import: closed loop, one block in flight, through `verify_signature_sets`.

Each request is one block's signature sets, handed whole to the backend's
`verify_signature_sets`, the call BlockSignatureVerifier.verify makes under
VERIFY_BULK (state_transition/per_block.py): one batch, one verdict for the
block. The coalescer is not running. `warmup_requests` blocks go first and
are set-up; the window then opens on the last warm-up's completion and
closes on the first completion at least `seconds` later. Its `units` are
the sets of the blocks completed in it.

Each timed call is logged, so a slow one shows where it fell and what it
was made of: its milliseconds, and the program's staging, dispatch,
device-wait and verdict-copy spans inside it (lighthouse_tpu_stage_seconds:
bls_stage, bls_dispatch, bls_device_execute, bls_fetch; 0 where the
program has no such span).
"""

from __future__ import annotations

import time

PER_SET = False  # one verdict for all of a block's sets
SPANS = {"stage_ms": "bls_stage", "dispatch_ms": "bls_dispatch", "wait_ms": "bls_device_execute",
         "fetch_ms": "bls_fetch"}


def buckets(stream) -> list[tuple[int, int]]:
    """One bucket per block size, each block one batch."""
    from lighthouse_tpu.crypto.bls.jax_backend import api

    max_keys = max(len(s.keys) for req in stream.base for s in req)
    return sorted({api.batch_bucket(len(req), max_keys) for req in stream.base})


def _span_totals() -> dict[str, float]:
    from lighthouse_tpu.common.tracing import TRACER

    report = TRACER.stage_report()
    return {k: report.get(span, {}).get("total_s", 0.0) for k, span in SPANS.items()}


def drive(ctx) -> dict:
    verify = ctx.bls.verify_signature_sets
    answers: dict[int, bool] = {}
    warm = ctx.traffic["warmup_requests"]
    for i in range(warm):
        answers[i] = bool(verify(ctx.make_sets(i)))
    log = {k: [] for k in ("request_ms", *SPANS)}
    ctx.window_opens()
    i, sets_done = warm, 0
    with ctx.annotate("window"):
        t0 = t_end = time.perf_counter()
        spans = _span_totals()
        while True:
            with ctx.annotate("prepare"):
                sets = ctx.make_sets(i)
            with ctx.annotate("verify"):
                answers[i] = bool(verify(sets))
            i += 1
            sets_done += len(sets)
            t_prev, t_end = t_end, time.perf_counter()
            spans_prev, spans = spans, _span_totals()
            log["request_ms"].append(round((t_end - t_prev) * 1000.0, 3))
            for k in SPANS:
                log[k].append(round((spans[k] - spans_prev[k]) * 1000.0, 3))
            if t_end - t0 >= ctx.seconds:
                break
    ctx.window_closes()
    return {
        "t0": t0,
        "t_end": t_end,
        "units": sets_done,
        "answers": answers,
        "submitted": i,
        "missing": 0,
        "notes": log,
    }
