"""Closed loop, one request in flight, through `verify_signature_sets`.

This is the call BlockSignatureVerifier makes for a block under
VERIFY_BULK: all of the block's sets in one batch, one verdict for all.
The coalescer is not running. `warmup_requests` requests go first and are
set-up; the window then runs from the first timed call to the first
completion at least `seconds` later, and the time per request is the
window's length over the requests completed in it. Each timed call's
milliseconds go to the notes, so a slow call shows where it fell.
"""

from __future__ import annotations

import time

PER_SET = False  # one verdict for all of a request's sets


def buckets(stream) -> list[tuple[int, int]]:
    """One bucket per request size, each request one batch."""
    from lighthouse_tpu.crypto.bls.jax_backend import api

    max_keys = max(len(s.keys) for req in stream.base for s in req)
    return sorted({api.batch_bucket(len(req), max_keys) for req in stream.base})


def drive(ctx) -> dict:
    verify = ctx.bls.verify_signature_sets
    answers: dict[int, bool] = {}
    warm = ctx.traffic["warmup_requests"]
    for i in range(warm):
        answers[i] = bool(verify(ctx.make_sets(i)))
    ctx.window_opens()
    i = warm
    call_ms = []
    with ctx.annotate("window"):
        t0 = t_end = time.perf_counter()
        while True:
            with ctx.annotate("prepare"):
                sets = ctx.make_sets(i)
            with ctx.annotate("verify"):
                answers[i] = bool(verify(sets))
            i += 1
            t_prev, t_end = t_end, time.perf_counter()
            call_ms.append(round((t_end - t_prev) * 1000.0, 3))
            if t_end - t0 >= ctx.seconds:
                break
    ctx.window_closes()
    n = i - warm
    return {
        "t0": t0,
        "t_end": t_end,
        "units": n,
        "answers": answers,
        "submitted": i,
        "missing": 0,
        "notes": {"request_ms": call_ms},
    }
