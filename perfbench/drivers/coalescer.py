"""Saturating load through the process-wide coalescer (BatchVerifier.submit).

`threads` submitter threads each take the next request of the stream and
submit its sets as one entry, keeping at most `queued_sets` sets unresolved;
the calling thread waits on the verdicts in submission order. Submission
takes a lock, so the coalescer's queue holds the sets in stream order and
every batch is a contiguous run of `s_bucket` sets.

The coalescer runs with the node's defaults in the window. Before it, its
first flush would go out at once with whatever single set is queued (the
device is idle), and while the submitters fill the queue a batch's 10 ms
deadline can pass with fewer than s_bucket sets queued: a partial batch is
another bucket, minutes of compile. So the lead-in runs with the device
marked busy and the deadline held off, and leaves only full batches; when
the window opens the queue holds `queued_sets` less those in flight, many
batches deep. At the end the stream stops on a multiple of s_bucket, and
the deadline is held off again, so no partial batch is formed then either.

While the resolver bisects a batch with an invalid set, later batches
finish on the device and wait; they resolve at once when it is done, so
the lead-in runs past that backlog before the window opens.

Window: it opens when the `lead_in_batches`-th batch resolves and closes at
the first batch resolution at least `seconds` later, so it holds whole
batches. The result's `units` are the sets resolved in it.
"""

from __future__ import annotations

import threading
import time

PER_SET = True  # each set gets its own verdict (bisection blames the invalid ones)


def buckets(stream) -> list[tuple[int, int]]:
    """The coalescer's full batch; bisection halves keep its bucket."""
    from lighthouse_tpu.crypto.bls.batch_verifier import DEFAULT_S_BUCKET
    from lighthouse_tpu.crypto.bls.jax_backend import api

    return [api.batch_bucket(DEFAULT_S_BUCKET, max(len(s.keys) for req in stream.base for s in req))]


def drive(ctx) -> dict:
    from lighthouse_tpu.crypto.bls import batch_verifier as bv

    p = ctx.traffic
    svc = bv.ensure_running(ctx.bls)
    if svc is None:
        raise RuntimeError("the process-wide coalescer is owned by another backend")
    s_bucket, max_wait = svc.s_bucket, svc.max_wait
    futures: list = []
    lock = threading.Lock()
    room = threading.Semaphore(p["queued_sets"])
    state = {"next": 0, "stop_at": None}
    errors: list = []

    def submitter():
        try:
            while True:
                room.acquire()
                with lock:
                    i = state["next"]
                    stop = state["stop_at"]
                    if stop is not None and i >= stop:
                        room.release()
                        return
                    state["next"] = i + 1
                    sets = ctx.make_sets(i)
                    with ctx.annotate("submit"):
                        futures.append(svc.submit(sets))
        except BaseException as exc:  # surfaced by the waiting thread
            errors.append(exc)
            raise

    def future(i):
        while len(futures) <= i:
            if errors:
                raise errors[0]
            time.sleep(0.0005)
        return futures[i]

    answers: dict[int, list[bool]] = {}
    late = {"missing": 0}

    def answer(j):
        """Wait for request j's verdicts; after one has never come, stop
        waiting and count the rest as missing."""
        timeout = 0 if late["missing"] else ctx.answer_timeout
        try:
            with ctx.annotate("wait"):
                answers[j] = future(j).result(timeout=timeout)
        except TimeoutError:
            late["missing"] += 1
        room.release()

    lead = p["lead_in_batches"] * s_bucket
    workers = [threading.Thread(target=submitter, name=f"submit-{k}") for k in range(p["threads"])]
    svc.max_wait = 3600.0
    busy = bv.mark_device_busy()
    busy.__enter__()
    try:
        for w in workers:
            w.start()
        t0 = t_end = window = None
        i = 0
        while True:
            answer(i)
            i += 1
            if i % s_bucket:
                continue
            now = time.perf_counter()
            if i == lead:
                svc.max_wait = max_wait
                busy.__exit__(None, None, None)
                busy = None
                t0 = now
                ctx.window_opens()
                window = ctx.annotate("window")
                window.__enter__()
            elif t0 is not None and (now - t0 >= ctx.seconds or late["missing"]):
                t_end = now
                window.__exit__(None, None, None)
                ctx.window_closes()
                break
        n_window = i - lead
        with lock:
            svc.max_wait = 3600.0  # the tail below is whole batches: no deadline flush
            state["stop_at"] = -(-state["next"] // s_bucket) * s_bucket
        for j in range(i, state["stop_at"]):
            answer(j)
        for w in workers:
            w.join(ctx.answer_timeout)
        if errors:
            raise errors[0]
        return {
            "t0": t0,
            "t_end": t_end,
            "units": n_window,
            "answers": answers,
            "submitted": len(futures),
            "missing": late["missing"],
            "notes": {"s_bucket": s_bucket, "dispatches": svc.dispatches, "sets": svc.sets_coalesced},
        }
    finally:
        if busy is not None:
            busy.__exit__(None, None, None)
        svc.max_wait = max_wait
        with lock:
            if state["stop_at"] is None:
                state["stop_at"] = 0
        for _ in workers:
            room.release()
        bv.release(svc)
