"""XLA compile or persistent-cache load of the stage programs at set-up,
in pool-thread seconds: the program's `bls_compile` spans
(api._build_kernel, one per stage program, up to COMPILE_WORKERS at once),
summed. Held to a warm load: every program the persistent cache keeps was
loaded from it (lighthouse_tpu_bls_compile_cache_total: a hit, and no
miss). A cold load compiles, about four times as long, and the cache's
state belongs to the checkout, not to the code. Read from the program's
tracer after the window: with no program built in the window, as each run
prints, the sum is what it was at the window's opening. None on a cold
load, when a program was built in the window, or when the program has no
such span or counter."""


def read(m):
    from lighthouse_tpu.common import metrics, tracing

    cache = getattr(metrics, "BLS_COMPILE_CACHE_TOTAL", None)
    if cache is None or m.counters.get("jit_builds"):
        return None
    if cache.labels(result="miss").value or not cache.labels(result="hit").value:
        return None
    row = tracing.TRACER.stage_report().get("bls_compile")
    return row["total_s"] if row else None
