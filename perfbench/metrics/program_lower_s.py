"""Tracing and lowering of the stage programs at set-up, in s: the
program's `bls_lower` spans (api.compile_verify_kernels, one per stage
program, in the calling thread), summed. Held to a warm load, as
program_compile_s is (lighthouse_tpu_bls_compile_cache_total: a hit, and
no miss): on a cold load the compiles share the host's cores with the
lowering and slow it. Read from the program's tracer after the window:
with no program built in the window, as each run prints, the sum is what
it was at the window's opening. None on a cold load, when a program was
built in the window, or when the program has no such span or counter."""


def read(m):
    from lighthouse_tpu.common import metrics, tracing

    cache = getattr(metrics, "BLS_COMPILE_CACHE_TOTAL", None)
    if cache is None or m.counters.get("jit_builds"):
        return None
    if cache.labels(result="miss").value or not cache.labels(result="hit").value:
        return None
    row = tracing.TRACER.stage_report().get("bls_lower")
    return row["total_s"] if row else None
