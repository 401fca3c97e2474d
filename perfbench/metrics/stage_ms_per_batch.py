"""Host staging time per staged batch in the window, in ms
(lighthouse_tpu_bls_stage_seconds: sum over count)."""


def read(m):
    n = m.counters.get("stage_count")
    return 1000.0 * m.counters["stage_seconds"] / n if n else None
