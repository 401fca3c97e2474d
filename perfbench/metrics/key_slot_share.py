"""Real public keys over all staged key slots over the run, bisection
halves included (lighthouse_tpu_bls_staged_key_slots_total, slot=key over
slot=key and slot=pad): the share of the keys stage's S x K slots that
carry a key rather than padding. Read from the program's counters after
the run, as totals. None where the program has no such counter or staged
nothing."""


def read(m):
    from lighthouse_tpu.common import metrics

    slots = getattr(metrics, "BLS_STAGED_KEY_SLOTS_TOTAL", None)
    if slots is None:
        return None
    key = slots.labels(slot="key").value
    total = key + slots.labels(slot="pad").value
    return key / total if total else None
