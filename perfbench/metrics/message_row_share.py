"""Message rows over staged rows over the run, bisection halves included
(lighthouse_tpu_bls_message_rows_total over
lighthouse_tpu_bls_staged_rows_total): 0.5 where every batch groups its
sets by message and stages S/2 message rows, 1.0 where none does. Read
from the program's counters after the run, as totals. None where the
program has no such counter."""


def read(m):
    from lighthouse_tpu.common import metrics

    msg = getattr(metrics, "BLS_MESSAGE_ROWS_TOTAL", None)
    staged = getattr(metrics, "BLS_STAGED_ROWS_TOTAL", None)
    if msg is None or staged is None:
        return None
    rows = sum(staged.labels(row=r).value for r in ("set", "pad"))
    return sum(msg.labels(row=r).value for r in ("distinct", "pad")) / rows if rows else None
