"""Set rows over all staged rows over the run, bisection halves included
(lighthouse_tpu_bls_staged_rows_total, row=set over row=set and row=pad):
the share of a batch's S device rows that carry a set rather than padding.
Read from the program's counters after the run, as totals. None where the
program has no such counter or staged nothing."""


def read(m):
    from lighthouse_tpu.common import metrics

    rows = getattr(metrics, "BLS_STAGED_ROWS_TOTAL", None)
    if rows is None:
        return None
    real = rows.labels(row="set").value
    total = real + rows.labels(row="pad").value
    return real / total if total else None
