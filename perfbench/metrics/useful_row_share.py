"""Mean sets per coalesced batch over mean padded rows per staged batch, in
the window (lighthouse_tpu_bls_coalesced_batch_size and
lighthouse_tpu_bls_batch_padded_size): the share of a batch's device rows
that carry a set. A ratio of means, so a batch formed before the window
and staged in it moves nothing. Bisection halves are not in it (their sets
are not counted); each run prints the bisection dispatches in its window."""


def read(m):
    c = m.counters
    if not c.get("coalesced_batches") or not c.get("padded_batches"):
        return None
    return (c["coalesced_sets"] / c["coalesced_batches"]) / (c["padded_rows"] / c["padded_batches"])
