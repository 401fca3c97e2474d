"""Device time of the stage chain per whole batch in the traced window (the
batches whose programs all ran inside it), in ms."""


def read(m):
    batches = m.trace.get("batches")
    return 1000.0 * sum(sum(b.values()) for b in batches) / len(batches) if batches else None
