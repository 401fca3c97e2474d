"""Device time of the keys stage program (key aggregation and the G1
ladders) per whole batch in the traced window, in ms."""


def read(m):
    batches = m.trace.get("batches")
    return 1000.0 * sum(b.get("keys", 0.0) for b in batches) / len(batches) if batches else None
