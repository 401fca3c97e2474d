"""1 - device busy time over the traced window."""


def read(m):
    return m.trace.get("idle_share")
