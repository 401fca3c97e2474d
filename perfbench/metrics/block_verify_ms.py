"""The window's milliseconds over the requests (blocks) verified in it."""


def read(m):
    return 1000.0 * (m.result["t_end"] - m.result["t0"]) / m.result["units"]
