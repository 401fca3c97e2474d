"""Sets whose verdict arrived in the window over the window's seconds."""


def read(m):
    return m.result["units"] / (m.result["t_end"] - m.result["t0"])
