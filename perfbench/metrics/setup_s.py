"""Process start to the window's opening, in s: JAX import, program
import, data, program load or compile, warm-up."""


def read(m):
    return m.setup_s
