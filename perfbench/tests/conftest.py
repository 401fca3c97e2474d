"""The benchmark's own tests run on the CPU, at sizes a test run holds."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
