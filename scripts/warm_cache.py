"""Populate the persistent compile cache (.jax_cache) for the slow tier.

pytest only READS the cache (tests/conftest.py), and the slow tier's CPU
verify buckets take minutes each to compile cold. This script runs a
child process on an 8-device virtual CPU mesh (XLA_FLAGS must be set before
its first jax import) that compiles the representative kernel shapes —
single-chip verify buckets and the 8-device sharded program — so later
test runs start warm.

Run: python scripts/warm_cache.py   (takes tens of minutes cold; reruns
are no-ops because every compile hits the cache)
"""

import os
import pathlib
import subprocess
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent

_CHILD = r"""
import os, sys
sys.path.insert(0, "@ROOT@")
from lighthouse_tpu.crypto import bls

b = bls.backend("jax")
pairs = [b.interop_keypair(i) for i in range(4)]
msg = b"\x5c" * 32

def sets(n, k):
    sk, pk = pairs[0]
    agg = b.aggregate_signatures([s.sign(msg) for s, _ in pairs[:k]])
    keys = [p for _, p in pairs[:k]]
    one = b.SignatureSet(signature=agg, signing_keys=keys, message=msg)
    return [one] * n

for n, k in ((4, 1), (4, 4), (128, 1)):
    ok = b.verify_signature_sets(sets(n, k))
    print(f"warmed verify S={n} K={k}: {ok}", flush=True)
    assert ok

from lighthouse_tpu.parallel.sharded import build_sharded_verify, make_mesh
from lighthouse_tpu.crypto.bls.jax_backend import api as japi
import jax.numpy as jnp

mesh = make_mesh(8)
staged = japi.stage_sets(sets(8, 1), rng=japi._ONE_RNG, s_floor=8)
kernel = build_sharded_verify(mesh)
assert bool(kernel(*(jnp.asarray(a) for a in staged)))
print("warmed 8-device sharded verify", flush=True)
"""


def main() -> None:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.replace("@ROOT@", str(_ROOT))], env=env, cwd=str(_ROOT)
    )
    raise SystemExit(proc.returncode)


if __name__ == "__main__":
    main()
