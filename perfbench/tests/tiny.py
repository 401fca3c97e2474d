"""Tiny versions of the benchmark's cells for CPU tests.

They keep each cell's traffic file (driver, groups, tamper rule) and shrink
only the sizes: a configuration with one 16-member committee a slot, and
the plain reference backend (lighthouse_tpu.crypto.bls.ref) in place of
the jax one, whose programs take minutes to compile on the CPU.
"""

from __future__ import annotations

import copy
import json
import time
import types

from perfbench import harness

BENCH = harness.load_benchmark()
# Both mixes the harness has files for, with or without a BENCHMARK.json
# entry: (configuration, traffic mix, end-to-end metric and its unit).
CELLS = {
    "gossip-burst": ("mainnet-300k-allsubnets", "gossip-burst", "sets_per_s", "sets/s"),
    "block-batch": ("mainnet-300k-default", "block-batch", "block_verify_ms", "ms"),
}


def full_cell(workload: str):
    """The cell as its files define it, at full size."""
    config, traffic, metric, unit = CELLS[workload]
    cell = harness.SimpleNamespace(
        name=workload, chips=1,
        config=json.loads((harness.HERE / "configs" / f"{config}.json").read_text()),
        traffic=json.loads((harness.HERE / "traffic" / f"{traffic}.json").read_text()),
        per_layer=[])
    cell.end_to_end = [{"name": metric, "unit": unit},
                       {"name": "setup_s", "unit": "s"}]
    return cell


def tiny_cell(workload: str):
    cell = full_cell(workload)
    cell.config = copy.deepcopy(cell.config)
    cell.config["active_validators"] = 512  # 16 a slot: one committee of 16
    cell.config["materialized_validators"] = 32
    t = cell.traffic = copy.deepcopy(cell.traffic)
    t["check_sets"] = 4
    if t["driver"] == "coalescer":
        # batches of 4: a swapped pair in the first half of the first, in the
        # second half of the second
        t.update(slots=2, threads=2, queued_sets=16, lead_in_batches=2,
                 tamper={"swap_requests": [0, 6]})
    else:
        t["slots"] = 2
        t["warmup_requests"] = 1
        t["tamper"] = {**t["tamper"], "every": 2, "at": 0}
        for g in t["groups"]:
            if g["kind"] == "committee":
                g["count"] = 6
            if g["kind"] == "sample":
                g["keys"] = 8
    return cell


def ref_backend():
    from lighthouse_tpu.crypto import bls

    return bls.backend("ref")


def broken(bls, fault: str):
    """The backend with its batch verdict broken: `flip` negates it, `half`
    verifies only the first half of the batch."""
    ns = types.SimpleNamespace(**{k: getattr(bls, k) for k in dir(bls) if not k.startswith("__")})
    verify = bls.verify_signature_sets
    if fault == "flip":
        ns.verify_signature_sets = lambda sets, rng=None: not verify(sets, rng=rng)
    elif fault == "half":
        ns.verify_signature_sets = lambda sets, rng=None: verify(sets[: max(1, len(sets) // 2)], rng=rng)
    else:
        raise ValueError(fault)
    return ns


def run_tiny(workload: str, bls=None, seed: int = 2**31 + 17, control=None, trace=False, cell=None,
             seconds: float = 2.5):
    """One CPU run of a tiny cell through the harness (no chip, no
    compile), with coalesced batches of 4 sets. The window is long enough
    for a block with its tampered pair in each half."""
    from lighthouse_tpu.crypto.bls import batch_verifier as bv

    cell = cell or tiny_cell(workload)
    bls = bls or ref_backend()
    svc = bv.ensure_running(bls, s_bucket=4)  # drivers over the coalescer join it
    try:
        return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(), bls=bls,
                                control=control)
    finally:
        bv.release(svc)
