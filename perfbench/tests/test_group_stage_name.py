"""The grouped chain's `group` program (jit name `_group_stage`) is a stage
of its own inside a whole batch, between unpack and final."""

from types import SimpleNamespace as NS

import pytest

from perfbench import trace
from perfbench.harness import load_reader


def _ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def test_a_grouped_chain_is_one_whole_batch():
    chain = ["jit__unpack_stage(1)", "jit__keys_stage(2)", "jit__sigs_stage(3)",
             "jit_hash_to_g2_device(4)", "jit__group_stage(5)", "jit__pairs_stage(6)",
             "jit__final_stage(7)"]
    events = [_ev(name, t0 + 10 * k, t0 + 10 * k + 8) for t0 in (-30, 100, 300)
              for k, name in enumerate(chain)]
    pd = NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=[_ev("window", 0, 380)])]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=events)]),
    ])
    r = trace.reduce(pd)
    assert trace.stage_of("jit__group_stage(4)", trace.stage_patterns()) == "group"
    stages = ("unpack", "keys", "sigs", "h2c", "group", "pairs", "final")
    assert r["batches"] == [{s: pytest.approx(8e-9) for s in stages}] * 2
    m = NS(trace=r)
    assert load_reader("device_ms_per_batch.gossip").read(m) == pytest.approx(56e-6)
