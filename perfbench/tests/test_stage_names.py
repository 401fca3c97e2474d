"""The unpack program under its stable jit name (`_unpack_stage`) starts a
whole batch, as the `<lambda>` it replaced did."""

from types import SimpleNamespace as NS

import pytest

from perfbench import trace
from perfbench.harness import load_reader


def _ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


@pytest.mark.parametrize("unpack", ["jit__lambda_(1)", "jit__unpack_stage(1)"])
def test_a_chain_starts_with_either_unpack_name(unpack):
    chain = [unpack, "jit__keys_stage(2)", "jit__sigs_stage(3)",
             "jit_hash_to_g2_device(4)", "jit__pairs_stage(5)", "jit__final_stage(6)"]
    events = [_ev(name, t0 + 10 * k, t0 + 10 * k + 8) for t0 in (-30, 100, 300)
              for k, name in enumerate(chain)]
    pd = NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=[_ev("window", 0, 340)])]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=events)]),
    ])
    r = trace.reduce(pd)
    assert trace.stage_of(unpack, trace.stage_patterns()) == "unpack"
    stages = ("unpack", "keys", "sigs", "h2c", "pairs", "final")
    assert r["batches"] == [{s: pytest.approx(8e-9) for s in stages}]
    m = NS(trace=r)
    assert load_reader("device_ms_per_batch.gossip").read(m) == pytest.approx(48e-6)
    assert load_reader("keys_stage_ms.gossip").read(m) == pytest.approx(8e-6)
