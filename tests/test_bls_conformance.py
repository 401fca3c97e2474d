"""BLS conformance matrix: all 7 eth2 case types against the ref oracle
(and the jax backend when LIGHTHOUSE_TPU_CONFORMANCE_JAX=1 — kept off the
default CI path because every kernel shape is a multi-minute cold XLA
compile on the 1-core CPU mesh; the shapes are exercised on the real chip
by chip_smoke.py and the benchmark, perfbench/).

The fake backend is deliberately excluded, as in the reference: its
verifications are unconditionally true (/root/reference/Makefile:102 runs
fake_crypto for state-transition vectors, not the bls runner).
"""

import os

import pytest

from lighthouse_tpu.conformance import ALL_CASE_TYPES, generate_bls_cases, run_case
from lighthouse_tpu.crypto import bls

CASES = generate_bls_cases()


def test_all_case_types_covered():
    assert {c.case_type for c in CASES} == set(ALL_CASE_TYPES)
    # every case type carries at least one negative/edge case
    for t in ALL_CASE_TYPES:
        of_type = [c for c in CASES if c.case_type == t]
        assert len(of_type) >= 3 or t == "sign"


@pytest.mark.parametrize("case", CASES, ids=lambda c: f"{c.case_type}-{c.name}")
def test_ref_backend(case):
    run_case(case, bls.backend("ref"))


_RUN_JAX = os.environ.get("LIGHTHOUSE_TPU_CONFORMANCE_JAX") == "1"


@pytest.mark.skipif(not _RUN_JAX, reason="set LIGHTHOUSE_TPU_CONFORMANCE_JAX=1 (compile-heavy)")
@pytest.mark.parametrize("case", CASES, ids=lambda c: f"{c.case_type}-{c.name}")
def test_jax_backend(case):
    run_case(case, bls.backend("jax"))
