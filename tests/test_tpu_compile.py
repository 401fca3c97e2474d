"""Compile the main path's kernels for a described TPU v5e chip.

No chip is needed: the TPU compiler is installed, and it compiles for a
chip that is described and not attached (on-chip-measurement guide §2).
What it refuses here — a program that does not fit the device's memory,
an op the TPU cannot lower — would otherwise cost a chip run to find.
Nothing runs, so these tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker imports
this file. The fast tier compiles the registered kernels (registry.py) at
the widths they take in the block bucket; the verify chain's stage
programs, up to a few minutes of compile each, are slow tier.
"""

import jax
import pytest

from lighthouse_tpu.crypto.bls.jax_backend import api, registry

HBM_BYTES = 16 * 2**30  # one v5e chip
# the block bucket: 128 committee aggregates x 128 keys + proposer, randao
S, K = api.batch_bucket(130, 128)

# (registered kernel, its batch dims in the block bucket, batch dims the
# registry's example args already carry)
CASES = [
    ("fp.mul", (S, K), 0),  # G1 pubkey aggregation: every (set, key) lane
    ("fp.mul_mxu", (S, K), 0),
    ("tower.fp2_mul", (S + 1,), 0),  # S Miller pairs + (-g1, sum r sig)
    ("tower.fp6_mul", (S + 1,), 0),
    ("tower.fp12_mul", (S + 1,), 0),
    ("tower.fp12_mul_sparse035", (S + 1,), 0),
    ("curve.add.g1", (S, K // 2), 0),  # first level of the pubkey tree fold
    ("curve.add.g2", (S // 2,), 0),  # first level of the signature fold
    ("curve.scalar_mul_bits.g1", (S,), 1),  # r_i * aggpk_i
    ("curve.scalar_mul_bits.g2", (2 * S,), 1),  # [|z|]sig and [r]sig, stacked
    ("curve.to_affine.g1", (S,), 1),
]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent cache off: a described-chip
    compile is written to it but cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.generated_code_size_in_bytes
        + m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
    )


@pytest.mark.parametrize("name,batch,lead", CASES, ids=[c[0] for c in CASES])
def test_kernel_compiles_for_one_v5e_chip(one_chip, name, batch, lead):
    spec = {s.name: s for s in registry.kernel_specs()}[name]
    fn, args, _ = spec.build()
    shaped = [
        jax.ShapeDtypeStruct(batch + a.shape[lead:], a.dtype, sharding=one_chip)
        for a in args
    ]
    compiled = jax.jit(fn).lower(*shaped).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def _compile_stage(one_chip, key):
    fn, specs = api.verify_stage(key)
    shaped = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), specs
    )
    return jax.jit(fn).lower(*shaped).compile()


def test_group_stage_compiles_for_one_v5e_chip(one_chip):
    """The grouped chain's own stage at the gossip bucket: 128 sets summed
    into 64 message rows."""
    compiled = _compile_stage(one_chip, ("group", 128, 64))
    assert 0 < _device_bytes(compiled) < HBM_BYTES


@pytest.mark.slow
@pytest.mark.parametrize(
    "chain",
    [*api.Chain.bucket(S, K)[:1], *api.Chain.bucket(*api.batch_bucket(128, 1))],
    ids=["block_256x128", "gossip_128x4", "gossip_128x4_grouped_64"],
)
def test_verify_stages_compile_for_one_v5e_chip(one_chip, chain):
    """Every stage program of a bucket's verify chain (api._VERIFY_CHAIN),
    or of its grouped chain (api._GROUPED_CHAIN: h2c and pairs at U rows)."""
    for key in chain.stage_keys():
        assert _device_bytes(_compile_stage(one_chip, key)) < HBM_BYTES, key
