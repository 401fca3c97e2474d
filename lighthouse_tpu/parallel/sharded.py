"""Multi-chip sharded BLS batch verification over a `jax.sharding.Mesh`.

The TPU-native analogue of the reference's rayon chunking of
`verify_signature_sets` across cores (/root/reference/consensus/
state_processing/src/per_block_processing/block_signature_verifier.rs:333-361):
signature sets are sharded over the mesh's `sets` axis with `shard_map`; each
chip runs the full local pipeline (hash-to-G2, subgroup checks, RLC ladders,
local Miller loops, local (-g1, sum_local r*sig) pair) and produces ONE Fp12
partial product plus a bool flag. Cross-chip communication is a single
all-gather of those ~3 KB partials over ICI, then every chip performs the
same final exponentiation (replicated — cheaper than an extra collective) and
ANDs the gathered flags.

This is SURVEY.md §2.8 item 1: partial pairing products reduce across chips,
one final exponentiation per global batch.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

SETS_AXIS = "sets"


def make_mesh(n_devices: int | None = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    return Mesh(np.array(devs[:n]), (SETS_AXIS,))


def build_sharded_verify(mesh: Mesh):
    """Compile a sharded verify kernel bound to `mesh`. Input arrays are
    sharded on their leading (sets) axis; S must divide by mesh size."""
    from ..crypto.bls.jax_backend.api import verify_pipeline_local
    from ..crypto.bls.jax_backend import pairing
    from ..crypto.bls.jax_backend.tower import fp12_is_one, fp12_mul

    spec = P(SETS_AXIS)
    rep = P()

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(spec,) * 8,
        out_specs=rep,
        check_vma=False,
    )
    def kernel(pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, u, r_bits):
        local, ok_local = verify_pipeline_local(
            pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, u, r_bits
        )
        # One ~3 KB Fp12 per chip crosses the ICI; the GT product and final
        # exponentiation are replicated on every chip.
        partials = lax.all_gather(local, SETS_AXIS)  # (n_dev, 2, 3, 2, 32)
        total = pairing.product_reduce(partials)
        gt = pairing.final_exponentiation(total)
        flags = lax.all_gather(ok_local, SETS_AXIS)
        return (fp12_is_one(gt) & jnp.all(flags))[None]

    return jax.jit(lambda *a: kernel(*a)[0])


def sharded_verify_signature_sets(sets, mesh: Mesh | None = None, rng=None) -> bool:
    """verify_signature_sets semantics, executed across every device of the
    mesh. Host staging is identical to the single-chip path."""
    from ..crypto.bls.jax_backend import api as japi

    if not japi._structurally_valid(sets):
        return False

    mesh = mesh or make_mesh()
    n = mesh.devices.size
    staged = japi.stage_sets(sets, rng=rng, s_floor=n)
    kernel = _kernel_cache(mesh, staged[0].shape[0], staged[0].shape[1])
    return bool(kernel(*(jnp.asarray(a) for a in staged)))


_KERNELS: dict = {}


def _kernel_cache(mesh: Mesh, S: int, K: int):
    # Key on the mesh's CONTENT, not id(mesh): a GC'd mesh's id can be
    # reused by a new mesh over different devices, which would serve a
    # kernel compiled for (and sharded across) the wrong device set.
    key = (
        tuple(d.id for d in mesh.devices.flat),
        mesh.axis_names,
        S,
        K,
    )
    if key not in _KERNELS:
        _KERNELS[key] = build_sharded_verify(mesh)
    return _KERNELS[key]
