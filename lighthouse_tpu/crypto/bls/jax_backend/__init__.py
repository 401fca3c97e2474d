"""jax BLS backend package: import-time environment guards.

The 12-bit-limb int32 kernels (fp.py) are proven overflow-safe by the
jaxpr interval analyzer (analysis/jaxpr_lint.py) under jax's DEFAULT
32-bit world.  With `jax_enable_x64` on, weakly-typed literals and
np->jnp conversions silently widen to int64, changing every width
assumption the proofs rest on (and hitting XLA's slow emulated 64-bit
path on TPU) — so an x64 interpreter is refused loudly at import instead
of producing subtly different kernels.

The backend is also the program's one home for JAX's persistent
compilation cache: each (S, K) verify bucket is minutes of TPU compile,
so a node that restarts must find them again. `JAX_COMPILATION_CACHE_DIR`
wins when set; otherwise the cache lives at the fixed path
`<checkout>/.jax_cache` (the path is part of the cache key, so it never
depends on a temp name, pid or time).
"""

import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[4] / ".jax_cache"


def assert_x64_disabled() -> None:
    """Fail fast if jax_enable_x64 is on (also re-checkable at runtime —
    tests call this under jax.enable_x64)."""
    if jax.config.jax_enable_x64:
        raise RuntimeError(
            "lighthouse_tpu's jax backend requires jax_enable_x64=False: "
            "the int32 limb kernels silently change width assumptions "
            "under x64, invalidating the analyzer's overflow proofs "
            "(analysis/jaxpr_lint.py). Unset JAX_ENABLE_X64 / call "
            "jax.config.update('jax_enable_x64', False) before importing "
            "the backend."
        )


assert_x64_disabled()
if not jax.config.jax_compilation_cache_dir:  # JAX_COMPILATION_CACHE_DIR unset
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
