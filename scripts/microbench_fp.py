"""Microbenchmark: where does a scan step's time go on the real chip?

Times a 64-step lax.scan of Montgomery multiplies at several batch widths.
If step time is flat across widths, the kernel is per-step-overhead-bound
(fix: fewer/fatter steps); if it scales ~linearly, it is VPU/memory-bound
(fix: layout/Pallas work on the field ops themselves).
"""

import pathlib
import statistics
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))

import numpy as np


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lighthouse_tpu.crypto.bls.jax_backend import fp

    print(f"platform={jax.default_backend()}")
    rng = np.random.default_rng(0)

    @jax.jit
    def scan_mul(a, b):
        def step(acc, _):
            return fp.mul(acc, b), None

        out, _ = lax.scan(step, a, None, length=64)
        return out

    @jax.jit
    def scan_fp12_sqr(f):
        from lighthouse_tpu.crypto.bls.jax_backend.tower import fp12_sqr

        def step(acc, _):
            return fp12_sqr(acc), None

        out, _ = lax.scan(step, f, None, length=64)
        return out

    for B in (32, 128, 512, 2048):
        a = jnp.asarray(rng.integers(0, 4096, size=(B, 32), dtype=np.int32))
        b = jnp.asarray(rng.integers(0, 4096, size=(B, 32), dtype=np.int32))
        jax.block_until_ready(scan_mul(a, b))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(scan_mul(a, b))
            ts.append(time.perf_counter() - t0)
        t = statistics.median(ts)
        print(f"fp.mul scan64 B={B:5d}: {t * 1e3:8.2f} ms  ({t / 64 * 1e6:7.1f} us/step)")

    for B in (8, 32, 128):
        f = jnp.asarray(
            rng.integers(0, 4096, size=(B, 2, 3, 2, 32), dtype=np.int32)
        )
        jax.block_until_ready(scan_fp12_sqr(f))
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(scan_fp12_sqr(f))
            ts.append(time.perf_counter() - t0)
        t = statistics.median(ts)
        print(f"fp12_sqr scan64 B={B:5d}: {t * 1e3:8.2f} ms  ({t / 64 * 1e6:7.1f} us/step)")

    # -- transposed-layout prototype: batch on the minor (lane) axis ----------
    # Hypothesis: (B, 32) puts 32 limbs on the 128-lane axis (25% full);
    # (32, B) puts the batch there (100% at B>=128).

    def poly_T(aT, bT):
        outer = aT[:, None, :] * bT[None, :, :]  # (32, 32, B)
        padded = jnp.pad(outer, [(0, 0), (0, 32), (0, 0)])
        flat = padded.reshape(32 * 64, -1)[: 32 * 64 - 32]
        skew = flat.reshape(32, 63, -1)
        return jnp.sum(skew, axis=0)  # (63, B)

    def pass1_T(cols):
        c = cols >> 12
        return (cols & 0xFFF) + jnp.pad(c, [(1, 0), (0, 0)])[:-1]

    def carry_T(cols):
        v = cols
        carry_out = jnp.zeros(v.shape[1:], jnp.int32)
        for _ in range(3):
            c = v >> 12
            v = (v & 0xFFF) + jnp.pad(c, [(1, 0), (0, 0)])[:-1]
            carry_out = carry_out + c[-1]
        fneg = (v - 1) >> 12
        f0 = v >> 12
        fpos = (v + 1) >> 12
        F = jnp.stack([fneg, f0, fpos], axis=0)  # (3, K, B)
        K = F.shape[1]
        ident = jnp.broadcast_to(jnp.array([-1, 0, 1], np.int32)[:, None, None], F.shape)
        d = 1
        while d < K:
            earlier = jnp.concatenate([ident[:, :d], F[:, :-d]], axis=1)
            rm1, r0, rp1 = F[0:1], F[1:2], F[2:3]
            F = jnp.where(earlier == -1, rm1, jnp.where(earlier == 0, r0, rp1))
            d *= 2
        zero_in = F[1]
        c_in = jnp.pad(zero_in, [(1, 0), (0, 0)])[:-1]
        return (v + c_in) & 0xFFF, carry_out + zero_in[-1]

    P_L = jnp.asarray(fp.P_LIMBS)[:, None]
    NP_L = jnp.asarray(fp.N_PRIME_LIMBS)[:, None]

    def redc_T(cols):  # cols (63 or 64, B), simplified mult=2 tail
        cols = jnp.pad(cols, [(0, 64 - cols.shape[0]), (0, 0)])
        lo = pass1_T(pass1_T(cols[:32]))
        m = pass1_T(pass1_T(poly_T(lo, NP_L)[:32]))
        t_all = cols + jnp.pad(poly_T(m, P_L), [(0, 1), (0, 0)])[:64]
        t, _ = carry_T(t_all)
        return t[32:]

    def mul_T(aT, bT):
        return redc_T(poly_T(aT, bT))

    @jax.jit
    def scan_mul_T(aT, bT):
        def step(acc, _):
            return mul_T(acc, bT), None

        out, _ = lax.scan(step, aT, None, length=64)
        return out

    for B in (32, 128, 512, 2048):
        aT = jnp.asarray(rng.integers(0, 4096, size=(32, B), dtype=np.int32))
        bT = jnp.asarray(rng.integers(0, 4096, size=(32, B), dtype=np.int32))
        jax.block_until_ready(scan_mul_T(aT, bT))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(scan_mul_T(aT, bT))
            ts.append(time.perf_counter() - t0)
        t = statistics.median(ts)
        print(f"mul_T scan64  B={B:5d}: {t * 1e3:8.2f} ms  ({t / 64 * 1e6:7.1f} us/step)")


if __name__ == "__main__":
    main()
