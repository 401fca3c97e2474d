"""Stage-level device profile of the 128x1 verify kernel.

The round-4 profile said ~590 ms of the 128-batch wall time is device
execute, but scan-step microbenchmarks (scripts/microbench_fp.py) price the
sequential arithmetic at single-digit milliseconds. This script times each
pipeline stage as its OWN jitted program (real block_until_ready syncs) so
the gap is attributable:

  - if the stage times sum to ~the full-kernel time, some stage's math is
    genuinely slow -> optimize that stage;
  - if the stages are all fast but the full verify chain is slow, the cost
    is program-level (e.g. straight-line code blowing TPU instruction
    memory) -> restructure into loops / split dispatches.

Run: python scripts/profile_stages.py   (on the bench platform)
     python scripts/profile_stages.py --coalesce
         concurrent-submitter profile of the cross-caller BatchVerifier
         (crypto/bls/batch_verifier.py) through the same span tracer:
         dispatch count vs caller count, coalesced batch sizes, waits.
         Env: PROFILE_COALESCE_CALLERS (64), PROFILE_COALESCE_ROUNDS (2).
     python scripts/profile_stages.py --staging
         host staging fast-path profile (stage_sets): cold caches vs warm
         on a repeated-message batch, per-stage span breakdown
         (bls_stage/bls_pack/bls_h2c_host) and the staging-cache hit/miss
         counters a /metrics scrape would show. Host-only — no device
         kernels run. Env: PROFILE_STAGING_SETS (64),
         PROFILE_STAGING_MSGS (8), PROFILE_REPS (5).
     python scripts/profile_stages.py --kernel
         fast-kernel-algebra stage split, pinned to CPU (matching
         `bench.py --kernel`): windowed scalar-mul vs Montgomery ladder,
         Karabina compressed pow_abs_x vs plain Fp12 square-and-multiply,
         batch-inversion affine conversion vs per-group to_affine — each
         its own jitted program, output-checked before timing.
         Env: PROFILE_KERNEL_SETS (8), PROFILE_REPS (5).
     python scripts/profile_stages.py --slot
         slot-SLO ledger budget table: runs a fake-backend harness chain
         for a few slots and prints the per-stage slot-budget attribution
         (common.slot_ledger) next to the raw span breakdown. Env:
         PROFILE_SLOT_VALIDATORS (16), PROFILE_SLOTS (8).
     python scripts/profile_stages.py --opcounts
         per-kernel jaxpr primitive counts from the analyzer registry
         (trace-only, no device) next to the committed budget baseline —
         op-count deltas read side by side with the wall-time deltas the
         other modes print. Standalone: fast tier only by default
         (PROFILE_OPCOUNTS_TIER=all adds the slow composites). Combined
         with the default device profile, the table prints after the span
         breakdown so one run shows both.
"""

import os
import pathlib
import statistics
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))


N_SETS = int(os.environ.get("PROFILE_N_SETS", "128"))
REPS = int(os.environ.get("PROFILE_REPS", "5"))


def print_stage_table(
    report,
    title="span-derived per-stage breakdown (common.tracing):",
    width=22,
):
    """THE stage-table printer every mode shares. Rows are
    {stage: {count, total_s, mean_s}} — the exact shape both
    TRACER.stage_report() and SlotLedger.stage_report() emit, so the span
    breakdown and the --slot ledger budget table render identically."""
    print(f"\n{title}", flush=True)
    for stage, rec in report.items():
        print(
            f"  {stage:{width}s} n={rec['count']:3d}"
            f"  mean={rec['mean_s'] * 1e3:9.2f} ms"
            f"  total={rec['total_s'] * 1e3:9.2f} ms",
            flush=True,
        )


def med(fn, label, reps=REPS):
    """Median of `reps` timed calls, each also recorded as a tracing span
    `label` — so the tracer/metrics breakdown printed at the end reports the
    SAME measurements as the medians below (bench rounds and the Prometheus
    scrape can no longer disagree about per-stage cost)."""
    from lighthouse_tpu.common.tracing import span

    fn()  # warm (compile) — deliberately NOT recorded as a span
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        with span(label):
            fn()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def coalesce_main() -> None:
    """--coalesce: the concurrent-submitter scenario through the PR-1 span
    tracer — N threads each submitting single sets to the BatchVerifier,
    reported via the same spans/metrics a /metrics scrape would show
    (coalesced batch sizes, waits, dispatch count, per-stage breakdown)."""
    import threading

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from lighthouse_tpu.common.metrics import (
        BLS_COALESCE_WAIT_SECONDS,
        BLS_COALESCED_BATCH_SIZE,
    )
    from lighthouse_tpu.common.tracing import TRACER, span
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls.batch_verifier import BatchVerifier

    n_callers = int(os.environ.get("PROFILE_COALESCE_CALLERS", "64"))
    rounds = int(os.environ.get("PROFILE_COALESCE_ROUNDS", "2"))
    b = bls.backend("jax")
    pairs = [b.interop_keypair(i) for i in range(8)]
    sets = []
    for i in range(n_callers):
        sk, pk = pairs[i % 8]
        msg = bytes([i % 8]) * 32
        sets.append(b.SignatureSet(signature=sk.sign(msg), signing_keys=[pk], message=msg))

    print(f"platform={jax.default_backend()} callers={n_callers} rounds={rounds}",
          flush=True)
    # warm the kernel buckets outside the measurement: S=4 (single-set
    # dispatches) AND the full-caller bucket — coalesced batches land on
    # intermediate pow2 buckets too, but these two bound the common cases
    # (a cold cache may still compile an intermediate shape in-window)
    assert b.verify_signature_sets(sets[:1])
    assert b.verify_signature_sets(sets)

    svc = BatchVerifier(b).start()
    try:
        t0 = time.perf_counter()

        def caller(s):
            for _ in range(rounds):
                with span("bls_coalesced_submit"):
                    ok = svc.submit([s]).result(timeout=600.0)[0]
                assert ok

        threads = [threading.Thread(target=caller, args=(s,)) for s in sets]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sec = time.perf_counter() - t0
    finally:
        svc.stop()

    total = n_callers * rounds
    print(f"total sets               {total}", flush=True)
    print(f"device dispatches        {svc.dispatches}  "
          f"(uncoalesced path would pay {total})", flush=True)
    print(f"throughput               {total / sec:9.2f} sets/s", flush=True)
    print(f"mean coalesced batch     "
          f"{svc.sets_coalesced / max(1, svc.dispatches):9.2f} sets", flush=True)
    if BLS_COALESCE_WAIT_SECONDS.count:
        print(f"mean coalesce wait       "
              f"{BLS_COALESCE_WAIT_SECONDS.sum / BLS_COALESCE_WAIT_SECONDS.count * 1e3:9.2f} ms",
              flush=True)
    print(f"batch-size histogram n   {BLS_COALESCED_BATCH_SIZE.count}", flush=True)

    print_stage_table(TRACER.stage_report())


def staging_main() -> None:
    """--staging: cold vs warm host staging through the span tracer and the
    lighthouse_tpu_bls_staging_cache_{hits,misses}_total counters."""
    import statistics as stats

    from lighthouse_tpu.common.metrics import (
        BLS_STAGE_SECONDS,
        BLS_STAGING_CACHE_HITS_TOTAL,
        BLS_STAGING_CACHE_MISSES_TOTAL,
    )
    from lighthouse_tpu.common.tracing import TRACER
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls.jax_backend import api as japi

    n_sets = int(os.environ.get("PROFILE_STAGING_SETS", "64"))
    distinct = int(os.environ.get("PROFILE_STAGING_MSGS", "8"))
    b = bls.backend("jax")
    pairs = [b.interop_keypair(i) for i in range(n_sets)]
    sets = []
    for i, (sk, pk) in enumerate(pairs):
        msg = bytes([i % distinct]) * 32
        sets.append(b.SignatureSet(signature=sk.sign(msg), signing_keys=[pk], message=msg))
    print(f"n_sets={n_sets} distinct_messages={distinct} (host-only profile)",
          flush=True)

    def counters():
        out = {}
        for cache in ("pk_limbs", "sig_limbs", "h2c"):
            out[cache] = (
                BLS_STAGING_CACHE_HITS_TOTAL.labels(cache=cache).value,
                BLS_STAGING_CACHE_MISSES_TOTAL.labels(cache=cache).value,
            )
        return out

    c0 = counters()
    colds, warms = [], []
    for _ in range(REPS):
        japi.drop_staging_caches(sets)
        t0 = time.perf_counter()
        japi.stage_sets(sets)
        colds.append(time.perf_counter() - t0)
        japi.stage_sets(sets)  # fully warm
        t0 = time.perf_counter()
        japi.stage_sets(sets)
        warms.append(time.perf_counter() - t0)
    cold, warm = stats.median(colds), stats.median(warms)
    c1 = counters()

    print(f"cold stage_sets          {cold * 1e3:9.2f} ms", flush=True)
    print(f"warm stage_sets          {warm * 1e3:9.2f} ms", flush=True)
    print(f"warm/cold speedup        {cold / warm:9.2f} x", flush=True)
    print(f"bls_stage histogram n    {BLS_STAGE_SECONDS.count}", flush=True)
    print("\nstaging cache counters (this profile's delta):", flush=True)
    for cache in ("pk_limbs", "sig_limbs", "h2c"):
        dh = c1[cache][0] - c0[cache][0]
        dm = c1[cache][1] - c0[cache][1]
        print(f"  {cache:10s} hits={dh:8.0f}  misses={dm:8.0f}", flush=True)

    print_stage_table(TRACER.stage_report())


def kernel_main() -> None:
    """--kernel: stage split of the fast-kernel-algebra rewrites, pinned to
    the CPU platform (matching `bench.py --kernel`): windowed scalar-mul vs
    the Montgomery ladder, Karabina compressed `_pow_abs_x` vs the plain
    Fp12 square-and-multiply chain, and shared-batch-inversion affine
    conversion vs per-group `to_affine`, each as its own jitted program.
    Every pair is output-checked before it is timed. Env: PROFILE_KERNEL_SETS
    (8), PROFILE_REPS (5)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from lighthouse_tpu.common.tracing import TRACER
    from lighthouse_tpu.crypto.bls.jax_backend import curve as cv
    from lighthouse_tpu.crypto.bls.jax_backend import fp, pack, pairing
    from lighthouse_tpu.crypto.bls.jax_backend.tower import fp12_mul, fp12_sqr, fp2_mul
    from lighthouse_tpu.crypto.bls.ref.curves import g1_generator, g2_generator
    from lighthouse_tpu.crypto.bls.ref.pairing import pairing as ref_pairing

    S = int(os.environ.get("PROFILE_KERNEL_SETS", "8"))
    print(f"platform={jax.default_backend()} n_points={S} (kernel-algebra split)",
          flush=True)

    g1s = [g1_generator().mul(3 + 5 * i) for i in range(S)]
    x, y, inf = (jnp.asarray(a) for a in pack.pack_g1_batch(g1s))
    P = cv.from_affine(cv.FP, x, y, inf)
    bits = jnp.asarray(np.random.default_rng(0).integers(0, 2, size=(S, 64), dtype=np.int32))

    windowed = jax.jit(lambda p, r: cv.scalar_mul_bits(cv.FP, p, r))
    ladder = jax.jit(lambda p, r: cv.scalar_mul_bits_ladder(cv.FP, p, r))
    w_aff = cv.to_affine(cv.FP, windowed(P, bits))
    l_aff = cv.to_affine(cv.FP, ladder(P, bits))
    assert all(np.array_equal(a, b) for a, b in zip(map(np.asarray, w_aff), map(np.asarray, l_aff)))
    t_w = med(lambda: jax.block_until_ready(windowed(P, bits)), "kernel_scalar_mul_windowed")
    t_l = med(lambda: jax.block_until_ready(ladder(P, bits)), "kernel_scalar_mul_ladder")
    print(f"scalar-mul windowed       {t_w * 1e3:9.2f} ms", flush=True)
    print(f"scalar-mul ladder         {t_l * 1e3:9.2f} ms   ({t_l / t_w:.2f}x)", flush=True)

    e = jnp.asarray(pack.pack_fp12_el(ref_pairing(g1_generator(), g2_generator())))

    def naive_pow(gg):
        acc = gg
        for bit in pairing._ABS_X_BITS_MSB[1:]:
            acc = fp12_sqr(acc)
            if bit:
                acc = fp12_mul(acc, gg)
        return acc

    kar = jax.jit(pairing._pow_abs_x)
    naive = jax.jit(naive_pow)
    assert np.array_equal(np.asarray(kar(e)), np.asarray(naive(e)))
    t_k = med(lambda: jax.block_until_ready(kar(e)), "kernel_pow_abs_x_karabina")
    t_n = med(lambda: jax.block_until_ready(naive(e)), "kernel_pow_abs_x_sqr_mul")
    print(f"final-exp chain karabina  {t_k * 1e3:9.2f} ms", flush=True)
    print(f"final-exp chain sqr-mul   {t_n * 1e3:9.2f} ms   ({t_n / t_k:.2f}x)", flush=True)

    g2s = [g2_generator().mul(2 + 3 * i) for i in range(S + 1)]
    qx, qy, qinf = (jnp.asarray(a) for a in pack.pack_g2_batch(g2s))
    Q = jax.jit(lambda a, b, c: cv.dbl(cv.FP2, cv.from_affine(cv.FP2, a, b, c)))(qx, qy, qinf)
    P2 = jax.jit(lambda p: cv.dbl(cv.FP, p))(P)

    def separate(p1, q2):
        return cv.to_affine(cv.FP, p1), cv.to_affine(cv.FP2, q2)

    def shared(p1, q2):
        z0, z1 = q2.z[..., 0, :], q2.z[..., 1, :]
        zsq = fp.sqr(jnp.stack([z0, z1]))
        dens = jnp.concatenate([p1.z, fp.add(zsq[0], zsq[1])], axis=0)
        inv_all = fp.batch_inv(dens)
        g1_aff = fp.mul(jnp.stack([p1.x, p1.y]), jnp.broadcast_to(inv_all[:S], (2, S, fp.N_LIMBS)))
        nm = fp.mul(jnp.stack([z0, z1]), jnp.broadcast_to(inv_all[S:], (2, S + 1, fp.N_LIMBS)))
        zinv2 = jnp.stack([nm[0], fp.neg(nm[1])], axis=-2)
        g2_aff = fp2_mul(jnp.stack([q2.x, q2.y]), jnp.broadcast_to(zinv2, (2, S + 1, 2, fp.N_LIMBS)))
        return g1_aff, g2_aff

    sep = jax.jit(separate)
    shr = jax.jit(shared)
    (p_ax, p_ay, _), (q_ax, q_ay, _) = sep(P2, Q)
    g1_aff, g2_aff = shr(P2, Q)
    assert np.array_equal(np.asarray(g1_aff), np.stack([np.asarray(p_ax), np.asarray(p_ay)]))
    assert np.array_equal(np.asarray(g2_aff), np.stack([np.asarray(q_ax), np.asarray(q_ay)]))
    t_s = med(lambda: jax.block_until_ready(shr(P2, Q)), "kernel_to_affine_batch_inv")
    t_p = med(lambda: jax.block_until_ready(sep(P2, Q)), "kernel_to_affine_separate")
    print(f"to-affine batch_inv       {t_s * 1e3:9.2f} ms", flush=True)
    print(f"to-affine separate        {t_p * 1e3:9.2f} ms   ({t_p / t_s:.2f}x)", flush=True)

    print_stage_table(TRACER.stage_report(), width=28)


def print_opcounts() -> None:
    """--opcounts: the analyzer registry's per-kernel primitive counts vs
    the committed baseline (scripts/jaxpr_budgets.json) — the compile-cost
    side of the profile (trace-only; pairs with the wall-time numbers)."""
    from lighthouse_tpu.analysis import jaxpr_lint
    from lighthouse_tpu.crypto.bls.jax_backend import registry

    tiers = (
        ("fast", "slow")
        if os.environ.get("PROFILE_OPCOUNTS_TIER") == "all"
        else ("fast",)
    )
    budgets = jaxpr_lint.load_budgets()
    print(
        f"\nper-kernel jaxpr primitive counts (tiers={'+'.join(tiers)}; "
        f"baseline scripts/jaxpr_budgets.json):",
        flush=True,
    )
    print(f"  {'kernel':34s} {'eqns':>7s} {'budget':>7s} {'delta':>7s}  top primitives")
    for spec in registry.kernel_specs(tiers=tiers):
        t0 = time.perf_counter()
        closed, _seeds = jaxpr_lint.trace_kernel(spec)
        counts = jaxpr_lint.count_primitives(closed)
        trace_s = time.perf_counter() - t0
        base = budgets.get(spec.name, {}).get("eqns")
        delta = "" if base is None else f"{counts['eqns'] - base:+7d}"
        budget = "-" if base is None else str(base)
        top = sorted(counts["by_prim"].items(), key=lambda kv: -kv[1])[:3]
        top_s = " ".join(f"{k}:{v}" for k, v in top)
        print(
            f"  {spec.name:34s} {counts['eqns']:7d} {budget:>7s} {delta:>7s}"
            f"  {top_s}  (trace {trace_s:.1f}s)",
            flush=True,
        )


def slot_main() -> None:
    """Slot-SLO ledger budget table: drive a harness chain for a few slots
    on the fake backend and print the per-stage slot-budget attribution
    (common.slot_ledger) next to the raw span breakdown, through the one
    shared table printer. Env: PROFILE_SLOT_VALIDATORS (16),
    PROFILE_SLOTS (8)."""
    from lighthouse_tpu.chain import BeaconChainHarness
    from lighthouse_tpu.common.tracing import TRACER
    from lighthouse_tpu.state_transition import TransitionContext

    n_val = int(os.environ.get("PROFILE_SLOT_VALIDATORS", "16"))
    n_slots = int(os.environ.get("PROFILE_SLOTS", "8"))

    h = BeaconChainHarness(n_val, TransitionContext.minimal("fake"))
    h.extend_chain(n_slots)
    led = h.chain.slot_ledger
    led.close()  # close the final window so every slot has a record

    records = led.records()
    missed = sum(1 for r in records if r["deadline_missed"])
    wall = sum(r["wall_seconds"] for r in records)
    print(
        f"slots={len(records)}  validators={n_val}  "
        f"budget={led.seconds_per_slot:.1f}s/slot  "
        f"wall={wall * 1e3:9.2f} ms  deadline_misses={missed}",
        flush=True,
    )
    print_stage_table(TRACER.stage_report())
    print_stage_table(
        led.stage_report(),
        title="slot-ledger per-stage budget attribution (common.slot_ledger):",
    )


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls.jax_backend import api as japi
    from lighthouse_tpu.crypto.bls.jax_backend import h2c, pairing
    from lighthouse_tpu.crypto.bls.jax_backend.curve import (
        FP,
        FP2,
        Proj,
        _stack2,
        add as p_add,
        eq_points,
        from_affine,
        is_infinity,
        neg as p_neg,
        psi,
        scalar_mul_bits,
        to_affine,
    )
    from lighthouse_tpu.crypto.bls.jax_backend.pack import G1_GEN_X_L, G1_GEN_NEG_Y_L
    from jax import lax

    b = bls.backend("jax")
    pairs = [b.interop_keypair(i) for i in range(8)]
    sets = []
    for i in range(N_SETS):
        sk, pk = pairs[i % 8]
        msg = bytes([i % 8]) * 32
        sets.append(b.SignatureSet(signature=sk.sign(msg), signing_keys=[pk], message=msg))

    print(f"platform={jax.default_backend()} n_sets={N_SETS}", flush=True)
    staged = japi.stage_sets(sets)
    S, K = staged[2].shape
    pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, u, r_bits = (jnp.asarray(a) for a in staged)
    jax.block_until_ready(pk_x)

    # -- stage 1: hash to G2 ---------------------------------------------------
    h2g = jax.jit(lambda uu: h2c.hash_to_g2_device(uu))
    t_h2c = med(lambda: jax.block_until_ready(h2g(u)), "bls_h2c")
    print(f"stage h2c                 {t_h2c * 1e3:9.2f} ms", flush=True)
    H = h2g(u)

    # -- stage 2: ladders + folds (pipeline steps 2-5) -------------------------
    def ladders(pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, r_bits):
        pks = from_affine(FP, pk_x, pk_y, pk_inf)
        agg = Proj(pks.x[:, 0], pks.y[:, 0], pks.z[:, 0])
        agg_inf = is_infinity(FP, agg)
        r_pk = scalar_mul_bits(FP, agg, r_bits)
        sigs = from_affine(FP2, sig_x, sig_y, sig_inf)
        absx = jnp.broadcast_to(jnp.asarray(pairing._ABS_X_BITS_MSB[-64:]), r_bits.shape)
        both = scalar_mul_bits(FP2, _stack2(FP2, sigs, sigs), jnp.stack([absx, r_bits]))
        zsig = Proj(both.x[0], both.y[0], both.z[0])
        rsig = Proj(both.x[1], both.y[1], both.z[1])
        sub_ok = eq_points(FP2, psi(sigs), p_neg(FP2, zsig)) | is_infinity(FP2, sigs)

        first = Proj(rsig.x[0], rsig.y[0], rsig.z[0])

        def fold2(acc, nxt):
            return p_add(FP2, acc, nxt), None

        rest = Proj(rsig.x[1:], rsig.y[1:], rsig.z[1:])
        sig_acc, _ = lax.scan(fold2, first, rest)
        return r_pk, sig_acc, sub_ok, agg_inf

    lad = jax.jit(ladders)
    t_lad = med(
        lambda: jax.block_until_ready(lad(pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, r_bits)),
        "bls_ladders",
    )
    print(f"stage ladders+folds       {t_lad * 1e3:9.2f} ms", flush=True)
    r_pk, sig_acc, sub_ok, agg_inf = lad(pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, r_bits)

    # -- stage 3: to_affine + miller + product tree ----------------------------
    def miller(r_pk, H, sig_acc):
        pk_ax, pk_ay, pk_ainf = to_affine(FP, r_pk)
        h_ax, h_ay, h_ainf = to_affine(FP2, H)
        sa_x, sa_y, sa_inf = to_affine(FP2, sig_acc)
        px = jnp.concatenate([pk_ax, jnp.asarray(G1_GEN_X_L)[None]], axis=0)
        py = jnp.concatenate([pk_ay, jnp.asarray(G1_GEN_NEG_Y_L)[None]], axis=0)
        p_in = jnp.concatenate([pk_ainf, jnp.zeros(1, bool)])
        qx = jnp.concatenate([h_ax, sa_x[None]], axis=0)
        qy = jnp.concatenate([h_ay, sa_y[None]], axis=0)
        q_in = jnp.concatenate([h_ainf, sa_inf[None]])
        f = pairing.miller_loop(px, py, p_in, qx, qy, q_in)
        return pairing.product_reduce(f)

    mil = jax.jit(miller)
    t_mil = med(lambda: jax.block_until_ready(mil(r_pk, H, sig_acc)), "bls_miller")
    print(f"stage affine+miller+tree  {t_mil * 1e3:9.2f} ms", flush=True)
    partial = mil(r_pk, H, sig_acc)

    # -- stage 4: final exponentiation ----------------------------------------
    fe = jax.jit(pairing.final_exponentiation)
    t_fe = med(lambda: jax.block_until_ready(fe(partial)), "bls_final_exp")
    print(f"stage final_exp           {t_fe * 1e3:9.2f} ms", flush=True)

    # -- the full verify chain (api._VERIFY_CHAIN) ------------------------------
    flat = jnp.asarray(japi._pack_staged(staged))
    kernel = japi._verify_kernel(S, K)
    t_full = med(lambda: jax.block_until_ready(kernel(flat)), "bls_full_kernel")
    print(f"full verify chain         {t_full * 1e3:9.2f} ms", flush=True)
    print(
        f"sum of stages             {(t_h2c + t_lad + t_mil + t_fe) * 1e3:9.2f} ms",
        flush=True,
    )

    # -- span-derived breakdown ------------------------------------------------
    # the same numbers the tracer feeds lighthouse_tpu_stage_seconds{stage=}
    # (stage_sets' host-side bls_pack/bls_h2c_host spans appear too), so a
    # bench round and a /metrics scrape attribute identically
    from lighthouse_tpu.common.tracing import TRACER

    print_stage_table(TRACER.stage_report())

    # op-count deltas next to the wall-time deltas above (one run, both axes)
    if "--opcounts" in sys.argv:
        print_opcounts()


if __name__ == "__main__":
    if "--coalesce" in sys.argv:
        coalesce_main()
    elif "--staging" in sys.argv:
        staging_main()
    elif "--kernel" in sys.argv:
        kernel_main()
    elif "--slot" in sys.argv:
        slot_main()
    elif sys.argv[1:] == ["--opcounts"]:
        print_opcounts()
    else:
        main()  # appends the opcounts table when --opcounts is also passed
