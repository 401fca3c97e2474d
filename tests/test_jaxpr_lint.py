"""The jaxpr kernel analyzer's own gate: seeded-bad fixtures prove each
analysis catches its bug class WITH eqn-level source provenance, known-good
fixtures stay quiet, the budget machinery fails on regressions/staleness,
the x64 import guard refuses a widened interpreter — and, the tier-1
teeth, the fast-tier registry kernels are PROVEN int32-overflow-free from
the canonical-limb precondition against the committed op-count baseline.
Everything here is trace-only (jax.make_jaxpr): no compilation, no device.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from lighthouse_tpu.analysis import jaxpr_lint
from lighthouse_tpu.crypto.bls.jax_backend import registry
from lighthouse_tpu.crypto.bls.jax_backend.registry import KernelSpec

REPO_ROOT = Path(__file__).resolve().parent.parent
THIS_FILE = Path(__file__).resolve().relative_to(REPO_ROOT).as_posix()

LIMB12 = (0, (1 << 12) - 1)
LIMB13 = (0, (1 << 13) - 1)


def analyze_fixture(fn, args, ranges, integer_only=True, name="fixture"):
    spec = KernelSpec(
        name=name,
        tier="fast",
        build=lambda: (fn, args, ranges),
        integer_only=integer_only,
        module=__name__,
    )
    closed, seeds = jaxpr_lint.trace_kernel(spec)
    return jaxpr_lint.analyze_closed(closed, seeds, spec)


# -- seeded-bad: 13-bit limb mul overflows int32 -------------------------------


def _schoolbook_columns(a, b):
    """Column sums of a 32x32 limb product plus one Montgomery-style
    accumulation — the exact shape of fp.mul's redc input."""
    outer = a[:, None] * b[None, :]  # (32, 32)
    cols = jnp.sum(outer, axis=0)  # 32 products per column
    return cols + cols  # + the m*p accumulation redc adds


def test_interval_catches_13_bit_limb_overflow():
    """With 13-bit limbs the column sum + Montgomery accumulation is
    32*(2^13-1)^2 * 2 ~ 2^32 > int32: the docstring bound fp.py relies on
    breaks, and the analyzer must say so with source provenance."""
    a = np.zeros(32, np.int32)
    findings = analyze_fixture(_schoolbook_columns, (a, a), [LIMB13, LIMB13])
    overflow = [f for f in findings if f.rule == "jaxpr-interval"]
    assert overflow, [f.format() for f in findings]
    f = overflow[0]
    assert "exceeds int32" in f.message and "proven value range" in f.message
    # eqn-level provenance: the finding points into THIS file at the line
    # of the offending accumulation
    assert f.path == THIS_FILE
    assert f.line > 0
    assert f.symbol == "fixture"


def test_interval_proves_12_bit_limb_scheme_safe():
    """The same graph with the real 12-bit precondition fits int32 — the
    analyzer proves fp.py's comment rather than pattern-matching it."""
    a = np.zeros(32, np.int32)
    findings = analyze_fixture(_schoolbook_columns, (a, a), [LIMB12, LIMB12])
    assert findings == [], [f.format() for f in findings]


def test_interval_checks_while_loop_condition():
    """The termination test of a lax.while_loop runs on-device with the
    same carry values as the body — an overflow there wraps just as hard
    and must be reported (regression: the cond jaxpr was once skipped)."""

    def kern(x):
        def cond(c):
            return jnp.all(c * c * c * 512 < 7)  # [0,4095]^3 * 512 ~ 2^45

        def body(c):
            return c & 0xFFF

        return lax.while_loop(cond, body, x)

    findings = analyze_fixture(kern, (np.zeros(8, np.int32),), [LIMB12])
    assert any(
        f.rule == "jaxpr-interval" and "exceeds int32" in f.message
        for f in findings
    ), [f.format() for f in findings]


def test_interval_flags_unhandled_primitive_instead_of_passing():
    findings = analyze_fixture(
        lambda x: lax.population_count(x), (np.zeros(8, np.int32),), [LIMB12]
    )
    assert any(
        f.rule == "jaxpr-interval" and "unhandled primitive" in f.message
        for f in findings
    ), [f.format() for f in findings]


# -- seeded-bad: unrolled 64-iteration Python loop -----------------------------


def _unrolled_64(x):
    acc = x
    for _ in range(64):
        acc = (acc * 3 + 1) & 0x7FF
    return acc


def _scanned_64(x):
    def step(acc, _):
        return (acc * 3 + 1) & 0x7FF, None

    acc, _ = lax.scan(step, x, None, length=64)
    return acc


def test_structure_catches_unrolled_python_loop():
    x = np.zeros(8, np.int32)
    findings = analyze_fixture(_unrolled_64, (x,), [(0, 2047)])
    unrolled = [f for f in findings if f.rule == "jaxpr-structure"]
    assert unrolled, [f.format() for f in findings]
    assert "lax.scan" in unrolled[0].message
    assert unrolled[0].path == THIS_FILE and unrolled[0].line > 0


def test_structure_quiet_on_lax_scan_form():
    x = np.zeros(8, np.int32)
    findings = analyze_fixture(_scanned_64, (x,), [(0, 2047)])
    assert findings == [], [f.format() for f in findings]


def test_structure_catches_host_sync_primitive():
    def synced(x):
        jax.debug.print("x={x}", x=x)
        return x + 1

    findings = analyze_fixture(synced, (np.zeros(4, np.int32),), [LIMB12])
    assert any(
        f.rule == "jaxpr-structure" and "host-sync" in f.message for f in findings
    ), [f.format() for f in findings]


# -- seeded-bad: int64 / float promotions --------------------------------------


def test_dtype_catches_int64_promotion_under_x64():
    """Under an x64 interpreter (what the import guard forbids) an explicit
    astype(int64) becomes a wide aval; the jaxpr dtype rule reports it with
    provenance. Under default config the promotion can't even appear — the
    AST lint (lints.TracePurityChecker) owns the source-level front door."""

    def widen(x):
        return x.astype(jnp.int64) * 2

    with jax.enable_x64():
        findings = analyze_fixture(widen, (np.zeros(8, np.int32),), [LIMB12])
    wide = [f for f in findings if f.rule == "jaxpr-dtype"]
    assert wide and "int64" in wide[0].message, [f.format() for f in findings]
    assert wide[0].path == THIS_FILE


def test_dtype_catches_float_promotion_in_integer_kernel():
    def leak(x):
        return (x * 1.5).astype(jnp.int32)

    findings = analyze_fixture(leak, (np.zeros(8, np.int32),), [LIMB12])
    assert any(
        f.rule == "jaxpr-dtype" and "float" in f.message for f in findings
    ), [f.format() for f in findings]


def test_wide_dtypes_single_sourced_with_ast_lint():
    from lighthouse_tpu.analysis.lints import WIDE_DTYPE_NAMES as ast_names

    assert jaxpr_lint.WIDE_DTYPE_NAMES is ast_names


# -- the float exact-integer domain (jaxpr-float-exact) ------------------------
#
# Fixtures are registered with integer_only=False (deliberate float paths,
# like fp.mul_mxu): the jaxpr-dtype promotion rule stands down and any
# finding below is the float-exactness analysis itself speaking.


def _f32_roundtrip(x):
    promoted = x.astype(jnp.float32)
    return promoted.astype(jnp.int32)


def _bf16_roundtrip(x):
    promoted = x.astype(jnp.bfloat16)
    return promoted.astype(jnp.int32)


def test_float_exact_proves_f32_roundtrip_inside_mantissa_window():
    """Integers up to 2^24 are exactly representable in float32: the
    int->float->int round-trip is PROVEN and produces no findings."""
    x = np.zeros(8, np.int32)
    findings = analyze_fixture(_f32_roundtrip, (x,), [(0, 1 << 24)], integer_only=False)
    assert findings == [], [f.format() for f in findings]


def test_float_exact_fails_f32_roundtrip_past_mantissa_window():
    """The SAME graph seeded one past the window (2^24 + 1) must fail, with
    file:line provenance at both the lossy promotion and the unproven
    conversion back."""
    x = np.zeros(8, np.int32)
    findings = analyze_fixture(
        _f32_roundtrip, (x,), [(0, (1 << 24) + 1)], integer_only=False
    )
    fx = [f for f in findings if f.rule == "jaxpr-float-exact"]
    assert len(fx) == 2, [f.format() for f in findings]
    enter, leave = fx
    assert "does not fit" in enter.message and "2^24" in enter.message
    assert "WITHOUT an exactness proof" in leave.message
    assert {enter.path, leave.path} == {THIS_FILE}
    assert 0 < enter.line < leave.line  # two distinct offending eqns


def test_float_exact_bfloat16_window_is_2_to_8():
    """bfloat16's 8-bit mantissa makes the exact window 2^8 — the analog
    pair proves/fails at 256/257."""
    x = np.zeros(8, np.int32)
    ok = analyze_fixture(_bf16_roundtrip, (x,), [(0, 1 << 8)], integer_only=False)
    assert ok == [], [f.format() for f in ok]
    bad = analyze_fixture(_bf16_roundtrip, (x,), [(0, (1 << 8) + 1)], integer_only=False)
    fx = [f for f in bad if f.rule == "jaxpr-float-exact"]
    assert fx and "bfloat16" in fx[0].message and "2^8" in fx[0].message


def _mxu_contract(a, b):
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    cols = jnp.einsum("i,ik->k", af, bf)
    return cols.astype(jnp.int32)


def test_float_exact_dot_general_bound_scales_with_contraction_depth():
    """Contracting K byte-limb products bounds each output by
    K * 255^2: PROVEN at K=48 (fp.mul_mxu's shape, bound 3,121,200 < 2^24),
    unprovable at K=512 (33,292,800 > 2^24) — the flip that tells ROADMAP
    item 5 what limb width is feasible at what contraction depth."""
    byte = (0, 255)
    a, b = np.zeros(48, np.int32), np.zeros((48, 8), np.int32)
    ok = analyze_fixture(_mxu_contract, (a, b), [byte, byte], integer_only=False)
    assert ok == [], [f.format() for f in ok]

    a, b = np.zeros(512, np.int32), np.zeros((512, 8), np.int32)
    bad = analyze_fixture(_mxu_contract, (a, b), [byte, byte], integer_only=False)
    fx = [f for f in bad if f.rule == "jaxpr-float-exact"]
    assert fx, [f.format() for f in bad]
    assert "float exactness LOST at 'dot_general'" in fx[0].message
    assert "contraction depth 512" in fx[0].message
    assert fx[0].path == THIS_FILE and fx[0].line > 0


def _mixed_reentry(x, scale):
    f = x.astype(jnp.float32)
    doubled = f + f
    back = doubled.astype(jnp.int32)
    return back * scale  # integer domain again — bounds must be concrete


def test_float_exact_reentry_keeps_integer_subgraph_proven():
    """A proven-exact float segment converts back to int32 and RE-ENTERS
    the integer interval domain (the mixed-graph fix): downstream integer
    math is judged on real bounds, not tainted to silence."""
    x = np.zeros(8, np.int32)
    s = np.ones(8, np.int32)
    seeds = [(0, 1 << 11), (0, 1 << 7)]
    findings = analyze_fixture(_mixed_reentry, (x, s), seeds, integer_only=False)
    assert findings == [], [f.format() for f in findings]
    # ...and the re-entered interval has teeth: scaling the same graph into
    # int32 overflow is caught IN THE INTEGER DOMAIN, downstream of the
    # float segment — impossible while mixed graphs collapsed to all-None
    bad = analyze_fixture(
        _mixed_reentry, (x, s), [(0, 1 << 11), (0, 1 << 20)], integer_only=False
    )
    wraps = [f for f in bad if f.rule == "jaxpr-interval"]
    assert wraps and "exceeds int32" in wraps[0].message, [f.format() for f in bad]


def test_float_exact_flags_fractional_float_into_int():
    """Genuinely fractional float math feeding an integer conversion is the
    original failure mode and still fails (now under the float-exact rule
    rather than by silent taint)."""

    def leak(x):
        return (x.astype(jnp.float32) * 1.5).astype(jnp.int32)

    findings = analyze_fixture(
        leak, (np.zeros(8, np.int32),), [LIMB12], integer_only=False
    )
    fx = [f for f in findings if f.rule == "jaxpr-float-exact"]
    assert fx and "without an exactness proof" in fx[0].message.lower(), [
        f.format() for f in findings
    ]


def test_float_exact_feasibility_bound_picks_fp_mxu_limb_width():
    """The analyzer's closed-form bound is the authority fp.py derives its
    MXU limb width from: widest sound width 9 for float32/384-bit, byte
    alignment picks 8, and bfloat16 admits NO width at all."""
    from lighthouse_tpu.crypto.bls.jax_backend import fp

    assert jaxpr_lint.max_exact_limb_width("float32", 384) == 9
    assert jaxpr_lint.max_exact_limb_width("bfloat16", 384) == 0
    assert fp.MXU_LIMB_BITS == 8 and fp.MXU_N_LIMBS == 48
    rows = {r["width"]: r for r in jaxpr_lint.limb_feasibility_table("float32", 384)}
    assert rows[8]["feasible"] and rows[9]["feasible"]
    assert not rows[10]["feasible"] and not rows[12]["feasible"]
    assert rows[8]["depth"] == 48 and rows[8]["bound"] == 48 * 255 * 255


def test_analyze_kernels_only_filter_and_vacuity_guard():
    """--only narrows the selection by substring; require_float_path makes
    a float-path-free selection fail instead of passing vacuously."""
    findings, counts = jaxpr_lint.analyze_kernels(
        tiers=("fast",), only="fp.add", require_float_path=True
    )
    assert set(counts) == {"fp.add"}
    vac = [f for f in findings if f.rule == "jaxpr-float-exact"]
    assert vac and "vacuous" in vac[0].message, [f.format() for f in findings]

    findings, counts = jaxpr_lint.analyze_kernels(
        tiers=("fast",), only="fp.mul_mxu", require_float_path=True
    )
    assert set(counts) == {"fp.mul_mxu"}
    assert findings == [], [f.format() for f in findings]


# -- budgets -------------------------------------------------------------------


def _counts(eqns, **by_prim):
    return {"eqns": eqns, "by_prim": by_prim}


def test_budget_regression_fails():
    counts = {"k": _counts(100, add=60, mul=40)}
    budgets = {"k": _counts(90, add=50, mul=40)}
    findings = jaxpr_lint.budget_findings(counts, budgets, ["k"])
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "jaxpr-budget" and f.symbol == "k"
    assert "90 -> 100" in f.message and "add +10" in f.message


def test_budget_equal_and_shrink_pass():
    budgets = {"k": _counts(100, add=60, mul=40)}
    assert jaxpr_lint.budget_findings({"k": _counts(100)}, budgets, ["k"]) == []
    assert jaxpr_lint.budget_findings({"k": _counts(80)}, budgets, ["k"]) == []


def test_budget_missing_and_stale_fail():
    findings = jaxpr_lint.budget_findings(
        {"new": _counts(10)}, {"gone": _counts(5)}, ["new"]
    )
    rules = sorted((f.symbol, f.rule) for f in findings)
    assert rules == [("gone", "jaxpr-budget"), ("new", "jaxpr-budget")]
    msgs = {f.symbol: f.message for f in findings}
    assert "no committed budget baseline" in msgs["new"]
    assert "stale budget baseline" in msgs["gone"]


def test_budget_regression_end_to_end(tmp_path):
    """Edit the baseline under a real kernel and assert the analyzer
    fails — the acceptance-criteria regression drill."""
    _, counts = jaxpr_lint.analyze_kernels(kernels=["fp.add"], budgets=None)
    real = counts["fp.add"]
    shrunk = {"fp.add": {"eqns": real["eqns"] - 1, "by_prim": real["by_prim"]}}
    findings, _ = jaxpr_lint.analyze_kernels(kernels=["fp.add"], budgets=shrunk)
    grow = [f for f in findings if f.rule == "jaxpr-budget" and f.symbol == "fp.add"]
    assert grow and "unexplained compile-cost growth" in grow[0].message


# -- the x64 import guard ------------------------------------------------------


def test_x64_guard_accepts_default_and_rejects_x64():
    from lighthouse_tpu.crypto.bls import jax_backend

    jax_backend.assert_x64_disabled()  # tier-1 config: x64 off
    with jax.enable_x64():
        with pytest.raises(RuntimeError, match="x64"):
            jax_backend.assert_x64_disabled()


# -- the tree gate (tier-1 teeth) ----------------------------------------------


def test_fast_tier_kernels_proven_overflow_free_within_budget():
    """Every fast-tier registered kernel is PROVEN int32-overflow-free from
    the canonical-limb precondition, int64/float/host-sync-free, unroll-
    free, and within its committed primitive-count budget. This is the gate
    the ROADMAP-1 kernel rewrite (windowed mul, Karabina squaring,
    batch-affine) lands against."""
    budgets = jaxpr_lint.load_budgets()
    assert budgets, "scripts/jaxpr_budgets.json missing — run --update-budgets"
    findings, counts = jaxpr_lint.analyze_kernels(
        tiers=("fast",), budgets=budgets, require_float_path=True
    )
    assert not findings, "\n".join(f.format() for f in findings)
    # the registry actually covered the kernel surface (guards accidental
    # registry emptiness making this gate vacuous)
    assert len(counts) >= 15
    for family in ("fp.", "tower.", "curve.", "pairing.", "h2c."):
        assert any(k.startswith(family) for k in counts), family
    # ...including the float-path kernel the jaxpr-float-exact analysis
    # exists for: zero findings above means its float32 dot_general is
    # PROVEN exact, not skipped
    assert "fp.mul_mxu" in counts


@pytest.mark.slow
def test_all_tiers_kernels_proven_overflow_free_within_budget():
    """Nightly tier: the slow composites too (Miller loop, final exp, full
    hash-to-G2, verify_pipeline_local at two (S, K) bucket shapes)."""
    budgets = jaxpr_lint.load_budgets()
    findings, counts = jaxpr_lint.analyze_kernels(
        tiers=("fast", "slow"), budgets=budgets
    )
    assert not findings, "\n".join(f.format() for f in findings)
    assert set(counts) == set(registry.kernel_names())
