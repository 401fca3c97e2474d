"""Jaxpr kernel analyzer: interval proofs of limb-overflow safety plus
compile-cost and structure budgets.

Why: the int32 limb scheme rests on a prose invariant — fp.py's docstring
claims every schoolbook column sum is bounded by 32*(2^12)^2 = 2^29 and
"fits int32 with headroom" — and ROADMAP item 1 rewrites exactly that
arithmetic (windowed scalar mul, Karabina squaring, batch-affine), where a
silent int32 wraparound is a verification-forgery bug that random-input
differential tests can miss.  This module traces every registered kernel
(crypto/bls/jax_backend/registry.py) to a closed jaxpr — trace-only, no
compilation, so the gate is cheap on a CPU-only box — and proves/monitors
four things, emitting engine.Finding objects through the same allowlist
machinery as the AST lints:

  jaxpr-interval   abstract interpretation with per-array integer ranges:
                   [lo, hi] bounds propagate through every arithmetic and
                   structural primitive and into scan/while/cond bodies
                   (fixpoint with power-of-two widening), seeded from the
                   canonical-limb precondition [0, 2^12).  An intermediate
                   whose PROVEN range escapes its integer dtype is a
                   finding carrying the offending eqn and its source
                   provenance — the docstring bound becomes a theorem every
                   kernel rewrite must re-prove.  Unhandled primitives are
                   findings too (the analysis never silently passes).
  jaxpr-float-exact  the MXU-readiness analysis: float-dtype values carry
                   an integer range [lo, hi] plus a PROVEN-exact flag that
                   holds iff every value (and every reduction partial) fits
                   the dtype's exact-integer window ±2^mantissa (float32:
                   2^24, bfloat16: 2^8 — FLOAT_MANTISSA_BITS, single-
                   sourced with lints).  int→float conversion enters the
                   domain when the range fits; add/sub/mul/reduce_sum/
                   dot_general propagate it (a contraction over K
                   multiplies the product bound by K — the bound that
                   answers "what limb width is feasible at what contraction
                   depth"); float→int conversion of a PROVEN-exact value
                   re-enters the integer interval domain, so mixed graphs
                   no longer collapse to all-unknown.  Any kernel that
                   routes integer data through floats WITHOUT such a proof
                   — window exceeded, or a float of unproven provenance
                   converted back to int — is a finding with eqn
                   source_info provenance.
  jaxpr-dtype      64-bit avals (int64/uint64/float64 — WIDE_DTYPE_NAMES,
                   single-sourced with lints.TracePurityChecker so the AST
                   and jaxpr checks cannot drift) and float promotions
                   inside integer-only kernels.  Under the x64 guard
                   (jax_backend/__init__) these cannot appear in a default
                   trace; the rule catches env drift and explicit wide
                   inputs.  Kernels registered with integer_only=False
                   (the deliberate MXU float paths, e.g. fp.mul_mxu) skip
                   the float-promotion rule and answer to jaxpr-float-exact
                   instead.
  jaxpr-structure  host-sync/callback primitives under trace, and long
                   repeated-eqn runs — an unrolled Python loop that should
                   be a lax.scan (XLA compile time tracks inlined op count
                   on this box).  Periods up to _MAX_PERIOD eqns are
                   detected numerically; coarser unrolls surface as budget
                   growth instead.
  jaxpr-budget     flattened primitive counts per kernel against the
                   committed baseline scripts/jaxpr_budgets.json.  Any
                   unexplained growth fails; refresh deliberately with
                   `python scripts/lint.py --update-budgets` (the diff of
                   the baseline file is the explanation reviewers see).

This module imports jax (unlike engine/lints) and is therefore NOT pulled
in by `lighthouse_tpu.analysis.__init__`; scripts/lint.py imports it only
under --jaxpr, keeping the default AST lint path dependency-free.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from .engine import Finding
from .lints import FLOAT_MANTISSA_BITS, WIDE_DTYPE_NAMES

REPO_ROOT = Path(__file__).resolve().parents[2]
BUDGETS_PATH = REPO_ROOT / "scripts" / "jaxpr_budgets.json"

#: primitives that stall the device on the host (or smuggle host effects
#: into traced code); never legal inside a BLS kernel
HOST_SYNC_PRIMS = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "debug_print",
        "infeed",
        "outfeed",
        "host_local_array_to_global_array",
        "global_array_to_host_local_array",
    }
)

# -- intervals -----------------------------------------------------------------


@dataclass(frozen=True)
class Interval:
    """Inclusive integer bounds for every element of an integer-dtype array
    (whole-array abstraction: one [lo, hi] per value, exact Python ints so
    no analysis-side overflow). `None` in the environment means unknown/
    tainted (unhandled primitives, unproven floats) — tainted values
    propagate without triggering range findings; the taint source itself
    is always a finding."""

    lo: int
    hi: int


@dataclass(frozen=True)
class FloatInterval:
    """Abstract value of a FLOAT-dtype array derived from integer data:
    integer bounds [lo, hi] plus a PROVEN-exact flag.  `exact=True` means
    every element is an exactly-representable integer equal to the value
    infinite-precision arithmetic would have produced — which holds while
    every intermediate (including reduction partials) stays inside the
    dtype's exact-integer window ±2^mantissa (FLOAT_MANTISSA_BITS).  Once
    exactness is lost the bounds are approximate (rounding can nudge past
    them) and the value can never re-enter the proven integer domain."""

    lo: int
    hi: int
    exact: bool


def _is_float_dtype(dtype) -> bool:
    dt = np.dtype(dtype)
    # ml_dtypes extension floats (bfloat16, float8_*) report kind 'V'
    return dt.kind == "f" or dt.name in FLOAT_MANTISSA_BITS


def float_exact_window(dtype) -> int | None:
    """W such that every integer in [-W, W] is exactly representable in
    `dtype` AND integer add/mul results remain exact while they stay within
    [-W, W].  W = 2^mantissa (implicit bit included); None for non-floats
    and exotic floats we have no table entry for."""
    bits = FLOAT_MANTISSA_BITS.get(np.dtype(dtype).name)
    return None if bits is None else 1 << bits


def max_exact_limb_width(dtype="float32", total_bits=384) -> int:
    """The analyzer's MXU feasibility bound: the widest limb width w such
    that a full schoolbook contraction over K = ceil(total_bits / w) limb
    products stays inside `dtype`'s exact-integer window:

        K * (2^w - 1)^2  <=  2^mantissa(dtype)

    This is the limb-width-vs-contraction-depth trade a dot_general-shaped
    bigint multiplier must respect (ROADMAP item 5); fp.MXU_LIMB_BITS is
    chosen against this bound and tests pin the two together.  Returns 0
    when NO width is feasible (e.g. bfloat16's 2^8 window cannot hold even
    one 384-bit schoolbook column)."""
    window = float_exact_window(dtype)
    if window is None:
        return 0
    best = 0
    for w in range(1, total_bits + 1):
        k = -(-total_bits // w)  # ceil
        if k * ((1 << w) - 1) ** 2 <= window:
            best = w
    return best


def limb_feasibility_table(dtype="float32", total_bits=384, widths=range(6, 13)):
    """Worked feasibility rows for documentation/tests: for each limb width
    w, the contraction depth K = ceil(total_bits/w), the worst-case column
    bound K*(2^w-1)^2, the dtype's exact window, and whether the bound
    fits.  ARCHITECTURE.md's MXU-readiness table is generated from this."""
    window = float_exact_window(dtype) or 0
    rows = []
    for w in widths:
        k = -(-total_bits // w)
        bound = k * ((1 << w) - 1) ** 2
        rows.append(
            {
                "width": w,
                "depth": k,
                "bound": bound,
                "window": window,
                "feasible": bound <= window,
            }
        )
    return rows


def _join(a, b):
    if a is None or b is None:
        return None
    if isinstance(a, FloatInterval) or isinstance(b, FloatInterval):
        if not (isinstance(a, FloatInterval) and isinstance(b, FloatInterval)):
            return None  # mixed domains cannot meet (dtype mismatch)
        return FloatInterval(
            min(a.lo, b.lo), max(a.hi, b.hi), a.exact and b.exact
        )
    return Interval(min(a.lo, b.lo), max(a.hi, b.hi))


def _widen(iv):
    """Power-of-two envelope: guarantees fixpoint termination in a few
    iterations while staying far tighter than dtype bounds.  Type- and
    exactness-preserving (widening only loosens bounds over the same value
    set, so a carried exact flag stays sound; the next fixpoint iteration
    re-checks the window against the widened bounds)."""
    hi = (1 << max(1, int(iv.hi).bit_length())) - 1 if iv.hi > 0 else iv.hi
    lo = -(1 << max(1, int(-iv.lo).bit_length())) if iv.lo < 0 else iv.lo
    if isinstance(iv, FloatInterval):
        return FloatInterval(lo, hi, iv.exact)
    return Interval(lo, hi)


def _const_interval(val):
    arr = np.asarray(val)
    if _is_float_dtype(arr.dtype):
        if arr.size == 0:
            return FloatInterval(0, 0, True)
        vals = np.asarray(arr, np.float64)
        if not (np.isfinite(vals).all() and (vals == np.round(vals)).all()):
            return None  # genuinely fractional / non-finite float data
        # a literal is its own intention: exactly the integers it holds
        return FloatInterval(int(vals.min()), int(vals.max()), True)
    if arr.dtype.kind not in "biu":
        return None
    if arr.size == 0:
        return Interval(0, 0)
    return Interval(int(arr.min()), int(arr.max()))


def _dtype_bounds(dtype) -> tuple[int, int] | None:
    dt = np.dtype(dtype)
    if dt.kind == "b":
        return (0, 1)
    if dt.kind in "iu":
        info = np.iinfo(dt)
        return (int(info.min), int(info.max))
    return None


# -- provenance ----------------------------------------------------------------


def _eqn_provenance(eqn) -> tuple[str, int]:
    """(repo-relative-or-absolute path, line) of the user frame that emitted
    this eqn — the `source_info` thread from the original Python source."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return "", 0
    path = frame.file_name
    try:
        path = Path(path).resolve().relative_to(REPO_ROOT).as_posix()
    except (ValueError, OSError):
        pass
    return path, int(frame.start_line)


def _spec_path(spec) -> str:
    """Fallback Finding path: the kernel's defining module."""
    import sys

    mod = sys.modules.get(spec.module)
    f = getattr(mod, "__file__", None)
    if f:
        try:
            return Path(f).resolve().relative_to(REPO_ROOT).as_posix()
        except (ValueError, OSError):
            return Path(f).as_posix()
    return spec.module.replace(".", "/") + ".py"


# -- sub-jaxpr plumbing --------------------------------------------------------


def _as_closed(obj):
    """Normalize a params value to (jaxpr, consts) if it wraps a jaxpr."""
    jaxpr = getattr(obj, "jaxpr", None)
    if jaxpr is not None and hasattr(obj, "consts"):
        return jaxpr, list(obj.consts)
    if hasattr(obj, "eqns") and hasattr(obj, "invars"):
        return obj, []
    return None


def _param_jaxprs(eqn):
    """Every (jaxpr, consts) nested in an eqn's params, any wrapping."""
    out = []
    for v in eqn.params.values():
        for item in v if isinstance(v, (tuple, list)) else (v,):
            got = _as_closed(item)
            if got is not None:
                out.append(got)
    return out


def _iter_jaxprs(jaxpr):
    """The jaxpr and every nested sub-jaxpr (each body yielded once)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub, _consts in _param_jaxprs(eqn):
            yield from _iter_jaxprs(sub)


def count_primitives(closed) -> dict:
    """Flattened primitive counts: every eqn in every nested jaxpr counted
    once (a scan body counts once — what the compiler ingests, and the
    number tracing/compile time actually tracks on this box)."""
    by_prim: dict[str, int] = {}
    for j in _iter_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            by_prim[eqn.primitive.name] = by_prim.get(eqn.primitive.name, 0) + 1
    return {"eqns": sum(by_prim.values()), "by_prim": dict(sorted(by_prim.items()))}


# -- the interval abstract interpreter -----------------------------------------

_SCAN_MAX_ITERS = 24
_SCAN_WIDEN_AFTER = 3


class _Ctx:
    """Per-kernel analysis state. `emit` gates finding emission so scan/while
    fixpoint iterations stay silent; the converged final pass reports."""

    def __init__(self, spec):
        self.spec = spec
        self.emit = True
        self.findings: list[Finding] = []
        self._seen: set[tuple] = set()

    def finding(self, rule: str, eqn, message: str) -> None:
        if not self.emit:
            return
        path, line = _eqn_provenance(eqn)
        # one finding per (rule, source line): a shared helper inlined many
        # times (fp.mul inside a composite) reports once, not per inlining
        key = (rule, path, line)
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(
            Finding(
                rule=rule,
                path=path or _spec_path(self.spec),
                line=line,
                symbol=self.spec.name,
                message=message,
            )
        )


def _corners(a: Interval, b: Interval, op) -> Interval:
    vals = (op(a.lo, b.lo), op(a.lo, b.hi), op(a.hi, b.lo), op(a.hi, b.hi))
    return Interval(min(vals), max(vals))


def _shift_corners(a: Interval, s: Interval, op) -> Interval:
    s_lo, s_hi = max(0, s.lo), max(0, min(s.hi, 64))
    vals = (op(a.lo, s_lo), op(a.lo, s_hi), op(a.hi, s_lo), op(a.hi, s_hi))
    return Interval(min(vals), max(vals))


def _reduced_count(eqn) -> int:
    """Number of elements folded into one output element by a reduce."""
    in_shape = eqn.invars[0].aval.shape
    axes = eqn.params.get("axes", ())
    n = 1
    for ax in axes:
        n *= int(in_shape[ax])
    return max(1, n)


def _contraction_depth(eqn) -> int:
    """Number of elements contracted into one output element by dot_general
    (K in the limb-width feasibility bound K * (2^w - 1)^2 <= 2^mantissa)."""
    (lhs_c, _rhs_c), _ = eqn.params["dimension_numbers"]
    n = 1
    for ax in lhs_c:
        n *= int(eqn.invars[0].aval.shape[ax])
    return max(1, n)


#: float-dtype primitives INSIDE the exact-integer closure: when every
#: operand is a proven-exact integer and every result (including reduction
#: partials — bounded by the corner bounds, see _float_arith_transfer)
#: stays inside the dtype's ±2^mantissa window, the float result is
#: bit-exact integer arithmetic.  Anything else (div, sqrt, exp, ...)
#: leaves the exact domain unconditionally.
_FLOAT_EXACT_OPS = frozenset(
    {
        "add", "sub", "mul", "neg", "abs", "sign", "max", "min",
        "reduce_sum", "reduce_prod", "integer_pow", "dot_general",
    }
)


def _float_arith_transfer(name, eqn, ins, ctx) -> list:
    """Arithmetic transfer for float-dtype outputs: integer corner math on
    the bounds plus the exactness judgment.  Exactness is LOST (a
    jaxpr-float-exact finding, once, at the losing eqn) when exact operands
    produce a range outside the dtype's exact-integer window; values of
    already-unproven provenance stay unproven silently — their proof
    failure was reported where it happened, or surfaces at the float→int
    conversion that tries to use them."""
    nouts = len(eqn.outvars)
    if name in ("floor", "ceil", "round", "round_nearest_even"):
        return [ins[0] if ins else None]  # identity on exact integers
    if name not in _FLOAT_EXACT_OPS or any(x is None for x in ins):
        return [None] * nouts
    exact_in = all(x.exact for x in ins if isinstance(x, FloatInterval))
    raw = _int_arith(name, eqn, [Interval(x.lo, x.hi) for x in ins])
    if raw is None:
        return [None] * nouts
    dt = np.dtype(eqn.outvars[0].aval.dtype)
    window = float_exact_window(dt)
    mant = FLOAT_MANTISSA_BITS.get(dt.name)
    outs = []
    for iv in raw:
        if iv is None:
            outs.append(None)
            continue
        mag = max(abs(iv.lo), abs(iv.hi))
        # the single corner-bound check also covers every accumulation
        # partial: same-sign terms only grow toward the corner, mixed
        # signs only shrink, so partial sums/products of values in
        # [lo, hi] are bounded by the final corner bounds
        if exact_in and window is not None and mag <= window:
            outs.append(FloatInterval(iv.lo, iv.hi, True))
            continue
        if exact_in:
            if name == "dot_general":
                detail = (
                    f" (contraction depth {_contraction_depth(eqn)} "
                    f"multiplies the product bound)"
                )
            elif name in ("reduce_sum", "reduce_prod"):
                detail = f" (reduces {_reduced_count(eqn)} elements per output)"
            else:
                detail = ""
            ctx.finding(
                "jaxpr-float-exact",
                eqn,
                f"float exactness LOST at '{name}': exact integer operands "
                f"yield result range [{iv.lo}, {iv.hi}], outside the "
                f"±2^{mant} exact-integer window of {dt.name}{detail} — "
                f"values round silently on the MXU/VPU; shrink the limb "
                f"width or contraction depth "
                f"(analysis/jaxpr_lint.max_exact_limb_width gives the "
                f"feasibility bound)",
            )
        outs.append(FloatInterval(iv.lo, iv.hi, False))
    return outs


def _convert_transfer(eqn, a, ctx):
    """convert_element_type: the gateway between the integer and float
    domains.  int→float enters the exact domain iff the proven range fits
    the window; float→int of a PROVEN-exact value re-enters the integer
    interval domain (mixed graphs keep their proofs); anything else is the
    exact failure mode this analysis exists for and is reported."""
    out_dt = np.dtype(eqn.outvars[0].aval.dtype)
    in_dt = np.dtype(eqn.invars[0].aval.dtype)
    if out_dt.kind == "b":
        return Interval(0, 1) if a is not None else None
    out_f, in_f = _is_float_dtype(out_dt), _is_float_dtype(in_dt)
    if a is None:
        if in_f and not out_f:
            ctx.finding(
                "jaxpr-float-exact",
                eqn,
                f"float value of unproven provenance converted to "
                f"{out_dt.name}: integer data was routed through floats "
                f"without an exactness proof — enter the float segment via "
                f"an in-window int→float conversion of proven-range data, "
                f"or keep the computation integer",
            )
        return None
    if not in_f and not out_f:
        return a  # int→int: the dtype-bounds check in _interp judges it
    if out_f:
        window = float_exact_window(out_dt)
        mant = FLOAT_MANTISSA_BITS.get(out_dt.name)
        exact_in = a.exact if isinstance(a, FloatInterval) else True
        mag = max(abs(a.lo), abs(a.hi))
        if exact_in and window is not None and mag <= window:
            return FloatInterval(a.lo, a.hi, True)
        if exact_in:
            ctx.finding(
                "jaxpr-float-exact",
                eqn,
                f"integer range [{a.lo}, {a.hi}] does not fit the "
                f"±2^{mant} exact-integer window of {out_dt.name}: values "
                f"round on conversion and the interval proof is lost — "
                f"narrow the range (smaller limbs) or use a wider float",
            )
        return FloatInterval(a.lo, a.hi, False)
    # float → int
    if isinstance(a, FloatInterval) and a.exact:
        return Interval(a.lo, a.hi)  # proven round-trip re-enters the integer domain
    ctx.finding(
        "jaxpr-float-exact",
        eqn,
        f"float value converted to {out_dt.name} WITHOUT an exactness "
        f"proof (bounds [{a.lo}, {a.hi}] are approximate: rounding may "
        f"have occurred upstream): the integer result is untrusted",
    )
    return None


def _transfer(eqn, ins, ctx) -> list:
    """Per-primitive interval transfer. Returns one Interval/FloatInterval/
    None per outvar. Pure integer math on Python ints — the analysis itself
    cannot overflow."""
    name = eqn.primitive.name
    a = ins[0] if ins else None
    b = ins[1] if len(ins) > 1 else None

    if name in HOST_SYNC_PRIMS:
        # already a jaxpr-structure finding; don't double-report as unhandled
        return [None] * len(eqn.outvars)

    if name == "convert_element_type":
        return [_convert_transfer(eqn, a, ctx)]

    # structural pass-throughs (value set preserved or shrunk) — domain-
    # agnostic: a FloatInterval rides through with its exactness intact
    if name in (
        "broadcast_in_dim", "reshape", "transpose", "squeeze", "rev", "copy",
        "device_put", "stop_gradient", "slice", "real", "expand_dims",
        "reduce_max", "reduce_min", "optimization_barrier",
    ):
        if name == "optimization_barrier":
            return list(ins)
        return [a]
    if name == "gather":
        # Integer gathers keep the historical in-bounds assumption (table
        # lookups whose index arithmetic the whole-array interval cannot
        # separate from its selector).  FLOAT gathers must not: FILL mode
        # injects NaN into out-of-bounds lanes, which would silently ride
        # an exactness proof — join the fill value (NaN taints) unless the
        # index interval proves every lane in bounds.  fp.mul_mxu uses
        # mode="clip" precisely so this stays precise.
        fv = eqn.params.get("fill_value")
        if fv is not None and _is_float_dtype(eqn.outvars[0].aval.dtype):
            in_bounds = False
            if b is not None:
                dnums = eqn.params["dimension_numbers"]
                sizes = eqn.params["slice_sizes"]
                shape = eqn.invars[0].aval.shape
                lim = min(
                    (int(shape[d]) - int(sizes[d]) for d in dnums.start_index_map),
                    default=0,
                )
                in_bounds = 0 <= b.lo and b.hi <= lim
            if not in_bounds:
                fill = np.asarray(fv, dtype=eqn.outvars[0].aval.dtype)
                return [_join(a, _const_interval(fill))]
        return [a]
    if name == "reduce_precision":
        if isinstance(a, FloatInterval):
            mbits = eqn.params.get("mantissa_bits")
            ok = (
                a.exact
                and mbits is not None
                and max(abs(a.lo), abs(a.hi)) <= (1 << int(mbits))
            )
            return [FloatInterval(a.lo, a.hi, bool(ok))]
        return [a]
    if name in ("dynamic_slice",):
        return [a]
    if name in ("concatenate",):
        out = ins[0]
        for x in ins[1:]:
            out = _join(out, x)
        return [out]
    if name == "pad":
        return [_join(a, b)]
    if name == "dynamic_update_slice":
        return [_join(a, ins[1])]  # (operand, update, *start_indices)
    if name in ("scatter", "select_and_scatter_add"):
        return [_join(a, ins[2] if len(ins) > 2 else b)]  # (operand, idx, updates)
    if name == "scatter-add":
        if a is None or ins[2] is None:
            return [None]
        upd = ins[2]
        out = Interval(a.lo + min(0, upd.lo), a.hi + max(0, upd.hi))
        if isinstance(a, FloatInterval) or isinstance(upd, FloatInterval):
            exact = all(
                x.exact for x in (a, upd) if isinstance(x, FloatInterval)
            )
            return [FloatInterval(out.lo, out.hi, exact)]
        return [out]
    if name == "select_n":
        out = ins[1]
        for x in ins[2:]:
            out = _join(out, x)
        return [out]
    if name == "clamp":
        lo_i, x, hi_i = ins
        if lo_i is None or x is None or hi_i is None:
            return [None]
        out = Interval(
            max(lo_i.lo, min(x.lo, hi_i.hi)), min(hi_i.hi, max(x.hi, lo_i.lo))
        )
        if any(isinstance(v, FloatInterval) for v in ins):
            exact = all(v.exact for v in ins if isinstance(v, FloatInterval))
            return [FloatInterval(out.lo, out.hi, exact)]
        return [out]
    if name == "iota":
        dim = eqn.params.get("dimension", 0)
        shape = eqn.params.get("shape", (1,))
        return [Interval(0, max(0, int(shape[dim]) - 1))]

    # comparisons / predicates
    if name in ("eq", "ne", "lt", "le", "gt", "ge", "is_finite"):
        return [Interval(0, 1)]
    if name in ("reduce_and", "reduce_or"):
        return [Interval(0, 1)]

    # control flow (before the taint guard: bodies are analyzed even when
    # some operand is tainted, so findings inside them still surface)
    if name in (
        "jit", "closed_call", "core_call", "xla_call", "remat", "checkpoint",
        "custom_jvp_call", "custom_vjp_call", "custom_jvp_call_jaxpr",
    ):
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            obj = eqn.params.get(key)
            got = _as_closed(obj) if obj is not None else None
            if got is not None:
                sub, consts = got
                return _interp(sub, consts, list(ins), ctx)
        return None  # fall through to unhandled
    if name == "scan":
        return _scan_transfer(eqn, ins, ctx)
    if name == "while":
        return _while_transfer(eqn, ins, ctx)
    if name == "cond":
        branches = eqn.params["branches"]
        outs = None
        for br in branches:
            sub, consts = _as_closed(br)
            res = _interp(sub, consts, list(ins[1:]), ctx)
            outs = res if outs is None else [_join(x, y) for x, y in zip(outs, res)]
        return outs

    # float-dtype arithmetic: the exact-integer closure keeps the proof
    # alive; everything else leaves the value unproven (never a silent
    # integer-domain pass)
    if eqn.outvars and _is_float_dtype(eqn.outvars[0].aval.dtype):
        return _float_arith_transfer(name, eqn, ins, ctx)

    # integer arithmetic: proven-exact float operands collapse to their
    # integer bounds (comparisons/selects over them are real integer facts),
    # unproven floats taint
    ins = [
        Interval(x.lo, x.hi)
        if isinstance(x, FloatInterval) and x.exact
        else (None if isinstance(x, FloatInterval) else x)
        for x in ins
    ]
    if any(x is None for x in ins) and name not in ("and", "or", "xor", "not"):
        return [None] * len(eqn.outvars)
    return _int_arith(name, eqn, ins)


def _int_arith(name, eqn, ins):
    """Integer corner math shared by the integer and float-exact domains.
    Returns a list of Interval/None per outvar, or None for an unhandled
    primitive."""
    a = ins[0] if ins else None
    b = ins[1] if len(ins) > 1 else None
    if name == "add":
        return [Interval(a.lo + b.lo, a.hi + b.hi)]
    if name == "sub":
        return [Interval(a.lo - b.hi, a.hi - b.lo)]
    if name == "mul":
        return [_corners(a, b, lambda x, y: x * y)]
    if name == "neg":
        return [Interval(-a.hi, -a.lo)]
    if name == "abs":
        lo = 0 if a.lo <= 0 <= a.hi else min(abs(a.lo), abs(a.hi))
        return [Interval(lo, max(abs(a.lo), abs(a.hi)))]
    if name == "sign":
        return [Interval(-1 if a.lo < 0 else (1 if a.lo > 0 else 0),
                         1 if a.hi > 0 else (-1 if a.hi < 0 else 0))]
    if name in ("max",):
        return [Interval(max(a.lo, b.lo), max(a.hi, b.hi))]
    if name in ("min",):
        return [Interval(min(a.lo, b.lo), min(a.hi, b.hi))]
    if name == "shift_right_arithmetic":
        return [_shift_corners(a, b, lambda x, s: x >> s)]
    if name == "shift_right_logical":
        if a.lo >= 0:
            return [_shift_corners(a, b, lambda x, s: x >> s)]
        bounds = _dtype_bounds(eqn.outvars[0].aval.dtype) or (0, 1)
        return [Interval(0, max(a.hi, bounds[1]))]
    if name == "shift_left":
        return [_shift_corners(a, b, lambda x, s: x << s)]
    if name in ("and", "or", "xor"):
        dt = np.dtype(eqn.outvars[0].aval.dtype)
        if dt.kind == "b":
            return [Interval(0, 1)]
        if a is None or b is None:
            return [None]
        if name == "and":
            nonneg = [x.hi for x in (a, b) if x.lo >= 0]
            if nonneg:
                return [Interval(0, min(nonneg))]
        elif a.lo >= 0 and b.lo >= 0:
            m = max(a.hi, b.hi)
            return [Interval(0, (1 << max(1, int(m).bit_length())) - 1)]
        bounds = _dtype_bounds(dt)
        return [Interval(*bounds) if bounds else None]
    if name == "not":
        dt = np.dtype(eqn.outvars[0].aval.dtype)
        if dt.kind == "b":
            return [Interval(0, 1)]
        if a is None:
            return [None]
        return [Interval(-a.hi - 1, -a.lo - 1)]
    if name == "reduce_sum":
        n = _reduced_count(eqn)
        return [Interval(a.lo * n, a.hi * n)]
    if name == "reduce_prod":
        n = _reduced_count(eqn)
        m = max(abs(a.lo), abs(a.hi), 1)
        return [Interval(-(m**n), m**n)]
    if name == "integer_pow":
        y = int(eqn.params.get("y", 1))
        if y < 0:
            return [None]
        cands = [a.lo**y, a.hi**y]
        if a.lo < 0 < a.hi:
            cands.append(0)
        return [Interval(min(cands), max(cands))]
    if name == "rem":
        m = max(abs(b.lo), abs(b.hi), 1)
        return [Interval(max(a.lo, -(m - 1)) if a.lo < 0 else 0, min(a.hi, m - 1) if a.hi > 0 else 0)]
    if name == "div":
        # conservative: |quotient| <= |dividend| for |divisor| >= 1, and the
        # quotient's sign set is covered by the dividend/divisor corners
        m = max(abs(a.lo), abs(a.hi))
        return [Interval(-m, m)]
    if name == "dot_general":
        dims = eqn.params["dimension_numbers"]
        (lhs_c, _rhs_c), _ = dims
        n = 1
        for ax in lhs_c:
            n *= int(eqn.invars[0].aval.shape[ax])
        prod = _corners(a, b, lambda x, y: x * y)
        return [Interval(prod.lo * max(1, n), prod.hi * max(1, n))]

    return None  # unhandled


def _fixpoint_carry(run_body, init, ctx):
    """Shared scan/while carry fixpoint with widening; returns converged
    carry intervals. `run_body(carry) -> new_carry` must be silent."""
    carry = list(init)
    emit_was = ctx.emit
    ctx.emit = False
    try:
        for it in range(_SCAN_MAX_ITERS):
            new = run_body(carry)
            joined = [_join(c, n) for c, n in zip(carry, new)]
            if it >= _SCAN_WIDEN_AFTER:
                joined = [
                    (_widen(j) if j is not None and j != c else j)
                    for j, c in zip(joined, carry)
                ]
            if joined == carry:
                return carry
            carry = joined
    finally:
        ctx.emit = emit_was
    return [None] * len(carry)  # did not converge: taint


def _scan_transfer(eqn, ins, ctx):
    p = eqn.params
    sub, consts = _as_closed(p["jaxpr"])
    nc, ncar = p["num_consts"], p["num_carry"]
    sc_consts, init, xs = ins[:nc], ins[nc : nc + ncar], ins[nc + ncar :]

    def run_body(carry):
        outs = _interp(sub, consts, list(sc_consts) + list(carry) + list(xs), ctx)
        return outs[:ncar]

    carry = _fixpoint_carry(run_body, init, ctx)
    outs = _interp(sub, consts, list(sc_consts) + list(carry) + list(xs), ctx)
    return list(carry) + outs[ncar:]  # final carries + stacked ys


def _while_transfer(eqn, ins, ctx):
    p = eqn.params
    cond, cond_consts = _as_closed(p["cond_jaxpr"])
    body, body_consts = _as_closed(p["body_jaxpr"])
    cn, bn = p["cond_nconsts"], p["body_nconsts"]
    c_consts, w_consts, init = ins[:cn], ins[cn : cn + bn], ins[cn + bn :]

    def run_body(carry):
        return _interp(body, body_consts, list(w_consts) + list(carry), ctx)

    carry = _fixpoint_carry(run_body, init, ctx)
    # emit passes over BOTH sub-jaxprs: the termination test runs on-device
    # with the same carry values, so an overflow there wraps just as hard
    _interp(cond, cond_consts, list(c_consts) + list(carry), ctx)
    _interp(body, body_consts, list(w_consts) + list(carry), ctx)
    return carry


def _coerce_domain(var, iv):
    """Align an abstract value with its variable's dtype.  Structural and
    generator ops that produced a plain Interval for a float output (iota,
    and constants folded through selects) gain the exactness judgment —
    their values ARE integers, so exact iff in-window.  A FloatInterval
    reaching an integer variable (only possible through value-preserving
    structure) collapses to its bounds when proven, taints otherwise."""
    if iv is None:
        return None
    isf = _is_float_dtype(var.aval.dtype)
    if isf and type(iv) is Interval:
        w = float_exact_window(var.aval.dtype)
        exact = w is not None and max(abs(iv.lo), abs(iv.hi)) <= w
        return FloatInterval(iv.lo, iv.hi, exact)
    if not isf and isinstance(iv, FloatInterval):
        return Interval(iv.lo, iv.hi) if iv.exact else None
    return iv


def _interp(jaxpr, consts, in_ivals, ctx) -> list:
    """Interpret one jaxpr level over intervals, checking every integer
    output against its dtype bounds."""
    env: dict = {}

    def read(atom):
        if hasattr(atom, "val"):  # Literal
            return _const_interval(atom.val)
        return env.get(atom)

    for var, const in zip(jaxpr.constvars, consts):
        env[var] = _coerce_domain(var, _const_interval(const))
    for var, iv in zip(jaxpr.invars, in_ivals):
        env[var] = _coerce_domain(var, iv)

    for eqn in jaxpr.eqns:
        ins = [read(x) for x in eqn.invars]
        outs = _transfer(eqn, ins, ctx)
        if outs is None:
            ctx.finding(
                "jaxpr-interval",
                eqn,
                f"unhandled primitive '{eqn.primitive.name}': interval "
                f"analysis cannot bound its output — extend "
                f"analysis/jaxpr_lint._transfer",
            )
            outs = [None] * len(eqn.outvars)
        for var, iv in zip(eqn.outvars, outs):
            iv = _coerce_domain(var, iv)
            if type(iv) is Interval:
                bounds = _dtype_bounds(var.aval.dtype)
                if bounds is not None:
                    lo, hi = bounds
                    if iv.lo < lo or iv.hi > hi:
                        ctx.finding(
                            "jaxpr-interval",
                            eqn,
                            f"proven value range [{iv.lo}, {iv.hi}] of "
                            f"'{eqn.primitive.name}' output exceeds "
                            f"{np.dtype(var.aval.dtype).name} [{lo}, {hi}] "
                            f"— silent wraparound (or a hidden int64 "
                            f"requirement) on the device",
                        )
                        iv = Interval(max(iv.lo, lo), min(iv.hi, hi))
            env[var] = iv

    return [read(v) for v in jaxpr.outvars]


# -- dtype / structure scans ---------------------------------------------------


def _dtype_findings(closed, spec, ctx) -> None:
    for j in _iter_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name in HOST_SYNC_PRIMS:
                ctx.finding(
                    "jaxpr-structure",
                    eqn,
                    f"host-sync primitive '{eqn.primitive.name}' inside "
                    f"traced kernel code: a device stall / host round-trip "
                    f"on the BLS hot path",
                )
            for var in eqn.outvars:
                dt = np.dtype(var.aval.dtype)
                if dt.name in WIDE_DTYPE_NAMES:
                    ctx.finding(
                        "jaxpr-dtype",
                        eqn,
                        f"{dt.name} aval produced by '{eqn.primitive.name}': "
                        f"the limb kernels assume 32-bit lanes (TPU has no "
                        f"fast 64-bit path; see jax_backend/__init__ x64 "
                        f"guard)",
                    )
                elif _is_float_dtype(dt) and spec.integer_only:
                    ctx.finding(
                        "jaxpr-dtype",
                        eqn,
                        f"float dtype {dt.name} produced by "
                        f"'{eqn.primitive.name}' inside an integer-only "
                        f"kernel: a silent promotion out of the exact limb "
                        f"domain",
                    )


_MAX_PERIOD = 128  # longest repeated-chunk period searched (eqns)
_MIN_REPEATS = 20  # instances of the chunk before it counts as an unroll
_MIN_RUN = 96  # and the run must span at least this many eqns


def _structure_findings(closed, ctx) -> None:
    """Detect long runs of period-p repeated primitive sequences at any
    jaxpr level: an unrolled Python loop that should be a lax.scan.  The
    intentional small unrolls in this codebase (pow windows' 14-entry
    tables, Kogge–Stone levels, Karatsuba folds) sit well under
    _MIN_REPEATS; unrolls with periods beyond _MAX_PERIOD surface as
    jaxpr-budget growth instead."""
    code_of: dict[str, int] = {}
    for j in _iter_jaxprs(closed.jaxpr):
        eqns = j.eqns
        n = len(eqns)
        if n < _MIN_RUN:
            continue
        codes = np.fromiter(
            (code_of.setdefault(e.primitive.name, len(code_of)) for e in eqns),
            dtype=np.int32,
            count=n,
        )
        best = None  # (repeats, period, start)
        for p in range(1, min(_MAX_PERIOD, n // 2) + 1):
            match = codes[p:] == codes[:-p]
            if not match.any():
                continue
            # longest run of consecutive True
            padded = np.concatenate(([False], match, [False]))
            edges = np.flatnonzero(padded[1:] != padded[:-1])
            starts, ends = edges[0::2], edges[1::2]
            lengths = ends - starts
            k = int(lengths.argmax())
            run = int(lengths[k])
            if run + p < max(_MIN_RUN, _MIN_REPEATS * p):
                continue
            repeats = (run + p) // p
            if best is None or repeats * p > best[0] * best[1]:
                best = (repeats, p, int(starts[k]))
        if best is not None:
            repeats, p, start = best
            ctx.finding(
                "jaxpr-structure",
                eqns[start],
                f"unrolled loop: ~{repeats} repeats of a {p}-eqn chunk "
                f"({repeats * p} inlined eqns) — roll it into lax.scan "
                f"(XLA compile time tracks inlined op count)",
            )


# -- budgets -------------------------------------------------------------------


def load_budgets(path=BUDGETS_PATH) -> dict:
    p = Path(path)
    if not p.exists():
        return {}
    return json.loads(p.read_text()).get("kernels", {})


def save_budgets(counts: dict, path=BUDGETS_PATH) -> None:
    payload = {
        "_comment": (
            "Per-kernel flattened jaxpr primitive counts (trace-only "
            "baseline). Regenerate with `python scripts/lint.py "
            "--update-budgets`; the diff of this file is the explanation "
            "for any compile-cost change a PR makes."
        ),
        "kernels": {k: counts[k] for k in sorted(counts)},
    }
    Path(path).write_text(json.dumps(payload, indent=2) + "\n")


def budget_findings(counts: dict, budgets: dict, registered_names) -> list[Finding]:
    """Zero-tolerance growth gate: any kernel whose flattened eqn count
    exceeds its committed baseline fails (shrinkage is silently fine —
    refresh the baseline to bank it). Missing/stale baseline entries fail
    too, so the file tracks the registry exactly."""
    out: list[Finding] = []
    path = BUDGETS_PATH.relative_to(REPO_ROOT).as_posix()
    for name, got in sorted(counts.items()):
        base = budgets.get(name)
        if base is None:
            out.append(
                Finding(
                    rule="jaxpr-budget",
                    path=path,
                    line=0,
                    symbol=name,
                    message=(
                        f"kernel has no committed budget baseline "
                        f"(traced {got['eqns']} eqns) — run "
                        f"`python scripts/lint.py --update-budgets`"
                    ),
                )
            )
            continue
        if got["eqns"] > base["eqns"]:
            grew = {
                prim: got["by_prim"].get(prim, 0) - base.get("by_prim", {}).get(prim, 0)
                for prim in set(got["by_prim"]) | set(base.get("by_prim", {}))
            }
            top = sorted(
                ((d, prim) for prim, d in grew.items() if d > 0), reverse=True
            )[:4]
            detail = ", ".join(f"{prim} +{d}" for d, prim in top) or "totals only"
            out.append(
                Finding(
                    rule="jaxpr-budget",
                    path=path,
                    line=0,
                    symbol=name,
                    message=(
                        f"primitive count grew {base['eqns']} -> "
                        f"{got['eqns']} eqns ({detail}): unexplained "
                        f"compile-cost growth — optimize, lax.scan the "
                        f"unroll, or refresh deliberately with "
                        f"--update-budgets"
                    ),
                )
            )
    known = set(registered_names)
    for name in sorted(budgets):
        if name not in known:
            out.append(
                Finding(
                    rule="jaxpr-budget",
                    path=path,
                    line=0,
                    symbol=name,
                    message=(
                        "stale budget baseline: kernel is no longer "
                        "registered — refresh with --update-budgets"
                    ),
                )
            )
    return out


# -- entry points --------------------------------------------------------------


def trace_kernel(spec):
    """Trace one registered kernel to (ClosedJaxpr, input_ranges). Trace
    only — nothing compiles, nothing executes on a device."""
    import jax

    fn, args, ranges = spec.build()
    leaves = jax.tree_util.tree_leaves(args)
    if len(ranges) != len(leaves):
        raise ValueError(
            f"kernel {spec.name!r}: {len(ranges)} input ranges for "
            f"{len(leaves)} argument leaves"
        )
    closed = jax.make_jaxpr(fn)(*args)
    if len(closed.jaxpr.invars) != len(leaves):
        raise ValueError(
            f"kernel {spec.name!r}: traced invars ({len(closed.jaxpr.invars)}) "
            f"!= argument leaves ({len(leaves)})"
        )
    return closed, [Interval(int(lo), int(hi)) for lo, hi in ranges]


def analyze_closed(closed, seeds, spec) -> list[Finding]:
    """All per-kernel analyses (interval, dtype, structure) over an
    already-traced jaxpr."""
    ctx = _Ctx(spec)
    _dtype_findings(closed, spec, ctx)
    _structure_findings(closed, ctx)
    ivals = [
        _coerce_domain(var, iv)
        for var, iv in zip(closed.jaxpr.invars, seeds)
    ]
    _interp(closed.jaxpr, list(closed.consts), ivals, ctx)
    return ctx.findings


def analyze_kernels(
    tiers=("fast",), kernels=None, budgets=None, only=None,
    require_float_path=False,
) -> tuple[list[Finding], dict]:
    """Trace + analyze registered kernels; returns (findings, counts).

    tiers: registry tiers to include ("fast" is the tier-1 gate; add
    "slow" for the full composite kernels). kernels: optional explicit
    name filter. budgets: baseline dict (load_budgets()) to gate against,
    or None to skip the budget comparison (e.g. while refreshing).
    only: substring filter over kernel names (scripts/lint.py --only —
    the big slow-tier composites take minutes each to trace, so
    all-or-nothing is not a workable CLI). require_float_path: emit a
    finding when the selection contains no integer_only=False kernel,
    so the jaxpr-float-exact gate can never pass vacuously (mirrors the
    >=15-kernel guard in tests/test_jaxpr_lint.py)."""
    from ..crypto.bls.jax_backend import registry

    specs = registry.kernel_specs(tiers=tiers)
    if kernels is not None:
        wanted = set(kernels)
        specs = [s for s in specs if s.name in wanted]
    if only:
        specs = [s for s in specs if only in s.name]
    findings: list[Finding] = []
    counts: dict = {}
    for spec in specs:
        closed, seeds = trace_kernel(spec)
        counts[spec.name] = count_primitives(closed)
        findings.extend(analyze_closed(closed, seeds, spec))
    if require_float_path and not any(not s.integer_only for s in specs):
        findings.append(
            Finding(
                rule="jaxpr-float-exact",
                path="lighthouse_tpu/crypto/bls/jax_backend/registry.py",
                line=0,
                symbol="<registry>",
                message=(
                    "vacuous float-exactness gate: no float-path kernel "
                    "(integer_only=False, e.g. fp.mul_mxu) was traced in "
                    "this selection — register one or widen the "
                    "tier/filter selection"
                ),
            )
        )
    if budgets is not None:
        findings.extend(budget_findings(counts, budgets, registry.kernel_names()))
    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.symbol))
    return findings, counts
