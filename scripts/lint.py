#!/usr/bin/env python
"""Run the repo-native analyzers (lighthouse_tpu/analysis) over the tree.

    python scripts/lint.py            # human-readable report (AST lints)
    python scripts/lint.py --check    # CI gate: exit 1 on any unallowlisted
                                      # finding or stale allowlist entry
    python scripts/lint.py --json     # machine-readable findings
    python scripts/lint.py network/   # lint a subset (paths relative to repo)

    python scripts/lint.py --jaxpr            # ALSO run the jaxpr kernel
                                              # analyses (fast tier: interval
                                              # overflow proofs, dtype/
                                              # structure lints, budgets)
    python scripts/lint.py --jaxpr --all-tiers  # include the slow composites
                                              # (miller/final-exp/h2c/verify
                                              # pipeline; several minutes of
                                              # trace time)
    python scripts/lint.py --update-budgets   # refresh the committed op-count
                                              # baseline (all tiers; the diff
                                              # of scripts/jaxpr_budgets.json
                                              # is the explanation reviewers
                                              # see)
    python scripts/lint.py --jaxpr --only fp.mul   # trace/analyze only the
                                              # kernels whose name contains
                                              # the substring (both tiers —
                                              # slow composites are ~150 s
                                              # each, all-or-nothing is not
                                              # workable); --json works too.
                                              # With --update-budgets, only
                                              # the matching entries are
                                              # rewritten (merge, not wipe)

Allowlist: scripts/lint_allowlist.txt — one `rule:path:symbol` per line,
each with a mandatory `  # one-line justification`. Unjustified or stale
entries fail the run: suppressions are reviewed code, not a dumping ground.

The default (AST-only) path is deliberately free of jax imports — the
analyzers read source, they never execute it, so `--check` runs in a few
seconds anywhere. `--jaxpr` imports jax and TRACES the registered BLS
kernels (crypto/bls/jax_backend/registry.py) to closed jaxprs — still
trace-only (no compilation, no device), ~1 min for the fast tier on CPU.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

from lighthouse_tpu.analysis.engine import (  # noqa: E402
    LintConfigError,
    apply_allowlist,
    load_allowlist,
    run_lints,
)
from lighthouse_tpu.analysis.lints import default_checkers  # noqa: E402

DEFAULT_PATHS = ["lighthouse_tpu", "scripts"]
ALLOWLIST = REPO_ROOT / "scripts" / "lint_allowlist.txt"


def _jaxpr_findings(all_tiers: bool, update_budgets: bool, only: str | None):
    """Deferred import: jax only loads under --jaxpr/--update-budgets."""
    from lighthouse_tpu.analysis import jaxpr_lint

    tiers = (
        ("fast", "slow")
        if (all_tiers or update_budgets or only)
        else ("fast",)
    )
    budgets = None if update_budgets else jaxpr_lint.load_budgets()
    findings, counts = jaxpr_lint.analyze_kernels(
        tiers=tiers,
        budgets=None if only else budgets,
        only=only,
        # a filtered selection may legitimately contain no float-path
        # kernel; the unfiltered gate must never be vacuously green
        require_float_path=only is None,
    )
    if only and not counts:
        raise LintConfigError(f"--only {only!r} matched no registered kernel")
    if only and not update_budgets and budgets is not None:
        # per-kernel budget comparison for just the selection (skip the
        # registry-staleness sweep, which needs the full kernel set)
        findings = findings + [
            f
            for f in jaxpr_lint.budget_findings(
                counts, budgets, jaxpr_lint_registry_names()
            )
            if f.symbol in counts
        ]
        findings.sort(key=lambda f: (f.path, f.line, f.rule, f.symbol))
    if update_budgets:
        if only:  # merge: refresh matching entries, keep the rest
            merged = jaxpr_lint.load_budgets()
            merged.update(counts)
            known = set(jaxpr_lint_registry_names())
            merged = {k: v for k, v in merged.items() if k in known}
            jaxpr_lint.save_budgets(merged)
        else:
            jaxpr_lint.save_budgets(counts)
        print(
            f"wrote {jaxpr_lint.BUDGETS_PATH.relative_to(REPO_ROOT)} "
            f"({len(counts)} kernel(s) refreshed)",
            file=sys.stderr,
        )
    return findings


def jaxpr_lint_registry_names():
    from lighthouse_tpu.crypto.bls.jax_backend import registry

    return registry.kernel_names()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", default=None, help="files/dirs (default: lighthouse_tpu)")
    ap.add_argument("--check", action="store_true", help="exit 1 on unallowlisted findings")
    ap.add_argument("--json", action="store_true", dest="as_json", help="JSON output")
    ap.add_argument(
        "--jaxpr",
        action="store_true",
        help="also trace+analyze the registered BLS kernels (interval "
        "overflow proofs, dtype/structure lints, op-count budgets)",
    )
    ap.add_argument(
        "--all-tiers",
        action="store_true",
        help="with --jaxpr: include the slow-tier composites (several "
        "minutes of trace time)",
    )
    ap.add_argument(
        "--update-budgets",
        action="store_true",
        help="refresh scripts/jaxpr_budgets.json from the current tree "
        "(implies --jaxpr --all-tiers; skips the budget comparison)",
    )
    ap.add_argument(
        "--only",
        metavar="SUBSTR",
        default=None,
        help="with --jaxpr/--update-budgets: restrict to kernels whose "
        "registry name contains SUBSTR (searches both tiers; with "
        "--update-budgets, merges the refreshed entries into the baseline)",
    )
    ap.add_argument(
        "--allowlist", default=str(ALLOWLIST), help="allowlist file (default: %(default)s)"
    )
    args = ap.parse_args(argv)

    if args.only and not (args.jaxpr or args.update_budgets):
        ap.error("--only requires --jaxpr or --update-budgets")

    paths = args.paths or DEFAULT_PATHS
    try:
        entries = load_allowlist(args.allowlist)
        findings = run_lints(paths, default_checkers(), root=REPO_ROOT)
        if args.jaxpr or args.update_budgets:
            findings = findings + _jaxpr_findings(
                args.all_tiers, args.update_budgets, args.only
            )
            findings.sort(key=lambda f: (f.path, f.line, f.rule))
        kept, suppressed, stale = apply_allowlist(findings, entries)
    except LintConfigError as e:
        print(f"lint configuration error: {e}", file=sys.stderr)
        return 2

    if args.as_json:
        print(
            json.dumps(
                {
                    "findings": [f.to_dict() for f in kept],
                    "suppressed": [f.to_dict() for f in suppressed],
                    "stale_allowlist_entries": [e.key for e in stale],
                },
                indent=2,
            )
        )
    else:
        for f in kept:
            print(f.format())
        for e in stale:
            print(f"{args.allowlist}:{e.lineno}: stale allowlist entry {e.key!r} (matches nothing — delete it)")
        print(
            f"{len(kept)} finding(s), {len(suppressed)} suppressed, "
            f"{len(stale)} stale allowlist entr(ies)"
        )

    if args.check and (kept or stale):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
