#!/usr/bin/env python
"""Unified source-lint runner (spark_tpu/analysis/lints framework).

One registry of AST passes over the repository — metric prefixes,
conf-key registration, fault-site wiring, tracer-leak shapes, the
concurrency analyzer's guarded-by and lock-order passes — run together
from preflight and tests/test_analysis.py.

Usage:
    scripts/lint.py --all            # every registered pass
    scripts/lint.py --list           # show the pass catalog
    scripts/lint.py --json [...]     # machine-readable findings
    scripts/lint.py conf-key ...     # named subset

--json emits one JSON object on stdout:
    {"ok": bool, "passes": [...],
     "violations": [{"pass", "code", "severity", "path", "line",
                     "message"}, ...],
     "notes": ["waiver: ...", ...]}
CI/preflight gates on exit status (nonzero iff any error-severity
violation) or on the `violations` array directly; `notes` carries the
reviewer-visible guarded-by waiver list and lock-order graph summary.
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(names=None, collect_notes=None):
    """All violations as 'path:line: [pass] message' strings (empty =
    clean tree)."""
    sys.path.insert(0, REPO)
    from spark_tpu.analysis.lints import run_passes
    return [v.render()
            for v in run_passes(names, collect_notes=collect_notes)]


def main(argv) -> int:
    sys.path.insert(0, REPO)
    from spark_tpu.analysis.lints import LINT_PASSES, run_passes
    from spark_tpu.analysis.lints import passes as _passes  # noqa: F401
    as_json = "--json" in argv
    args = [a for a in argv if a not in ("--all", "--json")]
    if "--list" in args:
        from spark_tpu.analysis.concurrency import (  # noqa: F401
            lint_passes as _cpasses)
        for name in sorted(LINT_PASSES):
            print(f"{name:14s} {LINT_PASSES[name].doc}")
        return 0
    names = args or None
    notes: list = []
    violations = run_passes(names, collect_notes=notes)
    errors = [v for v in violations if v.severity == "error"]
    if as_json:
        print(json.dumps({
            "ok": not errors,
            "passes": names or sorted(LINT_PASSES),
            "violations": [v.to_dict() for v in violations],
            "notes": notes,
        }, indent=2))
        return 1 if errors else 0
    label = ",".join(names) if names else "all passes"
    if errors:
        print(f"lint ({label}): FAILED")
        for v in violations:
            print("  " + v.render())
        return 1
    if violations:
        # warn/info only: surfaced, never failing — the verdict must
        # agree with the exit status (and with --json's `ok` field)
        print(f"lint ({label}): ok with {len(violations)} warning(s)")
        for v in violations:
            print("  " + v.render())
        return 0
    n = len(names) if names else len(LINT_PASSES)
    print(f"lint ({label}): ok ({n} passes, 0 violations)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
