"""CLI: ``python -m tools.dtxlint [--json] [--baseline FILE] [--root DIR]
[--pass NAME] [--changed [--base REF]]``.

Exit codes: 0 = clean (no non-suppressed findings), 1 = findings, 2 = the
linter itself failed (missing inputs, unparseable baseline).

``--changed`` is the pre-commit fast path: lint only what a diff against
``--base`` (default HEAD, untracked files included) could have broken —
cross-file passes (concurrency included: lock-order inversions span
files) run in full when any of their inputs changed, per-file passes
lint only the changed files, and stale-suppression accounting is OFF (a
suppression for an unlinted file is not stale).  On the files it
does lint, output matches the full run exactly (parity pinned by
tests/test_dtxlint.py).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from . import (
    JSON_SCHEMA_VERSION, LintConfig, PASS_NAMES, apply_baseline,
    load_baseline, run_passes,
)

DEFAULT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def changed_files(root: str, base: str = "HEAD") -> list[str]:
    """Absolute paths of files changed vs ``base`` (worktree + index) plus
    untracked files — the corpus a pre-commit lint must cover.  Raises
    OSError (-> rc 2) when ``root`` is not a git checkout: silently
    linting nothing would read as clean."""
    out: list[str] = []
    for cmd in (
        # --relative: diff paths come back relative to ROOT even when the
        # repo toplevel is an ancestor (vendored checkout) — without it
        # the join below doubles the prefix, every path misses the pass
        # inputs, and a dirty tree reads as clean.  ls-files is already
        # cwd-relative.
        ["git", "-C", root, "diff", "--relative", "--name-only", base, "--"],
        ["git", "-C", root, "ls-files", "--others", "--exclude-standard"],
    ):
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise OSError(
                f"--changed: {' '.join(cmd[3:])} failed in {root}: "
                f"{proc.stderr.strip()}"
            )
        out.extend(
            os.path.join(root, line)
            for line in proc.stdout.splitlines() if line.strip()
        )
    return sorted(set(out))


def build_report(results, active, suppressed, stale, baseline_path) -> dict:
    """The --json document (schema pinned by tests/test_dtxlint.py)."""
    return {
        "schema_version": JSON_SCHEMA_VERSION,
        "ok": not active and not stale,
        "passes": {
            name: {"findings": len(fs)} for name, fs in results.items()
        },
        "counts": {
            "active": len(active),
            "suppressed": len(suppressed),
            "stale_suppressions": len(stale),
        },
        "findings": [f.to_dict() for f in active],
        "suppressed": [f.to_dict() for f in suppressed],
        "stale_suppressions": stale,
        "baseline": baseline_path,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="dtxlint",
        description="wire-conformance + concurrency + fault-coverage + "
        "flag-drift static analysis for this repo",
    )
    ap.add_argument("--root", default=DEFAULT_ROOT, help="repo root")
    ap.add_argument(
        "--baseline", default=None,
        help="suppression file (default: <root>/tools/dtxlint_baseline.json)",
    )
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument(
        "--compact", action="store_true",
        help="with --json: one line of JSON (tools/dtxlint_step.py's "
        "shape: callers parse the last stdout line)",
    )
    ap.add_argument(
        "--pass", dest="only", default=None, choices=PASS_NAMES,
        help="run a single pass",
    )
    ap.add_argument(
        "--changed", action="store_true",
        help="lint only what a diff against --base could have broken "
        "(the pre-commit fast path)",
    )
    ap.add_argument(
        "--base", default="HEAD",
        help="with --changed: the git ref to diff against (default HEAD)",
    )
    args = ap.parse_args(argv)

    cfg = LintConfig.default(args.root)
    baseline_path = args.baseline or os.path.join(
        args.root, "tools", "dtxlint_baseline.json"
    )
    try:
        baseline = load_baseline(baseline_path)
        changed = changed_files(args.root, args.base) if args.changed else None
        results = run_passes(cfg, only=args.only, changed=changed)
    except (OSError, ValueError, SyntaxError) as e:
        print(f"dtxlint: error: {e}", file=sys.stderr)
        return 2
    if args.only is not None:
        # A single-pass run must not report every other pass's
        # suppressions as stale.
        baseline = {
            k: v for k, v in baseline.items()
            if k.split(":", 1)[0] == args.only
        }
    active, suppressed, stale = apply_baseline(results, baseline)
    if args.changed:
        # A suppression whose file was not linted this run is not stale —
        # only the full run owns stale accounting.
        stale = []

    if args.as_json:
        report = build_report(results, active, suppressed, stale, baseline_path)
        print(json.dumps(report, indent=None if args.compact else 1))
    else:
        for f in active:
            loc = f"{f.path}:{f.line}" if f.line else f.path
            print(f"[{f.pass_name}] {f.code} {loc} ({f.symbol})\n    {f.message}")
        for key in stale:
            print(f"[baseline] stale suppression (matched nothing): {key}")
        total = sum(len(fs) for fs in results.values())
        print(
            f"dtxlint: {len(active)} finding(s), {len(suppressed)} "
            f"suppressed, {len(stale)} stale suppression(s) "
            f"({total} raw across {len(results)} pass(es))"
        )
    return 0 if (not active and not stale) else 1


if __name__ == "__main__":
    sys.exit(main())
