"""CLI for the static-analysis pass.

Exit status is the CI gate: 0 only when every finding is waived or
baselined (and the baseline itself is well-formed).  ``--summary FILE``
appends one ``findings_by_rule`` JSON line so the counts can be trended
(``harness/check_regression.py --analysis`` gates the trend).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from harness.analysis import core


def _changed_files(root: str, base: str) -> set[str] | None:
    """Repo-relative paths changed since ``base`` (committed AND
    worktree), or None when git can't resolve the rev."""
    try:
        proc = subprocess.run(
            ["git", "diff", "--name-only", base],
            cwd=root, capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if proc.returncode != 0:
        return None
    return {line.strip().replace(os.sep, "/")
            for line in proc.stdout.splitlines() if line.strip()}


def _sarif(report) -> dict:
    """SARIF 2.1.0 log of the unsuppressed findings — the GitHub
    code-scanning upload format.  The driver's ``rules`` table
    enumerates EVERY registered rule exactly once (not just the rules
    that fired), so ``ruleIndex`` is stable across runs and a clean
    run still publishes the full rule inventory."""
    rules = list(core.RULES)
    index = {r: i for i, r in enumerate(rules)}
    return {
        "$schema": "https://json.schemastore.org/sarif-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "eges-analysis",
                "informationUri":
                    "https://example.invalid/eges-tpu/harness/analysis",
                "rules": [{"id": r} for r in rules],
            }},
            "results": [{
                "ruleId": f.rule,
                "ruleIndex": index[f.rule],
                "level": "error",
                "message": {"text": f.message},
                "locations": [{"physicalLocation": {
                    "artifactLocation": {
                        "uri": f.path,
                        "uriBaseId": "SRCROOT",
                    },
                    "region": {"startLine": f.line},
                }}],
            } for f in report.unsuppressed],
        }],
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m harness.analysis",
        description="AST static analysis: lock-discipline, lock-order/"
                    "fail-under-lock, future-lifecycle, determinism, "
                    "jit-purity, vocabulary, robustness-hygiene, "
                    "the device-hygiene pass (host-sync, "
                    "recompile-hazard, transfer-hygiene, "
                    "dtype-promotion) over the verifier hot path, "
                    "the ingress-taint pass, and the "
                    "architecture-conformance pass (layer-violation, "
                    "import-cycle, private-reach, perimeter-breach) "
                    "against the declared layer map.")
    ap.add_argument("paths", nargs="*", default=list(core.DEFAULT_PATHS),
                    help="directories/files to scan (default: eges_tpu "
                         "harness)")
    ap.add_argument("--root", default=None,
                    help="repo root (default: parent of harness/)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as JSON instead of text")
    ap.add_argument("--summary", metavar="FILE", default=None,
                    help="append a findings_by_rule JSON summary line")
    ap.add_argument("--diff", metavar="BASE", default=None,
                    help="gate only findings in files changed since this "
                         "git rev (the whole tree is still analyzed — "
                         "cross-file rules need it — but untouched files "
                         "can't fail the run)")
    ap.add_argument("--github", action="store_true",
                    help="also print ::error workflow annotations for "
                         "unsuppressed findings (GitHub Actions)")
    ap.add_argument("--sarif", metavar="FILE", default=None,
                    help="write unsuppressed findings as a SARIF 2.1.0 "
                         "log (GitHub code-scanning upload format); "
                         "'-' writes to stdout")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the checked-in baseline")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite baseline.json from current unsuppressed "
                         "findings (justifications must then be filled in)")
    args = ap.parse_args(argv)

    root = args.root or os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    rules = tuple(args.rules.split(",")) if args.rules else None
    baseline = None if args.no_baseline else core.DEFAULT_BASELINE

    try:
        report = core.run(root, tuple(args.paths), rules, baseline)
    except core.BaselineError as e:
        print(f"baseline error: {e}", file=sys.stderr)
        return 2

    if args.diff is not None:
        changed = _changed_files(root, args.diff)
        if changed is None:
            print(f"cannot resolve --diff base {args.diff!r}",
                  file=sys.stderr)
            return 2
        # membership, not just the anchor: a multi-file finding (an
        # import cycle) must fire when ANY member file changed, even
        # though it is anchored on the lexicographically-first module
        report.findings = [
            f for f in report.findings
            if f.path in changed
            or any(p in changed for p in f.related_paths)]
        # scoping is a reporting filter only: stale-baseline entries are
        # still judged against the full-tree findings above

    if args.update_baseline:
        core.save_baseline(core.DEFAULT_BASELINE, report.unsuppressed)
        print(f"wrote {len(report.unsuppressed)} entries to "
              f"{core.DEFAULT_BASELINE}; fill in the justifications.")
        return 0

    if args.as_json:
        print(json.dumps({"summary": report.summary_json(),
                          "findings": [f.as_json() for f in report.findings],
                          "stale_baseline": report.stale_baseline,
                          "errors": report.errors}, indent=2))
    else:
        for f in report.findings:
            print(f.render())
        for e in report.errors:
            print(f"error: {e}")
        for e in report.stale_baseline:
            print(f"stale baseline entry (no longer fires): "
                  f"[{e['rule']}] {e['path']} {e['symbol']}")
        for w in report.expiring_waivers:
            print(f"waiver expiring soon: {w['path']}:{w['line']} "
                  f"allow-{w['rule']} until={w['until']}")
        s = report.summary_json()
        print(f"{s['files']} files, {s['findings']} findings "
              f"({s['unsuppressed']} unsuppressed, {s['waived']} waived, "
              f"{s['baselined']} baselined) in {s['elapsed_s']}s")

    if args.github:
        for f in report.unsuppressed:
            print(f"::error file={f.path},line={f.line}::"
                  f"{f.rule}: {f.message}")

    if args.sarif:
        doc = json.dumps(_sarif(report), indent=2, sort_keys=True)
        if args.sarif == "-":
            print(doc)
        else:
            with open(args.sarif, "w", encoding="utf-8") as fh:
                fh.write(doc + "\n")

    if args.summary:
        with open(args.summary, "a", encoding="utf-8") as fh:
            fh.write(json.dumps(report.summary_json(),
                                sort_keys=True) + "\n")

    if report.errors:
        return 2
    return 1 if report.unsuppressed else 0


if __name__ == "__main__":
    sys.exit(main())
