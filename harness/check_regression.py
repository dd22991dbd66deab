"""Static-analysis trend gate over ``harness/analysis_history.jsonl``.

The newest ``unsuppressed_by_rule`` line (appended by
``python -m harness.analysis --summary``) is compared
against the previous one, and ANY rise in unsuppressed findings for any
rule fails — zero tolerance, no threshold: suppressions are explicit
(waiver/baseline), so a rise always means un-reviewed debt landed.
Rules absent from the previous line count as zero, so a newly added
rule gates from its first appearance — that is how the architecture
rules (layer-violation, import-cycle, private-reach, perimeter-breach)
entered the gate on day one, with no grace window.  The reverse is NOT symmetric:
a rule present in the previous line but missing from the newest one
fails outright — a renamed or deleted rule would otherwise silently
stop gating while its findings kept accumulating.

Exit codes: 0 ok (or fewer than two comparable entries), 1 regression,
2 unreadable history.

Usage::

    python harness/check_regression.py --analysis [analysis_history.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def load_analysis_history(path: str) -> list[dict]:
    """Lines carrying an ``unsuppressed_by_rule`` map, oldest first."""
    out = []
    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict) and isinstance(
                    obj.get("unsuppressed_by_rule"), dict):
                out.append(obj)
    return out


def check_analysis(entries: list[dict]) -> tuple[int, str]:
    """(exit_code, message): fail on ANY per-rule rise in unsuppressed
    findings between the two newest summary lines, and on any rule
    that disappears from the newest line entirely."""
    if len(entries) < 2:
        return 0, ("ok [analysis]: %d comparable entr%s — nothing to "
                   "compare" % (len(entries),
                                "y" if len(entries) == 1 else "ies"))
    prev = entries[-2]["unsuppressed_by_rule"]
    last = entries[-1]["unsuppressed_by_rule"]
    lines, code = [], 0
    for rule in sorted(set(prev) | set(last)):
        before = int(prev.get(rule, 0))
        if rule not in last:
            code = 1
            lines.append("REGRESSION [analysis:%s]: rule present in the "
                         "previous line is missing from the newest one — "
                         "a renamed or deleted rule silently stops "
                         "gating; keep emitting it (0 is fine)" % rule)
            continue
        after = int(last.get(rule, 0))
        if after > before:
            code = 1
            lines.append("REGRESSION [analysis:%s]: unsuppressed "
                         "findings rose %d -> %d — fix them or add a "
                         "justified waiver/baseline entry"
                         % (rule, before, after))
        elif after or before:
            lines.append("ok [analysis:%s]: %d -> %d unsuppressed"
                         % (rule, before, after))
    if not lines:
        lines.append("ok [analysis]: 0 unsuppressed findings in both "
                     "newest lines")
    return code, "\n".join(lines)


_DEFAULT_ANALYSIS_HISTORY = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "analysis_history.jsonl")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("history", nargs="?", default=None)
    ap.add_argument("--analysis", action="store_true", required=True,
                    help="gate the static-analysis unsuppressed-by-rule "
                         "trend (the one gate this script has)")
    args = ap.parse_args(argv)
    path = args.history or _DEFAULT_ANALYSIS_HISTORY
    try:
        entries = load_analysis_history(path)
    except OSError as e:
        print("cannot read %s: %s" % (path, e), file=sys.stderr)
        return 2
    code, msg = check_analysis(entries)
    print(msg)
    return code


if __name__ == "__main__":
    sys.exit(main())
