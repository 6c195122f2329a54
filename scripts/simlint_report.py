"""simlint CI reporter: run every pass, always emit the JSONL artifact.

Thin wrapper over `python -m wittgenstein_tpu.analysis` for CI: runs the
same ten passes (AST lint, registry coverage, the abstract-eval
contract tiers, beat RNG audit, SLO catalog, concurrency contract
checker, ...), writes one JSON object per finding to the output file
(plus a trailing summary record, so a clean run still produces a
non-empty artifact a dashboard can ingest), prints the human-readable
lines, and exits nonzero on any finding — CI treats simlint as strict.

Usage: python scripts/simlint_report.py [out.jsonl]   (default ./simlint_findings.jsonl)
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
from wittgenstein_tpu.analysis.cli import run  # noqa: E402
from wittgenstein_tpu.analysis.findings import RULES, Severity  # noqa: E402


def main(argv) -> int:
    out_path = argv[1] if len(argv) > 1 else "simlint_findings.jsonl"
    findings = run(ROOT)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))

    by_rule = {}
    for f in findings:
        print(f.format())
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1

    with open(out_path, "w", encoding="utf-8") as fh:
        for f in findings:
            fh.write(f.to_json() + "\n")
        fh.write(json.dumps({
            "record": "summary",
            "total": len(findings),
            "errors": sum(
                1 for f in findings if f.severity is Severity.ERROR
            ),
            "by_rule": by_rule,
            "rules_known": sorted(RULES),
        }, sort_keys=True) + "\n")

    print(
        f"simlint_report: {len(findings)} finding(s) -> {out_path}",
        file=sys.stderr,
    )
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
