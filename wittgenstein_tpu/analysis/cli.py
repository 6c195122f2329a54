"""simlint command line: `python -m wittgenstein_tpu.analysis [opts]`.

Runs up to ten passes and prints findings as `path:line: RULE [sev] msg`
(or JSONL with --format json):

  1. AST lint over every wittgenstein_tpu/*.py  (SL1xx/SL2xx)
  2. registry/test coverage meta-rule           (SL301)
  3. SLO alert catalog audit                    (SL1101)
  4. concurrency contract checker               (SL1301-SL1307)
  5. pinned-regression audit                    (SL1401)
  6. abstract-eval contract checks              (SL401-SL404)
  7. beat RNG audit                             (SL405)
  8. checkpoint completeness                    (SL501)
  9. phase-annotation presence                  (SL601)
 10. serve scheduler batching contract          (SL801)
 11. 2D-mesh replicated-leaf audit              (SL1001)

Exit status: 0 when clean; 1 when any ERROR finding (or, with --strict,
any finding at all) survives suppression; 2 on usage errors.  Passes 6-10
build every registered protocol and trace real kernels, so they take tens
of seconds — `--skip-contracts` runs just the fast text-level passes
(1-5; no JAX import; the SL1401 audit then checks structure only,
skipping its plan-lowering depth); `--skip-concurrency` drops the
lock-discipline pass from either mode.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from .findings import Finding, Severity


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m wittgenstein_tpu.analysis",
        description="simlint: static + abstract-eval contract checker for "
        "batched protocols and jit paths",
    )
    p.add_argument("--root", default=".",
                   help="repo root containing wittgenstein_tpu/ (default .)")
    p.add_argument("--strict", action="store_true",
                   help="exit nonzero on ANY finding, warnings included")
    p.add_argument("--format", choices=("text", "json"), default="text",
                   help="findings as text lines or JSONL")
    p.add_argument("-o", "--output", default=None,
                   help="also write findings (JSONL) to this file")
    p.add_argument("--skip-contracts", action="store_true",
                   help="skip the abstract-eval + RNG passes (AST and "
                   "registry rules only; no JAX import)")
    p.add_argument("--skip-concurrency", action="store_true",
                   help="skip the concurrency contract checker "
                   "(SL1301-SL1307)")
    p.add_argument("--protocol", action="append", default=None,
                   metavar="NAME",
                   help="restrict contract/RNG passes to this registered "
                   "protocol (repeatable)")
    return p


def _rel(path: str, root: str) -> str:
    try:
        rel = os.path.relpath(path, root)
    except ValueError:
        return path
    return path if rel.startswith("..") else rel


def run(root: str, skip_contracts: bool = False,
        protocols: Optional[List[str]] = None,
        skip_concurrency: bool = False) -> List[Finding]:
    """All passes over `root`; returns the surviving findings."""
    import dataclasses

    from .ast_lint import lint_package
    from .registry_check import check_registry_coverage

    # the AST pass covers the package tree only: tests/ hosts deliberately
    # bad fixtures for simlint's own test suite
    findings = list(lint_package(os.path.join(root, "wittgenstein_tpu")))
    findings += check_registry_coverage(root)
    from .slo_check import check_slo_catalog

    findings += check_slo_catalog(root)
    if not skip_concurrency:
        from .concurrency_check import check_concurrency

        findings += check_concurrency(root)
    if skip_contracts:
        from .regressions_check import check_regressions

        # pinned-regression audit (SL1401) at structural depth — the
        # lowering depth runs in the contracts block below instead (one
        # call either way, so a bad pin is reported exactly once)
        findings += check_regressions(root, lower=False)
    findings = [
        dataclasses.replace(f, path=_rel(f.path, root)) for f in findings
    ]

    if not skip_contracts:
        # pin the platform BEFORE anything imports jax: the contract
        # passes must run identically on a CPU-only CI box
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from .annotations_check import check_annotations
        from .checkpoint_check import check_checkpoints
        from .contracts import check_all
        from .rng_audit import audit_all

        if protocols:
            from ..core.registries import registry_batched_protocols

            unknown = set(protocols) - set(registry_batched_protocols.names())
            if unknown:
                raise SystemExit(
                    "simlint: unknown protocol(s): "
                    + ", ".join(sorted(unknown))
                    + " (known: "
                    + ", ".join(registry_batched_protocols.names())
                    + ")"
                )
        findings += check_all(root=root, names=protocols)
        findings += audit_all(root=root, names=protocols)
        findings += check_checkpoints(root=root, names=protocols)
        findings += check_annotations(root=root, names=protocols)
        from .serve_check import check_serve_scheduler

        findings += check_serve_scheduler(root=root, names=protocols)
        from .mesh_check import check_mesh_layout

        findings += check_mesh_layout(root=root, names=protocols)
        from .regressions_check import check_regressions

        findings += [
            dataclasses.replace(f, path=_rel(f.path, root))
            for f in check_regressions(root, lower=True)
        ]
    return findings


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    root = os.path.abspath(args.root)
    if not os.path.isdir(os.path.join(root, "wittgenstein_tpu")):
        print(f"simlint: no wittgenstein_tpu/ package under {root}",
              file=sys.stderr)
        return 2

    findings = run(root, skip_contracts=args.skip_contracts,
                   protocols=args.protocol,
                   skip_concurrency=args.skip_concurrency)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))

    lines = [
        f.to_json() if args.format == "json" else f.format()
        for f in findings
    ]
    for ln in lines:
        print(ln)
    if args.output:
        with open(args.output, "w", encoding="utf-8") as fh:
            for f in findings:
                fh.write(f.to_json() + "\n")

    n_err = sum(1 for f in findings if f.severity is Severity.ERROR)
    n_warn = len(findings) - n_err
    tail = f"simlint: {n_err} error(s), {n_warn} warning(s)"
    print(tail if findings else "simlint: clean", file=sys.stderr)

    if n_err or (args.strict and findings):
        return 1
    return 0
