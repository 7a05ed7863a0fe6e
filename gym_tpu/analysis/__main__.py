"""CLI: run the full static-analysis suite over the shipped package.

    python -m gym_tpu.analysis [--json PATH] [--nodes K]
                               [--only lint|trace|audit]

Runs the three checkers (host-concurrency lint, static comm-trace
reconciliation, jaxpr program audit), prints a one-line machine-greppable
summary (``violations=N``), writes the full report as JSON, and exits
non-zero iff any unsuppressed violation exists — the contract
``scripts/ci_analyze.sh`` gates on. Pure host work: traces only, no
device programs are compiled or executed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def run_all(num_nodes: int = 4, sections=("lint", "trace", "audit"),
            root: str = None, suppressions: str = None) -> dict:
    """Run the requested sections; returns the analysis.json payload."""
    report = {"sections": {}, "violations": 0}

    if "lint" in sections:
        from .lint import apply_suppressions, load_suppressions, run_lint
        lint_root = root or os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        t0 = time.perf_counter()
        violations = run_lint(lint_root)
        unsup, notes = apply_suppressions(
            violations, load_suppressions(suppressions))
        report["sections"]["lint"] = {
            "total": len(violations),
            "suppressed": len(violations) - len(unsup),
            "unsuppressed": [v.render() for v in unsup],
            "ratchet_notes": notes,
            "violations": len(unsup),
            "seconds": round(time.perf_counter() - t0, 2),
        }
        report["violations"] += len(unsup)

    if "trace" in sections:
        from .trace_check import check_all_strategies
        t0 = time.perf_counter()
        results = check_all_strategies(num_nodes=num_nodes)
        fails = {n: r.summary() for n, r in results.items() if not r.ok}
        report["sections"]["trace"] = {
            "strategies": {n: r.summary() for n, r in results.items()},
            "violations": len(fails),
            "seconds": round(time.perf_counter() - t0, 2),
        }
        report["violations"] += len(fails)

    if "audit" in sections:
        from .jaxpr_audit import audit_shipped_programs
        t0 = time.perf_counter()
        audit = audit_shipped_programs(num_nodes=num_nodes)
        audit["seconds"] = round(time.perf_counter() - t0, 2)
        report["sections"]["audit"] = audit
        report["violations"] += audit["violations"]

    return report


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m gym_tpu.analysis",
        description="static analysis: lint + trace reconciliation + "
                    "jaxpr audit")
    parser.add_argument("--json", default="analysis.json",
                        help="report output path ('' to skip writing)")
    parser.add_argument("--nodes", type=int, default=4,
                        help="simulated node count for the traces")
    parser.add_argument("--only", choices=["lint", "trace", "audit"],
                        action="append",
                        help="run only these sections (repeatable)")
    parser.add_argument("--suppressions", default=None,
                        help="override the lint suppression file")
    args = parser.parse_args(argv)

    sections = tuple(args.only) if args.only else ("lint", "trace", "audit")
    report = run_all(num_nodes=args.nodes, sections=sections,
                     suppressions=args.suppressions)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)

    parts = []
    for name in sections:
        sec = report["sections"][name]
        parts.append(f"{name}={sec['violations']}")
    print(f"gym_tpu.analysis: {' '.join(parts)} "
          f"violations={report['violations']}"
          + (f" (report: {args.json})" if args.json else ""))
    if "lint" in sections:
        for line in report["sections"]["lint"]["unsuppressed"]:
            print(f"  lint: {line}")
        for note in report["sections"]["lint"]["ratchet_notes"]:
            print(f"  lint: {note}")
    if "trace" in sections:
        for name, summ in report["sections"]["trace"]["strategies"].items():
            if not summ["ok"]:
                print(f"  trace: {name} FAILED: {summ['failures']}")
    if "audit" in sections:
        for prog in report["sections"]["audit"]["programs"]:
            for f_ in prog["findings"]:
                print(f"  audit: {prog['name']}: {f_['kind']}: "
                      f"{f_['detail']}")
    return 0 if report["violations"] == 0 else 1


if __name__ == "__main__":
    # the suite only traces — force the cheap backend, with or without
    # an accelerator on the host
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
