"""CLI: ``python -m repro.analysis`` — run the static verification suite.

Examples::

    python -m repro.analysis --ast                  # source hygiene only
    python -m repro.analysis --all                  # everything host-side
    python -m repro.analysis --artifact out/plan    # + offline audit
    python -m repro.analysis --all --json out.json  # machine-readable

Exit code 0 when no ``error``-severity findings (``warn``/``info`` never
gate); 1 otherwise — so CI can use the invocation directly as a gate.
"""

from __future__ import annotations

import argparse
import os
import sys

# the HLO/contract sweeps need a multi-device host platform; set BEFORE
# jax (transitively) imports, harmless when a real backend is present.
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.analysis",
        description="static deployment-invariant linters (DESIGN.md §12)")
    ap.add_argument("--ast", action="store_true",
                    help="AS rules: source hygiene over src/")
    ap.add_argument("--contracts", action="store_true",
                    help="CT rules: eval_shape dtype/shape contracts")
    ap.add_argument("--hlo", action="store_true",
                    help="HL rules: compiled-HLO byte/convert sweep")
    ap.add_argument("--bench", action="store_true",
                    help="BN rules: committed BENCH_*.json schema")
    ap.add_argument("--artifact", default=None, metavar="DIR",
                    help="MF rules: offline audit of a prepared "
                         "DeploymentArtifact directory")
    ap.add_argument("--all", action="store_true",
                    help="every host-side linter (AST + contracts + HLO + "
                         "bench; add --artifact for the manifest audit)")
    ap.add_argument("--tp", type=int, nargs="*", default=(2, 4, 8),
                    help="TP degrees for the contract/HLO sweeps")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write the findings summary as JSON")
    args = ap.parse_args(argv)

    run_ast = args.ast or args.all
    run_contracts = args.contracts or args.all
    run_hlo = args.hlo or args.all
    run_bench = args.bench or args.all or bool(args.artifact)
    if not (run_ast or run_contracts or run_hlo or run_bench
            or args.artifact):
        ap.error("pick at least one of --ast/--contracts/--hlo/--bench/"
                 "--artifact (or --all)")

    from repro.analysis.findings import has_errors, summarize, to_json_text

    findings = []
    if run_ast:
        from repro.analysis import ast_lint
        found = ast_lint.run()
        findings += found
        print(f"ast_lint: {len(found)} finding(s)")
    if run_contracts:
        from repro.analysis import contracts
        found = contracts.run(tps=(1, *args.tp))
        findings += found
        print(f"contracts: {len(found)} finding(s)")
    if run_hlo:
        from repro.analysis import hlo_lint
        found = hlo_lint.run(tps=tuple(args.tp))
        findings += found
        print(f"hlo_lint: {len(found)} finding(s)")
    if run_bench or args.artifact:
        from repro.analysis import manifest_lint
        found = manifest_lint.run(
            artifact=args.artifact) if run_bench else (
            manifest_lint.lint_artifact(args.artifact))
        findings += found
        print(f"manifest_lint: {len(found)} finding(s)")

    for f in findings:
        print(f"  {f}")
    summary = summarize(findings)
    print(f"{len(findings)} finding(s), "
          f"{summary['counts'].get('error', 0)} error(s)")
    if args.json:
        with open(args.json, "w") as fh:
            fh.write(to_json_text(findings))
        print(f"wrote {args.json}")
    return 1 if has_errors(findings) else 0


if __name__ == "__main__":
    sys.exit(main())
