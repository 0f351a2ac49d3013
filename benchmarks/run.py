import os
import sys

# TP benchmarks need multiple host devices (8, like the paper's 8-GPU node).
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# Fallback for `python benchmarks/run.py` without PYTHONPATH=src (the
# documented invocation is `python -m benchmarks.run` from the repo root
# with PYTHONPATH=src): both the repo root (the `benchmarks` package) and
# src/ (`repro`) must be importable before any repro import below.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

"""Benchmark harness: one module per paper table/figure group.

  PYTHONPATH=src python -m benchmarks.run [--only mlp|comm|kernels|fold]
      [--json]

Writes a CSV transcript to results/bench.csv as well as stdout.  With
``--json``, each suite's tables also land in a committed-per-PR
``BENCH_<suite>.json`` snapshot at the repo root (git SHA + config +
structured tables — see benchmarks/snapshot.py), so the perf
trajectory is visible across PRs.

The serving load generator (``serve`` suite) is opt-in via ``--only
serve`` — it spins up a real HTTP/SSE server per TP degree; run
``benchmarks/bench_serve.py`` directly for the full arrival-rate x TP
sweep that produces the committed ``BENCH_serve.json``.
"""

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["mlp", "comm", "kernels", "fold", "quality",
                             "serve"])
    ap.add_argument("--out", default="results/bench.csv")
    ap.add_argument("--json", action="store_true",
                    help="also write a BENCH_<suite>.json snapshot per "
                         "suite at the repo root")
    args = ap.parse_args()

    from benchmarks import (bench_comm, bench_fold, bench_kernels,
                            bench_mlp, bench_quality, bench_serve,
                            snapshot)

    suites = {
        "mlp": bench_mlp.run,        # paper Tables 1-28
        "comm": bench_comm.run,      # collective-bytes accounting
        "kernels": bench_kernels.run,  # Alg.-1 locality (ExllamaV2 kernel)
        "fold": bench_fold.run,      # beyond-paper attention fold
        "quality": bench_quality.run,  # int4 deployment quality ablation
    }
    if args.only == "serve":
        suites = {"serve": bench_serve.run}   # opt-in: boots a server
    elif args.only:
        suites = {args.only: suites[args.only]}

    lines: list = []
    for name, fn in suites.items():
        print(f"\n=== {name} ===")
        lines.append(f"=== {name} ===")
        suite_lines: list = []
        fn(suite_lines)
        lines.extend(suite_lines)
        if args.json and name != "serve":
            # bench_serve writes its own richer BENCH_serve.json
            path = snapshot.write(name, config={"suite": name},
                                  metrics={"tables":
                                           snapshot.tables_from_lines(
                                               suite_lines)})
            print(f"wrote {path}")

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        f.write("\n".join(str(l) for l in lines) + "\n")
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
