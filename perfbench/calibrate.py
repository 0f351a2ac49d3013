#!/usr/bin/env python3
"""Readings the correctness limits are set from, in one process.

    python3 perfbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds <n,n,...> [--out <file.jsonl>]

Each seed is one control run of the cell as ``run.py`` makes it (weights
from the seed, the cell's traffic for ``--seconds``): the plain reference
reads the finished greedy answers twice, the program's served tokens and
the token its float8 control puts first at each of those positions.  One
JSON line per seed: both widest logit gaps, and whether the program and
the control each pass the cell's limits (the control should not).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = os.path.dirname(HERE)

from perfbench import run as bench_run  # noqa: E402
from perfbench import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = spec.load(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        result, run = bench_run.execute(cell, seed, args.seconds, False,
                                        t_start=time.monotonic(),
                                        control=True)
        r = run.reading
        line = {"workload": cell.name, "seed": seed,
                "max_logit_gap": r["gap"],
                "control_max_logit_gap": r["control_gap"],
                "tokens": r["tokens"], "missing_answers": r["missing"],
                "program_correct": bench_run.passed(
                    bench_run.checks(cell, r)),
                "control_correct": result["correct"],
                "reference_s": r["reference_s"],
                "host_rss_peak_bytes": result["host_rss_peak_bytes"],
                "metrics": {k: v["value"]
                            for k, v in result["metrics"].items()},
                "memory_peak_bytes": result["device"]["memory_peak_bytes"]}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
