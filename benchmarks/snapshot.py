"""Shared ``BENCH_*.json`` snapshot writer.

Every benchmark that wants its numbers *tracked across PRs* writes a
snapshot through here: a single JSON file at the repo root named
``BENCH_<name>.json`` carrying the git SHA, the benchmark's config, and
its metrics.  Committing the file per PR gives future re-anchors a perf
trajectory instead of a point measurement.

Two producers:

* ``benchmarks/bench_serve.py`` builds its metrics dict directly
  (arrival-rate sweeps -> p50/p99 TTFT / ITL / tok/s).
* ``benchmarks/run.py --json`` routes the existing table benches
  (bench_comm, bench_mlp, bench_kernels, ...) through
  ``tables_from_lines`` to turn their CSV transcript into structured
  ``{"tables": [...]}`` metrics.
"""

from __future__ import annotations

import json
import os
import subprocess
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def git_sha(short: bool = True) -> str:
    try:
        args = ["git", "rev-parse"] + (["--short"] if short else [])
        return subprocess.run(
            args + ["HEAD"], cwd=REPO_ROOT, capture_output=True,
            text=True, check=True, timeout=10).stdout.strip()
    except Exception:
        return "unknown"


def _environment() -> dict:
    import jax

    env = {"jax": jax.__version__,
           "backend": jax.default_backend(),
           "device_count": jax.device_count(),
           "process_count": jax.process_count()}
    # the DP×TP grid the numbers were taken on (DESIGN.md §11) —
    # single-host benches report the trivial dp1xtp<N> shape only
    # when a mesh plan was exported by the runner
    plan = os.environ.get("REPRO_MESH")
    if plan:
        env["mesh"] = plan
    return env


def write(name: str, *, config: dict, metrics: dict,
          out_dir: str = REPO_ROOT) -> str:
    """Write ``BENCH_<name>.json``; returns the path."""
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    payload = {
        "bench": name,
        "git_sha": git_sha(),
        "created": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "environment": _environment(),
        "config": config,
        "metrics": metrics,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=False)
        f.write("\n")
    return path


def load(name: str, out_dir: str = REPO_ROOT) -> dict:
    with open(os.path.join(out_dir, f"BENCH_{name}.json")) as f:
        return json.load(f)


def tables_from_lines(lines) -> list[dict]:
    """Parse a bench transcript (the ``run(out_lines)`` accumulation:
    ``# title`` lines, CSV headers, CSV rows) into structured tables.

    Tolerant by construction — a line is a table title if it starts
    with ``#``, a header if it contains a comma while no table is open,
    a row if it contains a comma under an open header; anything else
    closes the current table.  Numeric cells are converted.
    """
    tables: list[dict] = []
    current = None
    for raw in lines:
        line = str(raw).strip()
        if not line or line.startswith("==="):
            current = None
            continue
        if line.startswith("#"):
            current = {"title": line.lstrip("# "), "columns": None,
                       "rows": []}
            tables.append(current)
            continue
        if "," not in line:
            current = None
            continue
        cells = [c.strip() for c in line.split(",")]
        if current is None or current["columns"] is None:
            if current is None:
                current = {"title": "", "columns": None, "rows": []}
                tables.append(current)
            current["columns"] = cells
            continue
        current["rows"].append([_cell(c) for c in cells])
    return [t for t in tables if t["columns"] is not None]


def _cell(text: str):
    for typ in (int, float):
        try:
            return typ(text)
        except ValueError:
            pass
    return text
