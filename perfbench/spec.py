"""A cell as ``BENCHMARK.json`` and its files define it."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict            # configs/<config>.json
    traffic: dict         # traffic/<mix>.json
    max_batch: int
    max_seq: int
    limits: dict          # number compared -> its limit
    sample: int           # greedy requests the reference reads per run
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _json(path):
    with open(path) as f:
        return json.load(f)


def load(workload: str, root: str = ROOT) -> Cell:
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    here = os.path.join(root, "perfbench")
    own = _json(os.path.join(here, "cells", workload + ".json"))

    def applies(m, default):
        return workload in m["workloads"] if "workloads" in m else default

    e2e = [m for m in bench["end_to_end"] if applies(m, True)]
    reported = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"] if applies(m, m["moves"] in reported)]
    return Cell(name=workload, chips=w["chips"],
                conf=_json(os.path.join(root, conf_file)),
                traffic=_json(os.path.join(here, "traffic",
                                           w["traffic"] + ".json")),
                max_batch=own["max_batch"], max_seq=own["max_seq"],
                limits=own["limits"], sample=own["sample"],
                end_to_end=e2e, per_layer=per)


def reader(metric: str, root: str = ROOT):
    """``read(run)`` of ``metrics/<metric>.py``."""
    path = os.path.join(root, "perfbench", "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "perfbench.metrics." + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
