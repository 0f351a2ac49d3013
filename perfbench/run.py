#!/usr/bin/env python3
"""One run of one benchmark cell on the chip(s) of this machine.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's weights on the device from the seed, the serving
engine and its loop, and warms every program the window uses.  The window
then offers the cell's traffic for ``--seconds``; with ``--trace 1`` a few
seconds in its middle are traced.  After it the program's state is freed
and the plain reference reads a sample of the greedy requests it
finished.  The last line of standard output is the JSON result; the last
lines of standard error give each number compared beside its limit.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from typing import Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# this directory's module names (trace, traffic...) must not shadow others
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path[0] = ROOT
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from perfbench import (arith, build, drive, reference, spec,  # noqa: E402
                       trace, traffic, weights)

#: seconds traced, in the middle of the window, by a ``--trace 1`` run
TRACE_S = 3.0


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Run:
    """What a metric's ``read(run)`` sees."""

    cell: spec.Cell
    chips: int
    device_kind: str
    seconds: float
    setup_s: float
    t0: float
    t_end: float
    records: list
    steps: tuple                     # engine steps at t0 and t_end
    admissions: dict                 # request id -> step it was admitted
    engine_stats: dict = dataclasses.field(default_factory=dict)
    reading: dict = dataclasses.field(default_factory=dict)
    trace: Optional[trace.Summary] = None
    trace_steps: Optional[tuple] = None

    def lanes(self, lo: int, hi: int):
        """``(request record, first position, steps)`` of every slot
        lane live in engine steps ``[lo, hi)``."""
        out = []
        for rec in self.records:
            if rec.stream is None or rec.stream.rid not in self.admissions:
                continue
            a = self.admissions[rec.stream.rid]
            if rec.end == "done":
                e = a + rec.req.prompt.size + len(rec.tokens) - 1
            else:
                e = max(hi, a + rec.req.prompt.size + len(rec.tokens) - 1)
            s0, s1 = max(a, lo), min(e, hi)
            if s1 > s0:
                out.append((rec, s0 - a, s1 - s0))
        return out


#: host times of this process's XLA compiles, once ``_listen_for_compiles``
#: has run
_COMPILES: list = []
_LISTENING: list = []


def _listen_for_compiles():
    import jax

    if not _LISTENING:
        _LISTENING.append(True)
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, dur, **kw: _COMPILES.append(time.monotonic())
            if event == "/jax/core/compile/backend_compile_duration" else None)


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def _steps(loop) -> int:
    return loop.stats()["engine"]["steps"]


def _warm(loop, cell):
    """Run every program the window runs once: a greedy and a sampled
    request through prompt replay, first emission and later emissions."""
    prompt = np.arange(2, dtype=np.int32)
    streams = [loop.submit(prompt, max_new_tokens=3, temperature=t, seed=1)
               for t in (0.0, cell.traffic["temperature"])]
    for s in streams:
        while s.events.get()[0] == "token":
            pass


class _Watch(threading.Thread):
    """Reads the engine's step counter when the window opens and closes,
    and with ``trace_dir`` traces ``TRACE_S`` seconds in its middle."""

    def __init__(self, loop, t0, seconds, trace_dir):
        super().__init__(daemon=True)
        self.loop, self.t0, self.seconds = loop, t0, seconds
        self.trace_dir = trace_dir
        self.steps = self.trace_steps = None

    def _until(self, t):
        time.sleep(max(0.0, t - time.monotonic()))

    def run(self):
        import jax

        self._until(self.t0)
        s0 = _steps(self.loop)
        if self.trace_dir:
            self._until(self.t0 + max(0.0, (self.seconds - TRACE_S) / 2))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                a = _steps(self.loop)
                time.sleep(TRACE_S)
                b = _steps(self.loop)
            jax.profiler.stop_trace()
            self.trace_steps = (a, b)
        self._until(self.t0 + self.seconds)
        self.steps = (s0, _steps(self.loop))


def _free(loop):
    """Delete the program's weights and cache from the device."""
    import jax

    sched = loop.scheduler
    for leaf in jax.tree.leaves((sched.engine.params, sched._cache)):
        leaf.delete()
    sched.engine.params = sched._cache = None
    gc.collect()


def compare(cell, key, recs, seed, control=False) -> dict:
    """The plain reference over a seeded sample of the finished greedy
    answers, the longest of them included: the widest gap of a served
    token below the reference's best (and with ``control``, of the token
    the float8 control puts first at the same positions), the tokens
    compared, the answers missing and the reference's wall time."""
    missing = sum(1 for r in recs if r.error is not None
                  or (r.due is not None and not r.complete))
    done = [r for r in recs if r.req.greedy and r.complete]
    out = {"missing": missing, "gap": None, "control_gap": None,
           "tokens": 0, "reference_s": 0.0}
    if not done:
        return out
    longest = max(done, key=lambda r: r.req.prompt.size + r.req.max_new)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) % 2**64 + 1)
    pick = [longest] + [rest[i] for i in rng.choice(
        len(rest), min(cell.sample - 1, len(rest)), replace=False)]
    t = time.monotonic()
    res = reference.Reference(cell.conf, key).gaps(
        [np.concatenate([r.req.prompt, r.tokens]) for r in pick],
        [r.req.prompt.size for r in pick], control)
    out["reference_s"] = time.monotonic() - t
    out["gap"] = float(max(g.max() for g, _ in res))
    out["tokens"] = int(sum(g.size for g, _ in res))
    if control:
        out["control_gap"] = float(max(c.max() for _, c in res))
    return out


def checks(cell, reading: dict, control=False) -> dict:
    """The numbers compared, each with its limit: the program's widest
    gap, or with ``control`` the control's in its place.  No greedy
    answer to compare reads as None, which fails."""
    gap = reading["control_gap"] if control else reading["gap"]
    return {"missing_answers": {"value": reading["missing"],
                                "limit": cell.limits["missing_answers"]},
            "max_logit_gap": {"value": gap,
                              "limit": cell.limits["max_logit_gap"],
                              "tokens": reading["tokens"]}}


def passed(checks: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def host_rss_peak_bytes() -> int:
    """Peak resident memory of this process on the host."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def start(cell, seed: int, allow_cpu: bool = False):
    """Set-up up to the warm-up: the cell's chips, its weights from the
    seed, the serving loop.  Returns ``(loop, devices, key)``."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.models.common import ParallelContext

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if not allow_cpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found {devices[0].platform}, not a TPU")
    if len(devices) < cell.chips:
        raise NoChip(f"cell needs {cell.chips} chips, found {len(devices)}")
    devs = devices[:cell.chips]
    if not allow_cpu:
        arith.peaks(devs[0].device_kind)
    ctx = ParallelContext()
    if cell.chips > 1:
        ctx = ParallelContext(mesh=make_mesh((1, cell.chips),
                                             ("data", "model"), devs))
    key = weights.jax_key(seed)
    cfg = build.model_config(cell.conf)
    params = build.build_params(cfg, cell.conf, key, ctx)
    jax.block_until_ready(params)
    loop = build.make_loop(cell, params, cfg, ctx, seed=int(seed) & 0x7FFFFFFF)
    return loop, devs, key


def execute(cell, seed: int, seconds: float, trace_on: bool, *,
            t_start: float, allow_cpu: bool = False,
            control: bool = False) -> tuple:
    """One run; returns ``(result line, Run)``.  With ``control`` the
    float8 control's gap stands where the program's is compared, so a
    sound control run reads ``correct`` false."""
    loop, devs, key = start(cell, seed, allow_cpu)
    kind = devs[0].device_kind
    log(f"weights built at {time.monotonic() - t_start:.1f} s")
    try:
        _warm(loop, cell)
        mix = cell.traffic
        clients = cell.max_batch * mix["clients_per_slot"] \
            if mix["loop"] == "closed" else None
        reqs = traffic.generate(mix, seed, seconds, cell.conf["vocab_size"],
                                clients)
        _listen_for_compiles()
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") \
            if trace_on else None
        t0 = time.monotonic() + 0.02
        watch = _Watch(loop, t0, seconds, trace_dir)
        watch.start()
        if mix["loop"] == "closed":
            recs = drive.closed_loop(loop, reqs, clients, t0, seconds,
                                     mix["temperature"])
        else:
            recs = drive.open_loop(loop, reqs, t0, seconds,
                                   mix["temperature"])
        watch.join()
        t_end = t0 + seconds
        in_window = sum(1 for t in _COMPILES if t0 <= t < t_end)
        mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs]
        admissions = {rid: step for step, rid in loop.scheduler.admissions}
        engine_stats = loop.stats()
    finally:
        loop.shutdown(drain=False, timeout=10.0)
    _free(loop)
    del loop
    log(f"window closed, program freed at {time.monotonic() - t_start:.1f} s")

    summary = None
    if trace_on:
        summary = trace.summarize(trace.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(cell=cell, chips=cell.chips, device_kind=kind, seconds=seconds,
              setup_s=t0 - t_start, t0=t0, t_end=t_end, records=recs,
              steps=watch.steps, admissions=admissions,
              engine_stats=engine_stats, trace=summary,
              trace_steps=watch.trace_steps)

    run.reading = compare(cell, key, recs, seed, control)
    compared = checks(cell, run.reading, control)
    correct = passed(compared)

    entries = cell.per_layer if trace_on else cell.end_to_end
    metrics = {}
    for m in entries:
        value = spec.reader(m["name"])(run)
        if value is None and not trace_on:
            raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(max(mem))}
    result = {"correct": bool(correct),
              "attempted": sum(1 for r in recs if r.sent is not None),
              "failed": run.reading["missing"],
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["window_compiles"] = in_window
    result["reference_s"] = run.reading["reference_s"]
    result["host_rss_peak_bytes"] = host_rss_peak_bytes()
    result["checks"] = compared
    log(f"setup_s {run.setup_s:.3f}  steps {run.steps}  window compiles "
        f"{in_window}  memory_peak_bytes {device['memory_peak_bytes']}  "
        f"reference_s {result['reference_s']:.1f}  host_rss_peak_bytes "
        f"{result['host_rss_peak_bytes']}")
    for k, v in compared.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    return result, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load(args.workload)
    try:
        result, _ = execute(cell, args.seed, args.seconds, bool(args.trace),
                            t_start=T_START)
    except NoChip as e:
        log(f"no accelerator for this cell: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
