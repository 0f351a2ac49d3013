"""Reduction of a profiler trace to device time, idle time and named
events.

A traced window is bracketed by the benchmark's own ``TraceAnnotation``
(``WINDOW``) on the host.  Device planes are ``/device:TPU:<n>``; on each,
the ``XLA Ops`` line holds one event per executed operation, named by its
whole HLO line (``%dequant_matmul.13 = f32[8,12800] custom-call(...)``
for a Pallas kernel; a ``%while`` loop's event spans its body's) and the
``XLA Modules`` line one per executed program, named after the jitted
function (``jit_<name>(<id>)``).  Busy time is the union of the op
intervals inside the window; idle gaps are the holes between them, each
named by the host event that overlaps it most.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

WINDOW = "perfbench.window"
DEVICE = re.compile(r"/device:TPU:(\d+)")
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: control-flow ops whose events span the ops they run
CONTAINER = re.compile(r"%?(while|conditional|call)\b")
#: entries kept in each list of the breakdown
TOP = 10
#: host spans longer than this (thread-long spans) name no idle gap
HOST_SPAN_NS = 1e9


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(log_dir: str) -> list:
    """Every event of the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    events = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                events.append(Event(plane.name, line.name, ev.name,
                                    float(ev.start_ns), float(ev.duration_ns)))
    return events


def op_name(text: str) -> str:
    """``%name`` of an op event, whose name is its whole HLO line."""
    return text.split(" = ", 1)[0].strip()


def merge(intervals) -> list:
    """Sorted, non-overlapping union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(ev: Event, lo: float, hi: float):
    s, e = max(ev.start_ns, lo), min(ev.end_ns, hi)
    return (s, e) if e > s else None


@dataclasses.dataclass
class Summary:
    """One traced window: device op and program events per chip, host
    events, and the window's bounds on the trace's clock."""

    lo: float
    hi: float
    ops: dict            # device plane -> [Event] overlapping the window
    modules: dict        # device plane -> [Event]
    host: list           # host events overlapping the window

    @property
    def devices(self) -> list:
        return sorted(self.ops, key=lambda p: int(DEVICE.fullmatch(p)[1]))

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_intervals(self, device: str) -> list:
        return merge(c for c in (_clip(e, self.lo, self.hi)
                                 for e in self.ops[device]) if c)

    def busy_s(self) -> float:
        """Busy seconds averaged over the chips that ran anything."""
        used = [d for d in self.devices if self.ops[d]]
        if not used:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(d))
                   for d in used) / len(used) / 1e9

    def op_events(self, pattern: str, device: str | None = None) -> list:
        """Op events inside the window whose name matches ``pattern``."""
        rx = re.compile(pattern)
        devs = [device] if device else self.devices
        return [e for d in devs for e in self.ops[d]
                if rx.search(e.name) and self.lo <= e.start_ns < self.hi]

    def module_events(self, pattern: str, device: str | None = None) -> list:
        rx = re.compile(pattern)
        devs = [device] if device else self.devices
        return [e for d in devs for e in self.modules[d]
                if rx.search(e.name) and self.lo <= e.start_ns < self.hi]

    def breakdown(self) -> dict:
        """The device ops that took the most time on the first chip, and
        idle time by what the host was doing, each as ``[name, s]``."""
        if not self.devices:
            return {"device_ops": [], "idle_gaps": []}
        dev = self.devices[0]
        per_op = collections.Counter()
        for e in self.ops[dev]:
            name = op_name(e.name)
            c = _clip(e, self.lo, self.hi)
            if c and not CONTAINER.match(name):
                per_op[name] += (c[1] - c[0]) / 1e9
        busy = self.busy_intervals(dev)
        edges = [self.lo] + [x for iv in busy for x in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        # host spans a gap can be named by: shorter than HOST_SPAN_NS, so
        # a gap's candidates start at most that long before it
        host = sorted((e for e in self.host
                       if e.name != WINDOW and e.dur_ns < HOST_SPAN_NS),
                      key=lambda e: e.start_ns)
        starts = [h.start_ns for h in host]
        idle = collections.Counter()
        for s, e in gaps:
            best, best_ov = "no host event", 0.0
            i = bisect.bisect_left(starts, s - HOST_SPAN_NS)
            for h in host[i:bisect.bisect_left(starts, e)]:
                ov = min(h.end_ns, e) - max(h.start_ns, s)
                if ov > best_ov:
                    best, best_ov = h.name, ov
            idle[best] += (e - s) / 1e9
        return {"device_ops": [[n, t] for n, t in per_op.most_common(TOP)],
                "idle_gaps": [[n, t] for n, t in idle.most_common(TOP)]}


def summarize(events: list, window: str = WINDOW) -> Summary:
    marks = [e for e in events if e.name == window
             and e.plane.startswith(HOST_PREFIX)]
    if not marks:
        raise RuntimeError(f"no {window!r} span in the trace")
    lo, hi = marks[0].start_ns, marks[0].end_ns
    ops, modules, host = {}, {}, []
    for e in events:
        device = bool(DEVICE.fullmatch(e.plane))
        if device:
            ops.setdefault(e.plane, [])
            modules.setdefault(e.plane, [])
        if e.end_ns <= lo or e.start_ns >= hi:
            continue
        if device:
            if e.line == OPS_LINE:
                ops[e.plane].append(e)
            elif e.line == MODULES_LINE:
                modules[e.plane].append(e)
        elif e.plane.startswith(HOST_PREFIX):
            host.append(e)
    return Summary(lo, hi, ops, modules, host)
