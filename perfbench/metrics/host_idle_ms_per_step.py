"""Host step: device idle time on the first chip, inside the traced
window, that falls within the serving loop's own phase spans
(``serve.*``, written by ``EngineLoop`` and ``Scheduler.step``) other
than ``serve.readback`` (the host waiting for the device) and
``serve.wait`` (no request to serve), per decode step: the idle that the
host's own work between programs causes.  Steps are the ``serve.decode``
spans that start in the window."""

from perfbench import trace

#: phases in which the host waits rather than works
WAITING = ("serve.readback", "serve.wait")


def _overlap(a, b) -> float:
    """Total length of the intersection of two sorted, disjoint interval
    lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_ms_per_step(run, counts):
    """Milliseconds per decode step of the first chip's idle time inside
    the window that falls within host spans whose name ``counts``
    accepts; None without a trace, a chip or ``serve.decode`` spans."""
    s = run.trace
    if s is None or not s.devices:
        return None
    steps = sum(1 for e in s.host
                if e.name == "serve.decode" and s.lo <= e.start_ns < s.hi)
    if not steps:
        return None
    busy = s.busy_intervals(s.devices[0])
    edges = [s.lo] + [x for iv in busy for x in iv] + [s.hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = trace.merge((max(e.start_ns, s.lo), min(e.end_ns, s.hi))
                        for e in s.host if counts(e.name))
    return _overlap(idle, spans) / 1e6 / steps


def read(run):
    return idle_ms_per_step(
        run, lambda n: n.startswith("serve.") and n not in WAITING)
