"""Sampling: device idle time on the first chip, inside the traced
window, that falls within ``serve.sample`` spans (the dispatch of
``sampling.sample_slots`` by ``Scheduler.step``), per decode step: the
part of ``host_idle_ms_per_step`` that sampling on the host causes."""

from perfbench.metrics.host_idle_ms_per_step import idle_ms_per_step


def read(run):
    return idle_ms_per_step(run, lambda n: n == "serve.sample")
