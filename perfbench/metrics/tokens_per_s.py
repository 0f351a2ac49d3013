"""Output tokens that reached the clients inside the window, per second
of the window."""

from perfbench import arith


def read(run):
    n = sum(1 for rec in run.records for t in rec.times
            if run.t0 <= t < run.t_end)
    return arith.rate(n, run.seconds)
