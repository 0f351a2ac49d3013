"""95th percentile of every gap between two consecutive tokens of one
request, as the client received them, over the gaps that end inside the
window."""

from perfbench import arith


def read(run):
    gaps = [b - a for rec in run.records
            for a, b in zip(rec.times, rec.times[1:])
            if run.t0 <= b < run.t_end]
    return 1e3 * arith.percentile(gaps, 95) if gaps else None
