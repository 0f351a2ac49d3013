"""Serving loop: 95th percentile of the time requests due inside the
window waited in the admission queue, from the program's own stamps
(``Stream.submitted`` to ``Stream.started``)."""

from perfbench import arith


def read(run):
    waits = [rec.stream.started - rec.stream.submitted
             for rec in run.records
             if rec.stream is not None and rec.stream.started is not None
             and rec.due is not None and run.t0 <= rec.due < run.t_end]
    return 1e3 * arith.percentile(waits, 95) if waits else None
