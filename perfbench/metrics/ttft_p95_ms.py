"""95th percentile, over every request due inside the window, of the
time from when it was due to its first token at the client.  A request
that never answered counts with the time until the run gave up on it."""

from perfbench import arith


def read(run):
    ttft = []
    for rec in run.records:
        if rec.due is None or not run.t0 <= rec.due < run.t_end:
            continue
        end = rec.times[0] if rec.times else rec.closed_at
        ttft.append(end - rec.due)
    return 1e3 * arith.percentile(ttft, 95) if ttft else None
