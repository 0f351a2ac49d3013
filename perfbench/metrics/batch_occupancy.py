"""Scheduler: tokens fed (prompt replay and generated) per slot and
step, over the window's engine steps.  A slot's lane runs from the step
that admitted its request (the scheduler's own log) for prompt length
plus tokens emitted, less one, steps."""


def read(run):
    lo, hi = run.steps
    if hi <= lo:
        return None
    fed = sum(n for _, _, n in run.lanes(lo, hi))
    return fed / ((hi - lo) * run.cell.max_batch)
