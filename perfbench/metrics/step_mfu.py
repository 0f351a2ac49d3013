"""Engine step: model FLOPs of the tokens fed in the traced window, per
second of it, over the chips' bf16 peak.  FLOPs per token come from the
configuration's shapes (``arith.span_flops``): every weight once and
attention over the keys before the token; idle slots count nothing."""

from perfbench import arith


def read(run):
    if run.trace is None or not run.trace.devices or not run.trace_steps:
        return None
    lo, hi = run.trace_steps
    flops = sum(arith.span_flops(run.cell.conf, first, n)
                for _, first, n in run.lanes(lo, hi))
    if not flops:
        return None
    peak = arith.peaks(run.device_kind)["flops_per_s"]
    return 100.0 * flops / run.trace.window_s / (run.chips * peak)
