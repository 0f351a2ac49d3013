"""Dequant-GEMM kernel: the least time the traced Pallas dequant-GEMM
calls could take on the chip (``arith.roofline_seconds`` of each call's
shapes: packed int4 weights, fp16 group scales and zeros, bf16 input and
output) over the device time they took.  Each layer makes three calls
per step (gate, up, down), so the calls are counted in threes."""

from perfbench import arith

KERNEL = r"dequant_matmul"


def read(run):
    if run.trace is None:
        return None
    evs = run.trace.op_events(KERNEL)
    if not evs:
        return None
    peak = arith.peaks(run.device_kind)
    conf = run.cell.conf
    per_layer = sum(
        arith.roofline_seconds(arith.dequant_gemm_flops(run.cell.max_batch,
                                                        k, n),
                               arith.dequant_gemm_bytes(run.cell.max_batch,
                                                        k, n, g), peak)
        for k, n, g in arith.mlp_gemm_shapes(conf, run.chips))
    device_s = sum(e.dur_ns for e in evs) / 1e9
    return 100.0 * (len(evs) / 3) * per_layer / device_s
