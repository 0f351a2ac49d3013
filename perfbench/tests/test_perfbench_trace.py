"""Trace reduction: busy time as the union of device intervals, program
and kernel events by name, idle gaps named by the host, on a hand-made
trace and on a few steps recorded from a v5e chip."""

import json
import os
import types

import pytest

from perfbench import trace
from perfbench.trace import Event

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "granite_decode_trace.jsonl")
DEV = "/device:TPU:0"


def _hand_trace():
    ms = 1e6
    host = "/host:CPU"
    return [
        Event(host, "python", trace.WINDOW, 0.0, 100 * ms),
        # program 1: ops overlap (10-30, 20-40) and abut (40-50)
        Event(DEV, "XLA Modules", "jit_decode(7)", 10 * ms, 40 * ms),
        Event(DEV, "XLA Ops", "dequant_matmul.3", 10 * ms, 20 * ms),
        Event(DEV, "XLA Ops", "fusion.1", 20 * ms, 20 * ms),
        Event(DEV, "XLA Ops", "dequant_matmul.4", 40 * ms, 10 * ms),
        # gap 50-70: the host was sampling
        Event(host, "engine", "PjitFunction(sample)", 52 * ms, 15 * ms),
        Event(host, "engine", "np.asarray", 66 * ms, 2 * ms),
        Event(DEV, "XLA Modules", "jit_decode(7)", 70 * ms, 20 * ms),
        Event(DEV, "XLA Ops", "dequant_matmul.3", 70 * ms, 20 * ms),
        # outside the window: ignored
        Event(DEV, "XLA Ops", "dequant_matmul.3", 120 * ms, 5 * ms),
        Event("/device:TPU:0 SparseCore", "XLA Ops", "x", 0.0, 100 * ms),
    ]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    s = trace.summarize(_hand_trace())
    assert s.devices == [DEV]
    assert s.window_s == pytest.approx(0.1)
    # 10-50 and 70-90
    assert s.busy_s() == pytest.approx(0.060)


def test_programs_and_kernels_are_found_by_name():
    s = trace.summarize(_hand_trace())
    mods = s.module_events(r"^jit_decode\b")
    assert [m.dur_ns for m in mods] == [40e6, 20e6]
    kern = s.op_events(r"dequant_matmul")
    assert len(kern) == 3 and sum(e.dur_ns for e in kern) == 50e6


def test_breakdown_names_top_ops_and_idle_gaps_by_host_activity():
    b = trace.summarize(_hand_trace()).breakdown()
    ops = dict(b["device_ops"])
    assert ops == pytest.approx({"dequant_matmul.3": 0.040,
                                 "fusion.1": 0.020,
                                 "dequant_matmul.4": 0.010})
    idle = dict(b["idle_gaps"])
    # 0-10 and 90-100 had no host event; 50-70 mostly sampling
    assert idle == pytest.approx({"no host event": 0.020,
                                  "PjitFunction(sample)": 0.020})


def test_merge_unions_overlapping_and_touching_intervals():
    assert trace.merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 6]]


def test_a_trace_without_the_window_span_is_an_error():
    with pytest.raises(RuntimeError, match="perfbench.window"):
        trace.summarize([Event(DEV, "XLA Ops", "x", 0.0, 1.0)])


def _fixture():
    with open(FIXTURE) as f:
        return [Event(p, line, name, start, dur)
                for p, line, name, start, dur in map(json.loads, f)]


def test_recorded_chip_trace_reduces_to_decode_steps_and_kernels():
    s = trace.summarize(_fixture())
    assert s.devices == [DEV]
    steps = s.module_events(r"^jit_decode\b", DEV)
    assert len(steps) >= 2
    kern = s.op_events(r"dequant_matmul", DEV)
    # gate, up and down in each of the 32 layers of every decode step
    assert len(kern) == 3 * 32 * len(steps)
    assert sum(e.dur_ns for e in kern) < sum(e.dur_ns for e in steps)
    assert 0 < s.busy_s() < s.window_s
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= trace.TOP
    # the ten host activities named cover most of the idle time
    idle = s.window_s - s.busy_s()
    assert 0.5 * idle < sum(t for _, t in b["idle_gaps"]) <= idle + 1e-9
    assert not any(n.startswith("%while") for n, _ in b["device_ops"])


def _fixture_hlo(events):
    """An HLO text with a line for every op of the recorded trace; every
    third, by name, in the ``attention`` scope."""
    names = sorted({trace.op_name(e.name) for e in events
                    if e.line == trace.OPS_LINE})
    body = "\n".join(
        f'  {n} = f32[8]{{0}} fusion(%x), metadata={{op_name="jit(decode)/'
        f'while/body/closed_call/{"attention/" if i % 3 == 0 else ""}op"}}'
        for i, n in enumerate(names))
    return "HloModule jit_decode\nENTRY %main.1 () -> f32[8] {\n" + body \
        + "\n}\n"


def test_attention_reads_the_recorded_granite_trace_as_before(monkeypatch):
    from perfbench import decode_program
    from perfbench.metrics import attention_ms_per_step as attention

    events = _fixture()
    hlo = _fixture_hlo(events)
    monkeypatch.setattr(decode_program, "decode_hlo", lambda cell: hlo)
    run = types.SimpleNamespace(trace=trace.summarize(events), cell=None)
    # what the reader's own one-chip compile and op filter read here before
    # it went through decode_program's
    assert attention.read(run) == 39.3498715
