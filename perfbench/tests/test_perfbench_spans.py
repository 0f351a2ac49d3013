"""The serving loop's own spans and counters, and the readers of the
metrics built on them: a few steps of the smoke cell's engine loop under
the profiler on the CPU, a whole smoke run, and hand-made traces."""

import glob
import os
import re
import time
import types

import jax
import numpy as np
import pytest

from perfbench import decode_program
from perfbench import run as bench_run
from perfbench import spec, trace
from perfbench.metrics import attention_ms_per_step as attention
from perfbench.metrics import replay_share
from perfbench.trace import Event
from smoke_cells import smoke_cell

SEED = 2**33 + 7
#: the phases of one decode step, in order (EngineLoop and Scheduler.step)
PHASES = ["serve.admit", "serve.admit", "serve.prepare", "serve.decode",
          "serve.sample", "serve.readback", "serve.update", "serve.emit"]
DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def _steps_of_spans(log_dir) -> dict:
    """``step`` stat of every ``serve.*`` event of the trace under
    ``log_dir``, by ``(name, start_ns)``."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return {(ev.name, float(ev.start_ns)): dict(ev.stats).get("step")
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith("serve.")}


@pytest.fixture(scope="module")
def traced_loop(tmp_path_factory):
    """The smoke cell's loop, warmed, serving six requests on four slots
    under the profiler; yields the loop, the trace's events and the step
    number each ``serve.*`` span carries."""
    cell = smoke_cell("granite-3-8b.decode")
    loop, _, _ = bench_run.start(cell, SEED, allow_cpu=True)
    try:
        bench_run._warm(loop, cell)
        rng = np.random.default_rng(0)
        log_dir = str(tmp_path_factory.mktemp("trace"))
        jax.profiler.start_trace(log_dir)
        time.sleep(0.1)                       # the loop idles meanwhile
        streams = [loop.submit(rng.integers(0, 512, n).astype(np.int32),
                               max_new_tokens=m, temperature=t, seed=i)
                   for i, (n, m, t) in enumerate(
                       [(5, 4, 0.0), (3, 6, 0.8), (7, 3, 0.8), (2, 5, 0.0),
                        (4, 4, 0.8), (6, 2, 0.0)])]
        for s in streams:
            assert s.finished.wait(120)
        time.sleep(0.1)
        jax.profiler.stop_trace()
        yield loop, trace.load(log_dir), _steps_of_spans(log_dir)
    finally:
        loop.shutdown(drain=False, timeout=10.0)


def _serve_spans(events):
    spans = [e for e in events if e.name.startswith("serve.")]
    assert spans and len({(e.plane, e.line) for e in spans}) == 1
    return sorted(spans, key=lambda e: e.start_ns)


def _step(steps, e):
    return steps[e.name, e.start_ns]


def test_each_step_has_its_phase_spans_in_order(traced_loop):
    _, events, steps = traced_loop
    spans = _serve_spans(events)
    assert all(isinstance(_step(steps, e), int) for e in spans)
    by_step = {}
    for e in spans:
        by_step.setdefault(_step(steps, e), []).append(e.name)
    decoded = [n for n, names in by_step.items() if "serve.decode" in names]
    assert len(decoded) >= 8
    assert decoded == list(range(decoded[0], decoded[0] + len(decoded)))
    for n in decoded:
        names = by_step[n]
        assert names[-len(PHASES):] == PHASES, (n, names)
        # before its decode a step may only have idled, admitting nothing
        idle = names[:-len(PHASES)]
        assert idle == ["serve.admit", "serve.wait"] * (len(idle) // 2)


def test_no_serve_span_encloses_another(traced_loop):
    _, events, _ = traced_loop
    spans = _serve_spans(events)
    for a, b in zip(spans, spans[1:]):
        assert b.start_ns >= a.end_ns, (a, b)


def test_spans_cover_the_loop_from_the_first_to_the_last_step(traced_loop):
    _, events, steps = traced_loop
    spans = _serve_spans(events)
    decoded = [_step(steps, e) for e in spans if e.name == "serve.decode"]
    first = [e for e in spans if _step(steps, e) == min(decoded)]
    last = [e for e in spans if _step(steps, e) == max(decoded)]
    # from the admission that opens the first step's decode iteration to
    # the fan-out that closes the last one's
    lo, hi = first[-len(PHASES)].start_ns, last[-1].end_ns
    assert hi - lo > 0
    covered = sum(e - s for s, e in trace.merge(
        (max(e.start_ns, lo), min(e.end_ns, hi)) for e in spans
        if e.end_ns > lo and e.start_ns < hi))
    assert covered >= 0.95 * (hi - lo)


def test_lane_counters_count_replay_and_emission(traced_loop):
    loop, _, _ = traced_loop
    stats = loop.stats()
    engine = stats["engine"]
    finished = loop.scheduler.finished.values()
    assert all(r.done and not r.cancelled for r in finished)
    assert engine["lanes_replay"] == sum(r.prompt.size - 1 for r in finished)
    assert engine["lanes_emit"] == sum(len(r.output) for r in finished) \
        == stats["tokens"]["generated"]
    assert "per_s" not in stats["tokens"]


def test_the_attention_reader_compiles_the_program_that_ran(traced_loop):
    loop, _, _ = traced_loop
    sched = loop.scheduler
    lanes = jax.numpy.zeros((sched.max_batch,), jax.numpy.int32)
    ran = sched.engine._decode.lower(sched.engine.params, sched._cache,
                                     lanes, lanes).compile().as_text()
    hlo = decode_program.compile_hlo(smoke_cell("granite-3-8b.decode"))

    def instructions(text):
        # source locations differ with the caller; the program may not
        return [re.sub(r", metadata=\{.*\}$", "", m[0])
                for m in decode_program.INSTRUCTION.finditer(text)]

    assert instructions(hlo) == instructions(ran)
    assert decode_program.scoped(hlo, attention.SCOPE) == \
        decode_program.scoped(ran, attention.SCOPE)
    assert decode_program.scoped(hlo, attention.SCOPE)


def test_a_traced_run_keeps_counters_and_the_scoped_decode_program(
        monkeypatch):
    cell = smoke_cell("granite-3-8b.decode")
    # the engine's counters at each instant the harness reads its steps,
    # the window's opening and closing among them
    reads = []
    steps_of = bench_run._steps

    def counted(loop):
        reads.append(loop.stats()["engine"])
        return steps_of(loop)

    monkeypatch.setattr(bench_run, "_steps", counted)
    _, run = bench_run.execute(cell, SEED, 4.0, True,
                               t_start=time.monotonic(), allow_cpu=True)
    # the attention ops of the compiled decode program carry the scope
    assert decode_program.scoped(decode_program.decode_hlo(cell),
                                 attention.SCOPE)
    first, last = reads[0], reads[-1]
    assert (first["steps"], last["steps"]) == run.steps
    fed = (last["lanes_replay"] - first["lanes_replay"]
           + last["lanes_emit"] - first["lanes_emit"])
    steps = last["steps"] - first["steps"]
    assert fed > 0 and steps > 0
    occupancy = spec.reader("batch_occupancy")(run)
    assert fed / (steps * cell.max_batch) == occupancy
    engine = run.engine_stats["engine"]
    assert engine["lanes_replay"] >= last["lanes_replay"]
    share = spec.reader("replay_share")(run)
    assert share == engine["lanes_replay"] / (engine["lanes_replay"]
                                              + engine["lanes_emit"])
    assert 0 < share < 1


# ----------------------------------------------------------------------
# readers on hand-made traces
# ----------------------------------------------------------------------

def _span(name, start, dur):
    return Event(HOST, "engine-loop", name, start * MS, dur * MS)


def _op(name, start, dur):
    return Event(DEV, "XLA Ops", f"{name} = f32[8] fusion()", start * MS,
                 dur * MS)


#: the compiled decode program's HLO text, as far as the readers look
HLO = """HloModule jit_decode
%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %dot.7 = f32[8]{0} dot(%p, %p), metadata={op_name="jit(decode)/while/body/closed_call/attention/dot_general"}
}
ENTRY %main.2 () -> f32[8] {
  %while.1 = f32[8]{0} while(%x), metadata={op_name="jit(decode)/while"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode)/while/body/closed_call/attention/dot_general" stack_frame_id=3}
  %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(decode)/while/body/closed_call/attention/exp"}
  %dequant_matmul.1 = f32[8]{0} custom-call(%x), metadata={op_name="jit(decode)/while/body/closed_call/dot_general"}
  %convert.2 = bf16[8]{0} convert(%x)
}
"""


def _hand_trace():
    """A 100 ms window: device busy 0-10, 20-40, 50-60, 70-80, 90-95.
    Idle 10-20 falls in serve.prepare, 40-50 in serve.sample (and 45-50
    in serve.readback), 60-70 in serve.readback, 80-90 in serve.wait and
    95-100 in serve.update."""
    return [
        Event(HOST, "python3", trace.WINDOW, 0.0, 100 * MS),
        _span("serve.decode", -10, 12),       # starts before
        _span("serve.prepare", 8, 14),
        _span("serve.decode", 22, 3),
        _span("serve.sample", 25, 20),
        _span("serve.readback", 45, 10),
        _span("serve.update", 55, 2),
        _span("serve.decode", 57, 2),
        _span("serve.readback", 59, 12),
        _span("serve.emit", 71, 1),
        _span("serve.wait", 79, 12),
        _span("serve.update", 95, 5),
        Event(DEV, "XLA Modules", "jit_decode(1)", 20 * MS, 20 * MS),
        Event(DEV, "XLA Modules", "jit_decode(1)", 50 * MS, 10 * MS),
        Event(DEV, "XLA Modules", "jit_sample(2)", 70 * MS, 10 * MS),
        Event(DEV, "XLA Modules", "jit_decode(1)", 90 * MS, 15 * MS),
        _op("%while.1", 20, 20),
        _op("%fusion.1", 20, 6),
        _op("%dequant_matmul.1", 26, 14),
        _op("%fusion.1", 50, 4),
        _op("%convert.2", 54, 6),       # compiler-inserted: no metadata
        _op("%fusion.3", 70, 10),       # scoped name, but not jit_decode's
        _op("%fusion.1", 90, 5),        # its program outlasts the window
        _op("%x", 0, 10),
    ]


def _hand_run(events=None):
    return types.SimpleNamespace(
        trace=trace.summarize(events or _hand_trace()), cell=None)


def test_host_idle_counts_gaps_in_working_phases_only():
    run = _hand_run()
    # idle 10-20 (prepare), 40-45 (sample), 95-100 (update); not 45-50
    # and 60-70 (readback) nor 80-90 (wait); decode spans in the window: 2
    read = spec.reader("host_idle_ms_per_step")
    assert read(run) == pytest.approx((10 + 5 + 5) / 2)


def test_sample_idle_counts_gaps_in_the_sample_phase():
    read = spec.reader("sample_idle_ms_per_step")
    assert read(_hand_run()) == pytest.approx(5 / 2)


def test_attention_counts_scoped_ops_inside_whole_decode_programs(
        monkeypatch):
    monkeypatch.setattr(decode_program, "decode_hlo", lambda cell: HLO)
    # 6 ms and 4 ms of attention in the two decode programs wholly in the
    # window; the while loop, the unscoped ops, the scoped op of another
    # program and the program cut by the window's end count nothing
    assert attention.read(_hand_run()) == pytest.approx((6 + 4) / 2)
    assert decode_program.scoped(HLO, attention.SCOPE) == \
        {"%fusion.1", "%fusion.3", "%dot.7"}


@pytest.mark.parametrize("hlo", [
    HLO.replace("attention/", ""),                   # no scope
    HLO.replace("%convert.2 = ", "%convert.9 = "),   # not the program run
    None,                                            # a mesh or pages
])
def test_attention_reads_nothing_without_the_scoped_program(monkeypatch,
                                                           hlo):
    monkeypatch.setattr(decode_program, "decode_hlo", lambda cell: hlo)
    assert attention.read(_hand_run()) is None


def test_readers_of_spans_find_nothing_in_a_trace_without_them():
    run = _hand_run(events=[e for e in _hand_trace()
                            if not e.name.startswith("serve.")])
    for name in ("host_idle_ms_per_step", "sample_idle_ms_per_step"):
        assert spec.reader(name)(run) is None, name
        assert spec.reader(name)(types.SimpleNamespace(trace=None)) is None


def test_replay_share_reads_the_counters_at_the_window_close():
    def run(**engine):
        return types.SimpleNamespace(engine_stats={"engine": engine})

    assert replay_share.read(run(steps=20, lanes_replay=38,
                                 lanes_emit=110)) == pytest.approx(38 / 148)
    # a program without the counters, or an engine that fed nothing
    assert replay_share.read(run(steps=20)) is None
    assert replay_share.read(run(steps=0, lanes_replay=0,
                                 lanes_emit=0)) is None
    assert replay_share.read(types.SimpleNamespace(engine_stats={})) is None
