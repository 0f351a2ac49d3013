"""The four-chip cell ``mistral-large-123b.tp4.decode``: what the harness
loads for it, the readers of its collective metrics on hand-made traces
of several chips, and the decode program those readers compile, against
the program a smoke-size run of the cell serves on 4 virtual devices."""

import json
import os
import subprocess
import sys
import textwrap
import types

import pytest

from perfbench import arith, decode_program, spec, trace
from perfbench.trace import Event

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mistral-large-123b.tp4.decode"
NEW_METRICS = {"collective_ms_per_step", "collectives_per_step",
               "epilogue_ms_per_step"}
#: per-layer metrics the cell shares with the one-chip cell
SHARED_METRICS = {"decode_step_ms", "step_mfu", "dequant_gemm_roofline",
                  "idle_share", "host_idle_ms_per_step",
                  "sample_idle_ms_per_step", "attention_ms_per_step"}
MS = 1e6


def test_the_cell_loads_with_four_chips_the_decode_mix_and_its_metrics():
    cell = spec.load(CELL)
    assert cell.chips == 4
    with open(os.path.join(ROOT, "perfbench", "traffic", "decode.json")) as f:
        assert cell.traffic == json.load(f)
    assert cell.conf["name"] == "mistral-large-123b"
    assert cell.conf["num_hidden_layers"] == 22
    assert (cell.max_batch, cell.max_seq) == (8, 512)
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s",
                                                    "itl_p95_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == NEW_METRICS | SHARED_METRICS
    for m in cell.per_layer:
        assert m["moves"] == "itl_p95_ms" and CELL in m["workloads"]


def test_the_configuration_keeps_every_published_width():
    conf = spec.load(CELL).conf
    assert (conf["hidden_size"], conf["intermediate_size"],
            conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"], conf["vocab_size"]) == \
        (12288, 28672, 96, 8, 128, 32768)
    assert conf["rope_theta"] == 1e6 and conf["rms_norm_eps"] == 1e-5
    assert conf["tie_word_embeddings"] is False
    assert conf["sliding_window"] is None
    # the down GEMM's groups tile a 4-way shard of d_ff
    q = conf["quantization"]
    assert q["tp_groups"] % 4 == 0
    assert (conf["intermediate_size"] // 4) % q["group_size_down"] == 0


# ----------------------------------------------------------------------
# readers on hand-made traces of two chips
# ----------------------------------------------------------------------

#: a decode program on a mesh, as far as the readers look: a synchronous
#: all-reduce under the epilogue scope, one outside it, an async
#: all-gather pair, a fusion that calls an all-to-all, and plain ops
HLO = """HloModule jit_decode, is_scheduled=true
%fused_a2a (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %all-to-all.1 = (f32[2]{0}, f32[2]{0}) all-to-all(%p), channel_id=3
}
%body.4 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %psum.9 = f32[8,12288]{1,0:T(8,128)S(1)} all-reduce(%dequant_matmul.5), channel_id=1, to_apply=%add, metadata={op_name="jit(decode)/while/body/closed_call/shard_map/epilogue/psum" stack_frame_id=129}
  %all-reduce.3 = f32[8,1,12288]{2,0,1:T(8,128)S(1)} all-reduce(%fusion.99), channel_id=2, metadata={op_name="jit(decode)/while/body/closed_call/attention/dot_general"}
  %dequant_matmul.5 = f32[8,12288]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/while/body/closed_call/shard_map/dot_general"}
  %all-gather-start.2 = (f32[8]{0}, f32[32]{0}) all-gather-start(%x), channel_id=4
  %all-gather-done.2 = f32[32]{0} all-gather-done(%all-gather-start.2)
  %fusion.7 = f32[8]{0} fusion(%x), kind=kCustom, calls=%fused_a2a
}
ENTRY %main.2 () -> f32[8] {
  %while.1 = (s32[], f32[8]) while(%t), condition=%cond, body=%body.4
  %copy.3 = f32[8]{0} copy(%y)
}
"""


def _op(dev, name, start, dur):
    return Event(f"/device:TPU:{dev}", "XLA Ops", name, start * MS, dur * MS)


def _program(dev, start, dur):
    return Event(f"/device:TPU:{dev}", "XLA Modules", "jit_decode(7)",
                 start * MS, dur * MS)


def _hand_trace():
    """A 100 ms window with two decode programs wholly in it on each chip
    (10-40 and 50-80 on chip 0) and one cut by its end (90-110); chip 1
    runs the same ops at other times, which must not count."""
    events = [Event("/host:CPU", "python3", trace.WINDOW, 0.0, 100 * MS)]
    for dev, shift in ((0, 0), (1, 3)):
        for p0 in (10, 50, 90):
            t = p0 + shift
            events.append(_program(dev, t, 30 if p0 < 90 else 20))
            events += [
                _op(dev, "%while.1", t, 20),
                _op(dev, "%dequant_matmul.5", t, 6),
                _op(dev, "%psum.9", t + 6, 2),
                _op(dev, "%all-reduce.3", t + 8, 1),
                _op(dev, "%all-gather-start.2", t + 9, 0.5),
                _op(dev, "%all-gather-done.2", t + 9.5, 1.5),
                _op(dev, "%fusion.7", t + 11, 3),
                _op(dev, "%copy.3", t + 20, 2),
            ]
    events.append(_op(0, "%copy.3", 45, 1))   # between programs
    return events


def _run(events=None, hlo=HLO):
    return types.SimpleNamespace(
        trace=trace.summarize(_hand_trace() if events is None else events),
        cell=types.SimpleNamespace(name="hand", chips=2, max_batch=8,
                                   max_seq=512, conf={}, _hlo=hlo))


@pytest.fixture
def hand_hlo(monkeypatch):
    monkeypatch.setattr(decode_program, "decode_hlo",
                        lambda cell: cell._hlo)


def test_collectives_are_told_by_their_instruction():
    assert decode_program.collectives(HLO) == {
        "%psum.9": True, "%all-reduce.3": True, "%all-gather-start.2": True,
        "%all-gather-done.2": False, "%fusion.7": True, "%all-to-all.1": True}
    assert decode_program.scoped(HLO, "epilogue") == {"%psum.9"}


def test_collective_readers_count_chip_0s_whole_decode_programs(hand_hlo):
    run = _run()
    # per program on chip 0: psum 2 + all-reduce 1 + the async pair 0.5 +
    # 1.5 + the fusion 3 ms; four exchanges (the -done half ends one)
    assert spec.reader("collective_ms_per_step")(run) == pytest.approx(8.0)
    assert spec.reader("collectives_per_step")(run) == pytest.approx(4.0)
    assert spec.reader("epilogue_ms_per_step")(run) == pytest.approx(2.0)


def test_the_epilogue_reads_nothing_without_its_scope(hand_hlo):
    run = _run(hlo=HLO.replace("/epilogue/", "/"))
    assert spec.reader("epilogue_ms_per_step")(run) is None
    assert spec.reader("collectives_per_step")(run) == pytest.approx(4.0)


@pytest.mark.parametrize("case", ["no program", "other program", "no trace",
                                  "pages"])
def test_collective_readers_read_nothing_without_the_program(hand_hlo,
                                                            case):
    if case == "no program":
        run = _run(events=[e for e in _hand_trace()
                           if e.line != "XLA Modules"])
    elif case == "other program":
        run = _run(hlo=HLO.replace("%copy.3 = ", "%copy.8 = "))
    elif case == "no trace":
        run = types.SimpleNamespace(trace=None, cell=None)
    else:
        run = _run(hlo=None)
    for name in NEW_METRICS:
        assert spec.reader(name)(run) is None, name


def test_attention_reads_the_scope_of_the_mesh_program(hand_hlo):
    # the all-reduce under the attention scope, 1 ms per program on chip 0
    assert spec.reader("attention_ms_per_step")(_run()) == \
        pytest.approx(1.0)


# ----------------------------------------------------------------------
# the step, kernel, device and host readers on a hand-made trace of the
# cell's four chips
# ----------------------------------------------------------------------

def _host(name, start, dur):
    return Event("/host:CPU", "engine-loop", name, start * MS, dur * MS)


def _four_chip_trace():
    """A 100 ms window with two decode programs on every chip, at 10 and
    50 ms, each 30 ms long.  In each, two layers of gate, up and down
    dequant-GEMMs back to back, 2 + 2 + 3 ms, but chip ``d``'s last down
    takes 3 + d: chip ``d`` is busy 14 + d ms a program.  The host's
    spans leave chip 0 idle in working phases 0-10 (admit 5-9, decode
    9-10), 40-50 (sample 40-45, update 45-49, decode 49-50) and 80-85
    (prepare); not in readback 24-40 and 64-80 nor in wait 85-100."""
    events = [Event("/host:CPU", "python3", trace.WINDOW, 0.0, 100 * MS),
              _host("serve.admit", 5, 4), _host("serve.decode", 9, 1),
              _host("serve.readback", 24, 16), _host("serve.sample", 40, 5),
              _host("serve.update", 45, 4), _host("serve.decode", 49, 1),
              _host("serve.readback", 64, 16), _host("serve.prepare", 80, 5),
              _host("serve.wait", 85, 15)]
    for dev in range(4):
        for p0 in (10, 50):
            events.append(_program(dev, p0, 30))
            t = p0
            for layer in range(2):
                for name, dur in (("%dequant_matmul.3", 2),
                                  ("%dequant_matmul.4", 2),
                                  ("%dequant_matmul.5",
                                   3 + dev * layer)):
                    events.append(_op(dev, name, t, dur))
                    t += dur
    return events


def _four_chip_run():
    cell = spec.load(CELL)
    return types.SimpleNamespace(
        trace=trace.summarize(_four_chip_trace()), cell=cell, chips=4,
        device_kind="TPU v5 lite", trace_steps=(10, 14),
        lanes=lambda lo, hi: [(None, 100, 4), (None, 7, 4)])


def test_step_and_device_readers_on_four_chips():
    run = _four_chip_run()
    assert spec.reader("decode_step_ms")(run) == pytest.approx(30.0)
    # busy 2 * (14 + d) ms on chip d: 31 ms of 100 on average
    assert spec.reader("idle_share")(run) == pytest.approx(69.0)


def test_the_gemm_roofline_sums_every_chips_calls_at_per_chip_shapes():
    run = _four_chip_run()
    peak = arith.peaks("TPU v5 lite")
    # one chip's share of a layer at tp=4: gate and up 12288 x 7168, down
    # 7168 x 12288, groups of 128, 8 rows
    per_layer = sum(arith.roofline_seconds(
        arith.dequant_gemm_flops(8, k, n),
        arith.dequant_gemm_bytes(8, k, n, 128), peak)
        for k, n in ((12288, 7168), (12288, 7168), (7168, 12288)))
    # 48 calls (16 layers' worth) over all four chips, 2 * (14 + d) ms of
    # kernel time on chip d
    device_s = sum(2 * (14 + d) for d in range(4)) / 1e3
    assert spec.reader("dequant_gemm_roofline")(run) == \
        pytest.approx(100 * 16 * per_layer / device_s)


def test_step_mfu_counts_the_stage_over_four_chips_peak():
    run = _four_chip_run()
    flops = arith.span_flops(run.cell.conf, 100, 4) + \
        arith.span_flops(run.cell.conf, 7, 4)
    assert spec.reader("step_mfu")(run) == \
        pytest.approx(100 * flops / 0.1 / (4 * 197e12))


def test_host_readers_read_chip_0s_idle_in_working_phases():
    run = _four_chip_run()
    # 4 + 1 + 5 + 4 + 1 + 5 ms over the two decode spans
    assert spec.reader("host_idle_ms_per_step")(run) == pytest.approx(10.0)
    assert spec.reader("sample_idle_ms_per_step")(run) == pytest.approx(2.5)


# ----------------------------------------------------------------------
# the program the readers compile, at smoke size on 4 virtual devices
# ----------------------------------------------------------------------

SCRIPT = r"""
import dataclasses, re, time
import jax, jax.numpy as jnp
from perfbench import decode_program, spec
from perfbench import run as bench_run

cell = spec.load("mistral-large-123b.tp4.decode")
conf = dict(cell.conf, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=4, head_dim=32, vocab_size=512)
conf["quantization"] = dict(conf["quantization"], tp_groups=4,
                            group_size_up=128, group_size_down=128)
cell = dataclasses.replace(
    cell, conf=conf, max_batch=4, max_seq=64,
    traffic=dict(cell.traffic, pool=8,
                 prompt_len={"dist": "uniform", "min": 4, "max": 24},
                 output_len={"dist": "uniform", "min": 8, "max": 24}))

# a whole traced run: correct, and the readers of device metrics find no
# chip in a CPU trace and read nothing, without failing the run
result, run = bench_run.execute(cell, 2**35 + 1, 4.0, True,
                                t_start=time.monotonic(), allow_cpu=True)
assert result["correct"], result["checks"]
assert result["window_compiles"] == 0, result
assert not set(result["metrics"]) & {m["name"] for m in cell.per_layer}

loop, _, _ = bench_run.start(cell, 2**35 + 2, allow_cpu=True)
try:
    bench_run._warm(loop, cell)
    sched = loop.scheduler
    lanes = jnp.zeros((cell.max_batch,), jnp.int32)
    ran = sched.engine._decode.lower(sched.engine.params, sched._cache,
                                     lanes, lanes).compile().as_text()
finally:
    loop.shutdown(drain=False, timeout=10.0)
hlo = decode_program.decode_hlo(cell)


def instructions(text):
    return [re.sub(r", metadata=\{.*\}$", "", m[0])
            for m in decode_program.INSTRUCTION.finditer(text)]


assert instructions(hlo) == instructions(ran)
names = decode_program.collectives(hlo)
assert sorted(names) == sorted(decode_program.collectives(ran))
scoped = decode_program.scoped(hlo, "epilogue")
# per layer the epilogue's all-reduce and wo's, and the embedding's one
assert len(names) == 3 and all(names.values()), names
assert len(scoped & set(names)) == 1, scoped
print("OK", sorted(names))
"""


def test_the_readers_compile_the_program_a_tp4_run_serves(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(SCRIPT)],
                         env=env, cwd=ROOT, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.splitlines()[-1].startswith("OK")
