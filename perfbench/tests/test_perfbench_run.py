"""A whole run at smoke size on the CPU, past the harness's look for a
chip: the counts of requests, tokens and steps (no times), and the
correctness comparison failing for the control and for each fault a
served cell can have."""

import dataclasses
import time

import numpy as np
import pytest

from perfbench import run as bench_run
from smoke_cells import smoke_cell

SEED = 2**33 + 5


def _execute(name, seconds=3.0, control=False, layers=None, **kw):
    cell = smoke_cell(name, **kw)
    if layers:
        cell = dataclasses.replace(
            cell, conf=dict(cell.conf, num_hidden_layers=layers))
    return bench_run.execute(cell, SEED, seconds, False,
                             t_start=time.monotonic(), allow_cpu=True,
                             control=control)


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch, tmp_path):
    # the persistent cache stays off: JAX is imported already, so setting
    # the variable keeps enable_compile_cache from naming a directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_closed_loop_counts_requests_tokens_and_steps():
    result, run = _execute("granite-3-8b.decode")
    recs = run.records
    sent = [r for r in recs if r.sent is not None]
    assert result["attempted"] == len(sent) > run.cell.max_batch
    assert result["failed"] == 0 and not any(r.error for r in recs)
    done = [r for r in recs if r.end == "done"]
    assert done and all(len(r.tokens) == r.req.max_new for r in done)
    # every token the engine emitted reached a client (warm-up: 2 x 3)
    assert run.engine_stats["tokens"]["generated"] == \
        sum(len(r.tokens) for r in recs) + 6
    # a request sent just before the close may be cancelled in the queue
    assert all(r.stream.rid in run.admissions for r in sent if r.tokens)
    assert all(r.end == "cancelled" for r in sent
               if r.stream.rid not in run.admissions)
    lo, hi = run.steps
    assert 0 < lo < hi <= run.engine_stats["engine"]["steps"]
    lanes = run.lanes(lo, hi)
    fed = sum(n for _, _, n in lanes)
    assert 0 < fed <= (hi - lo) * run.cell.max_batch
    # a finished request occupied prompt + output - 1 steps of one lane
    for r in done:
        a = run.admissions[r.stream.rid]
        full = run.lanes(a, a + 10_000)
        mine = [n for rec, first, n in full if rec is r]
        assert mine == [r.req.prompt.size + r.req.max_new - 1]
    assert set(result["metrics"]) == {"tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    assert result["correct"], result["checks"]


def test_open_loop_answers_every_request_due_in_the_window():
    result, run = _execute("granite-3-8b.decode", open_loop=True,
                           qk_norm=True)
    due = [r for r in run.records if run.t0 <= r.due < run.t_end]
    assert len(due) == len(run.records) == result["attempted"]
    assert all(r.complete for r in due) and result["failed"] == 0
    assert all(r.stream.started >= r.stream.submitted for r in due)
    assert all(r.times[0] >= r.due for r in due)
    assert set(result["metrics"]) == {"itl_p95_ms", "ttft_p95_ms",
                                      "setup_s"}
    assert result["correct"], result["checks"]


def test_the_control_fails_the_limit_the_program_passes():
    # twelve layers and answers of 48-96 tokens: the control's float8
    # error, like the program's bfloat16 error, grows with depth, and its
    # widest gap with the positions compared
    cell = smoke_cell("granite-3-8b.decode")
    cell = dataclasses.replace(
        cell, conf=dict(cell.conf, num_hidden_layers=12), max_seq=160,
        traffic=dict(cell.traffic, output_len={"dist": "uniform",
                                               "min": 48, "max": 96}))
    result, run = bench_run.execute(cell, SEED, 4.0, False,
                                    t_start=time.monotonic(), allow_cpu=True,
                                    control=True)
    assert not result["correct"]
    gap = result["checks"]["max_logit_gap"]
    assert gap["tokens"] > 0 and gap["value"] > gap["limit"]
    assert gap["value"] == run.reading["control_gap"]
    program = bench_run.checks(run.cell, run.reading)
    assert bench_run.passed(program), program


def test_an_altered_token_fails(monkeypatch):
    from repro.runtime import sampling

    real = sampling.sample_slots

    def altered(*args):
        toks = real(*args)
        return (toks + 1) % 512

    monkeypatch.setattr(sampling, "sample_slots", altered)
    result, _ = _execute("granite-3-8b.decode")
    assert not result["correct"]
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_a_step_that_returns_its_cache_unchanged_fails(monkeypatch):
    from repro.models import transformer

    real = transformer.decode_step

    def stale(cfg, params, cache, *args, **kw):
        logits, _ = real(cfg, params, cache, *args, **kw)
        return logits, cache

    monkeypatch.setattr(transformer, "decode_step", stale)
    result, _ = _execute("granite-3-8b.decode")
    assert not result["correct"]
    gap = result["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_no_greedy_answer_to_compare_fails():
    checks = {"max_logit_gap": {"value": None, "limit": 1.0},
              "missing_answers": {"value": 0, "limit": 0}}
    assert not bench_run.passed(checks)
    checks["max_logit_gap"]["value"] = np.float32(0.5)
    assert bench_run.passed(checks)
