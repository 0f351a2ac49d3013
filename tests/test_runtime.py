"""Serving runtime: engine, sampling, scheduler."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.runtime import sampling
from repro.runtime.scheduler import Request, Scheduler
from repro.runtime.serve import make_engine


def test_greedy_sampling_deterministic():
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 50))
    cfg = sampling.SamplingConfig(temperature=0.0)
    a = sampling.sample(jax.random.PRNGKey(1), logits, cfg)
    b = sampling.sample(jax.random.PRNGKey(2), logits, cfg)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a),
                                  np.asarray(jnp.argmax(logits, -1)))


def test_topk_sampling_stays_in_topk():
    logits = jnp.asarray([[0.0, 5.0, 4.0, -1.0, 3.0]] * 4)
    cfg = sampling.SamplingConfig(temperature=1.0, top_k=3)
    for seed in range(5):
        s = sampling.sample(jax.random.PRNGKey(seed), logits, cfg)
        assert set(np.asarray(s).tolist()) <= {1, 2, 4}


def test_engine_generate_shapes():
    cfg = get_smoke_config("qwen3-4b")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=32)
    inputs = {"tokens": jnp.zeros((2, 8), jnp.int32)}
    out = eng.generate(jax.random.PRNGKey(1), inputs,
                       jnp.asarray([8, 5]), max_new_tokens=4)
    assert out.shape == (2, 4)
    assert int(out.max()) < cfg.vocab_size


def test_engine_prefill_matches_forward():
    """Prefill-by-decode-replay last logits == full forward logits at the
    prompt's last position (KV-cache correctness through the engine)."""
    from repro.models.common import REPLICATED

    cfg = get_smoke_config("granite-3-8b")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=16)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0,
                              cfg.vocab_size)
    inputs = {"tokens": toks}
    fwd = eng.model.forward(eng.params, inputs, REPLICATED)
    cache = eng.init_cache(2)
    last, _ = eng.prefill(inputs, cache, jnp.asarray([6, 6]))
    err = float(jnp.abs(last - fwd[:, -1]).max())
    scale = float(jnp.abs(fwd[:, -1]).max())
    assert err < 2e-2 * scale, err / scale


def test_scheduler_drains_and_batches():
    cfg = get_smoke_config("qwen3-4b")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=40)
    sched = Scheduler(eng, max_batch=3, prompt_budget=8,
                      scfg=sampling.SamplingConfig(temperature=0.5,
                                                   top_k=10))
    rng = np.random.default_rng(0)
    for i in range(7):   # 7 requests, batch 3 -> 3 waves
        plen = int(rng.integers(2, 8))
        sched.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=plen).astype(np.int32),
            max_new_tokens=3))
    done = sched.run()
    assert sorted(done) == list(range(7))
    assert all(len(r.output) == 3 for r in done.values())
    assert all(r.done for r in done.values())


def test_scheduler_admits_between_decode_steps():
    """Continuous batching: a queued request is admitted into a retired
    slot while other slots are still decoding — and every request's
    greedy output is bit-identical to running it alone (per-slot position
    clocks + the causal mask isolate slots exactly)."""
    cfg = get_smoke_config("qwen3-4b")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=24)
    greedy = sampling.SamplingConfig(temperature=0.0)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 6, 4)]
    new = (2, 8, 3)   # req 0 retires early; req 2 takes its slot

    sched = Scheduler(eng, max_batch=2, prompt_budget=8, scfg=greedy)
    for i, (p, mn) in enumerate(zip(prompts, new)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=mn))
    done = sched.run()
    assert sorted(done) == [0, 1, 2]
    assert [len(done[i].output) for i in range(3)] == list(new)
    # the third request entered mid-stream, not after the first wave
    admitted = dict((rid, step) for step, rid in sched.admissions)
    assert admitted[2] > 0
    last_step = max(p.size for p in prompts[:2]) + max(new[:2])
    assert admitted[2] < last_step

    # alone, at the scheduler's batch width: CPU matmuls sum in an order
    # that depends on the batch size, so a batch of one differs in the
    # last bits and greedy near-ties may tip
    for i, (p, mn) in enumerate(zip(prompts, new)):
        inputs = {"tokens": jnp.asarray(np.stack([p, p]))}
        ref = np.asarray(eng.generate(
            jax.random.PRNGKey(9), inputs, jnp.asarray([p.size] * 2),
            max_new_tokens=mn, scfg=greedy))[0]
        np.testing.assert_array_equal(np.asarray(done[i].output), ref,
                                      err_msg=f"req {i}")


def test_scheduler_vector_pos_matches_scalar_decode():
    """The per-slot position decode program agrees bit-for-bit with the
    scalar-pos program when every slot shares the same clock."""
    cfg = get_smoke_config("granite-3-8b")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=16)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0,
                              cfg.vocab_size)
    cache_s = eng.init_cache(2)
    cache_v = eng.init_cache(2)
    for t in range(4):
        ls, cache_s = eng._decode(eng.params, cache_s, toks[:, t],
                                  jnp.int32(t))
        lv, cache_v = eng._decode(eng.params, cache_v, toks[:, t],
                                  jnp.full((2,), t, jnp.int32))
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lv))
    for a, b in zip(jax.tree_util.tree_leaves(cache_s),
                    jax.tree_util.tree_leaves(cache_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scheduler_recurrent_families_continuous_bit_identical():
    """ssm/hybrid are first-class continuous-batching citizens: each
    lane's recurrent state is independent at dim 1, and a re-admitted
    slot's lane is zeroed (``Engine.reset_slot``) — exactly the
    fresh-cache initial condition, so every request's greedy output is
    bit-identical to a solo run even through slot reuse."""
    greedy = sampling.SamplingConfig(temperature=0.0)
    for arch in ("rwkv6-3b", "recurrentgemma-2b"):
        cfg = get_smoke_config(arch)
        eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=24)
        assert eng.supports_continuous, arch
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
                   for n in (5, 6, 4)]
        new = (2, 8, 3)   # req 0 retires early; req 2 reuses its lane
        sched = Scheduler(eng, max_batch=2, prompt_budget=8, scfg=greedy)
        for i, (p, mn) in enumerate(zip(prompts, new)):
            sched.submit(Request(rid=i, prompt=p, max_new_tokens=mn))
        done = sched.run()
        admitted = dict((rid, step) for step, rid in sched.admissions)
        assert admitted[2] > 0, arch     # entered a previously-used lane
        for i, (p, mn) in enumerate(zip(prompts, new)):
            ref = np.asarray(eng.generate(
                jax.random.PRNGKey(9), {"tokens": jnp.asarray(p)[None]},
                jnp.asarray([p.size]), max_new_tokens=mn, scfg=greedy))[0]
            np.testing.assert_array_equal(
                np.asarray(done[i].output), ref,
                err_msg=f"{arch} req {i}")


def test_scheduler_batch_drain_fallback_families():
    """audio/vlm (batch-global cross prefill) still fall back to
    batch-drain and drain the queue."""
    cfg = get_smoke_config("whisper-large-v3")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=24)
    assert not eng.supports_continuous
    sched = Scheduler(eng, max_batch=2, prompt_budget=6,
                      scfg=sampling.SamplingConfig(temperature=0.0))
    rng = np.random.default_rng(0)
    for i in range(3):
        sched.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=4).astype(np.int32),
            max_new_tokens=2))
    done = sched.run()
    assert sorted(done) == [0, 1, 2]
    assert all(len(r.output) == 2 for r in done.values())


def test_scheduler_rejects_oversized_prompt():
    cfg = get_smoke_config("qwen3-4b")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=16)
    sched = Scheduler(eng, prompt_budget=4)
    with pytest.raises(ValueError, match="budget"):
        sched.submit(Request(rid=0, prompt=np.zeros(10, np.int32)))


def test_sample_slots_matches_scalar_sample():
    """One row of the per-slot vectorized sampler is bit-identical to
    the scalar ``sample`` path with the same key and params (this is
    what makes HTTP per-request sampling reproduce solo runs)."""
    logits = jax.random.normal(jax.random.PRNGKey(0), (1, 64))
    for t, p, k in ((0.7, 0.9, 0), (1.2, 0.5, 0), (0.9, 1.0, 10),
                    (0.0, 1.0, 0)):
        cfg = sampling.SamplingConfig(temperature=t,
                                      top_k=k or None,
                                      top_p=None if p == 1.0 else p)
        for seed in range(4):
            key = jax.random.PRNGKey(seed)
            a = sampling.sample(key, logits, cfg)
            b = sampling.sample_slots(
                key[None], logits,
                jnp.asarray([t], jnp.float32), jnp.asarray([p],
                                                           jnp.float32),
                jnp.asarray([k], jnp.int32))
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{t},{p},{k},{seed}")


#: per-row (temperature, top_p, top_k) of the compiled sampler's cases
SAMPLER_ROWS = {
    "greedy": [(0.0, 1.0, 0)] * 4,
    "top_k": [(0.8, 1.0, 40), (1.3, 1.0, 5), (0.5, 1.0, 1), (1.0, 1.0, 64)],
    "top_p": [(0.9, 0.9, 0), (1.2, 0.5, 0), (0.7, 0.99, 0), (1.0, 0.1, 0)],
    "mixed": [(0.0, 1.0, 0), (0.8, 1.0, 40), (0.9, 0.9, 0), (1.1, 0.8, 10)],
}


def _eager_step(keys, advance, logits, temperature, top_p, top_k):
    """The chain the scheduler used to run on the host, one eager
    ``jax.random.split`` per advancing slot, then ``sample_slots``."""
    draw, nxt = [], []
    for key, adv in zip(keys, np.asarray(advance)):
        if adv:
            key, sub = jax.random.split(key)
            draw.append(sub)
        else:
            draw.append(key)
        nxt.append(key)
    toks = sampling.sample_slots(jnp.stack(draw), logits, temperature,
                                 top_p, top_k)
    return np.asarray(toks), np.asarray(jnp.stack(nxt))


@pytest.mark.parametrize("mix", sorted(SAMPLER_ROWS))
def test_compiled_sample_step_matches_eager_chain(mix):
    """``compile_sample_step`` draws the eager path's tokens and keys bit
    for bit, on advancing and non-advancing rows, over several keys."""
    rows = SAMPLER_ROWS[mix]
    temperature, top_p, top_k = (jnp.asarray(c, d) for c, d in zip(
        zip(*rows), (jnp.float32, jnp.float32, jnp.int32)))
    step = sampling.compile_sample_step()
    for seed in range(3):
        lk, kk, ak = jax.random.split(jax.random.PRNGKey(100 + seed), 3)
        logits = 3 * jax.random.normal(lk, (len(rows), 97))
        keys = jax.random.split(kk, len(rows))
        for advance in (np.zeros(len(rows), bool), np.ones(len(rows), bool),
                        np.asarray(jax.random.bernoulli(ak, 0.5,
                                                        (len(rows),)))):
            want = _eager_step(keys, advance, logits, temperature, top_p,
                               top_k)
            got = step(jnp.array(keys), jnp.asarray(advance), logits,
                       temperature, top_p, top_k)
            np.testing.assert_array_equal(np.asarray(got[0]), want[0],
                                          err_msg=f"{mix} {seed} {advance}")
            np.testing.assert_array_equal(np.asarray(got[1]), want[1])
    assert step._cache_size() == 1


def test_compiled_sample_step_key_chain_over_steps():
    """After k steps the device-side chain holds each slot's key split
    once per advancing step and untouched on the others, and one program
    served every step, whatever its masks and parameters."""
    b, steps = 4, 12
    rng = np.random.default_rng(7)
    step = sampling.compile_sample_step()
    keys = jax.random.split(jax.random.PRNGKey(3), b)
    want = list(keys)
    for k in range(steps):
        advance = rng.random(b) < 0.6
        temperature = np.where(rng.random(b) < 0.25, 0.0,
                               rng.uniform(0.5, 1.5, b)).astype(np.float32)
        top_p = rng.choice([1.0, 0.9, 0.5], b).astype(np.float32)
        top_k = rng.choice([0, 5, 40], b).astype(np.int32)
        logits = jnp.asarray(rng.normal(size=(b, 61)), jnp.float32)
        before = np.asarray(keys)
        _, keys = step(keys, jnp.asarray(advance), logits,
                       jnp.asarray(temperature), jnp.asarray(top_p),
                       jnp.asarray(top_k))
        want = [jax.random.split(w)[0] if a else w
                for w, a in zip(want, advance)]
        np.testing.assert_array_equal(np.asarray(keys),
                                      np.asarray(jnp.stack(want)),
                                      err_msg=f"step {k}")
        moved = (np.asarray(keys) != before).any(axis=1)
        np.testing.assert_array_equal(moved, advance)
    assert step._cache_size() == 1


def _forbid_eager_keys(monkeypatch):
    def refuse(*args, **kw):
        raise AssertionError("per-slot key work on the host")

    for name in ("split", "PRNGKey", "fold_in", "key"):
        monkeypatch.setattr(jax.random, name, refuse)


def test_scheduler_prepare_fills_host_arrays_only(monkeypatch):
    """``Scheduler._prepare`` derives no key: it returns the uploaded
    (B,) decode and sampler inputs, and the keys stay one (B, 2) device
    array held by the scheduler."""
    cfg = get_smoke_config("qwen3-4b")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=24)
    sched = Scheduler(eng, max_batch=3, prompt_budget=8,
                      scfg=sampling.SamplingConfig(temperature=0.8,
                                                   top_k=20))
    rng = np.random.default_rng(1)
    for i, (t, n) in enumerate(((0.0, 3), (None, 6))):
        sched.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=n).astype(np.int32), max_new_tokens=6,
            temperature=t))
    for _ in range(4):     # slot 0 has emitted twice, slot 1 replays
        sched.step()
    _forbid_eager_keys(monkeypatch)
    args, advance, params = sched._prepare()
    arrays = [*args, advance, *params]
    assert all(isinstance(a, jax.Array) and a.shape == (3,) for a in arrays)
    np.testing.assert_array_equal(np.asarray(advance), [True, False, False])
    assert sched._keys.shape == (3, 2)
    assert not hasattr(sched._slots[0], "key")


@pytest.mark.parametrize("kv", ["dense", "paged:16"])
def test_scheduler_mixed_requests_match_solo_through_reuse_and_cancel(kv):
    """Greedy, sampled and seeded requests through slot reuse after
    retirement and a cancellation mid-stream: every request's tokens are
    its solo ``Engine.generate`` tokens (a prefix of them for the
    cancelled one), and ``lanes_drawn`` counts the sampled ones'."""
    from repro.cache import PageSpec
    from repro.core.policy import ExecutionPolicy

    cfg = get_smoke_config("qwen3-4b")
    policy = ExecutionPolicy.from_config(cfg)
    if kv != "dense":
        policy = policy.with_(kv=PageSpec.parse(kv))
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=24, policy=policy)
    assert eng.uses_page_table == (kv != "dense")
    scfg = sampling.SamplingConfig(temperature=0.7, top_k=30)
    rng = np.random.default_rng(11)
    # (temperature, top_p, seed, max_new): None takes the scheduler's
    reqs = [(0.0, None, None, 3), (None, None, 5, 7), (1.1, 0.8, None, 9),
            (None, 0.9, 21, 4), (0.0, None, 8, 5)]
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (4, 6, 3, 5, 2)]
    sched = Scheduler(eng, max_batch=2, prompt_budget=8, scfg=scfg, seed=4)
    for i, (p, (t, tp, sd, mn)) in enumerate(zip(prompts, reqs)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=mn,
                             temperature=t, top_p=tp, seed=sd))
    for _ in range(9):
        sched.step()
    assert sched.cancel(1)             # live, mid-stream
    done = sched.run()
    assert done[1].cancelled and 0 < len(done[1].output) < reqs[1][3]
    admitted = dict((rid, step) for step, rid in sched.admissions)
    assert admitted[2] > 0 and admitted[4] > admitted[3]   # reused slots
    drawn = 0
    for i, (p, (t, tp, sd, mn)) in enumerate(zip(prompts, reqs)):
        temp = scfg.temperature if t is None else t
        key = (jax.random.PRNGKey(sd) if sd is not None else
               jax.random.fold_in(jax.random.PRNGKey(4), i))
        ref = np.asarray(eng.generate(
            key, {"tokens": jnp.asarray(p)[None]}, jnp.asarray([p.size]),
            max_new_tokens=mn, scfg=sampling.SamplingConfig(
                temperature=temp, top_k=scfg.top_k, top_p=tp)))[0]
        out = np.asarray(done[i].output)
        np.testing.assert_array_equal(out, ref[:out.size],
                                      err_msg=f"{kv} req {i}")
        assert done[i].cancelled or out.size == mn
        drawn += out.size if temp > 0 else 0
    assert sched.lanes_drawn == drawn
    assert sched._sample._cache_size() == 1


def test_scheduler_per_request_params_bit_identical():
    """Concurrent requests with different temperature/top_p/seed each
    reproduce a solo Engine.generate run with the same params."""
    cfg = get_smoke_config("qwen3-4b")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=24)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 6, 4)]
    params = [(0.9, 0.8, 7), (1.3, 0.5, 11), (0.0, None, 3)]
    sched = Scheduler(eng, max_batch=2, prompt_budget=8,
                      scfg=sampling.SamplingConfig(temperature=0.5))
    for i, (p, (t, tp, sd)) in enumerate(zip(prompts, params)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=6,
                             temperature=t, top_p=tp, seed=sd))
    done = sched.run()
    for i, (p, (t, tp, sd)) in enumerate(zip(prompts, params)):
        scfg = sampling.SamplingConfig(temperature=t, top_p=tp)
        ref = np.asarray(eng.generate(
            jax.random.PRNGKey(sd), {"tokens": jnp.asarray(p)[None]},
            jnp.asarray([p.size]), max_new_tokens=6, scfg=scfg))[0]
        np.testing.assert_array_equal(np.asarray(done[i].output), ref,
                                      err_msg=f"req {i}")


def test_scheduler_rejects_mixed_family():
    """One scheduler serves one family: a request declaring a different
    family fails loudly instead of silently serializing behind (or in
    front of) batch-drain waves."""
    cfg = get_smoke_config("qwen3-4b")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=16)
    sched = Scheduler(eng, prompt_budget=8)
    sched.submit(Request(rid=0, prompt=np.zeros(2, np.int32),
                         max_new_tokens=2, family="dense"))
    with pytest.raises(ValueError, match="one Scheduler per family"):
        sched.submit(Request(rid=1, prompt=np.zeros(2, np.int32),
                             max_new_tokens=2, family="audio"))


def test_scheduler_cancel_frees_slot():
    """A cancelled live request retires at the next step boundary and
    its slot admits the next queued request; a cancelled queued request
    never runs."""
    from repro.runtime.scheduler import StepEvent

    cfg = get_smoke_config("qwen3-4b")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=24)
    sched = Scheduler(eng, max_batch=1, prompt_budget=8,
                      scfg=sampling.SamplingConfig(temperature=0.0))
    rng = np.random.default_rng(0)
    for i in range(3):
        sched.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=3).astype(np.int32),
            max_new_tokens=10))
    for _ in range(4):          # request 0 holds the only slot
        sched.step()
    assert sched.live_slots == 1
    assert sched.cancel(0)      # live -> retires at next boundary
    assert sched.cancel(2)      # queued -> dropped, never admitted
    assert not sched.cancel(99)
    events = sched.step()
    assert StepEvent(0, None, True, cancelled=True) in events
    assert StepEvent(2, None, True, cancelled=True) in events
    done = sched.run()
    assert sorted(done) == [0, 1, 2]
    assert len(done[1].output) == 10 and done[1].done
    assert done[0].cancelled and len(done[0].output) < 10
    assert done[2].cancelled and done[2].output == []
    admitted = [rid for _, rid in sched.admissions]
    assert admitted == [0, 1]   # 2 was never admitted


@pytest.mark.parametrize("step", ["decode", "forward"])
def test_bf16_attention_projections_return_f32(step):
    """The plan compiler stores the attention projections in bf16: decode
    and full-sequence attention still return f32, and agree with the same
    computation on the same bf16 values held in f32 at HIGHEST
    precision."""
    from repro.models import common as cm
    from repro.plan.compiler import ATTN_PROJ

    cfg = get_smoke_config("granite-3-8b")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=16)
    p = jax.tree.map(lambda a: a[0], eng.params["layers"]["attn"])
    assert {p[k].dtype for k in ATTN_PROJ} == {jnp.dtype(jnp.bfloat16)}
    p32 = {k: v.astype(jnp.float32) for k, v in p.items()}
    kx, kc = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (2, 6, cfg.d_model)).astype(jnp.bfloat16)
    if step == "decode":
        kvh = cm.head_grid(cfg)[0]
        cache = {n: jax.random.normal(k, (2, 16, kvh, cfg.head_dim))
                 .astype(jnp.bfloat16)
                 for n, k in zip("kv", jax.random.split(kc))}

        def run(q):
            return cm.attention_decode(cfg, q, x[:, :1], cache,
                                       jnp.asarray([3, 9]), cm.REPLICATED)[0]
    else:
        def run(q):
            return cm.attention_forward(cfg, q, x, cm.REPLICATED)

    got = run(p)
    with jax.default_matmul_precision("highest"):
        want = run(p32)
    assert got.dtype == want.dtype == jnp.float32
    err = float(jnp.abs(got - want).max())
    scale = float(jnp.abs(want).max())
    assert err < 5e-3 * scale, err / scale


def test_engine_loop_reports_param_bytes_per_dtype():
    """``EngineLoop.stats()["engine"]["param_bytes"]``: the served tree's
    bytes per dtype, the attention projections under bfloat16."""
    from repro.plan.compiler import ATTN_PROJ
    from repro.serving import EngineLoop

    cfg = get_smoke_config("granite-3-8b")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=16)
    attn = eng.params["layers"]["attn"]
    want = sum(attn[k].nbytes for k in ATTN_PROJ)
    loop = EngineLoop(Scheduler(eng, max_batch=2, prompt_budget=8))
    stats = loop.stats()["engine"]
    assert stats["lanes_drawn"] == 0
    got = stats["param_bytes"]
    assert got["bfloat16"] == want
    assert sum(got.values()) == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(eng.params))
