#!/usr/bin/env python3
"""Smoke run of the served path on TPU: qwen3-4b at full published width.

    python chip_smoke.py [--seed N]             # one chip
    python chip_smoke.py --chips 4 [--layers N] # tp=4 on four chips vs tp=1

One chip: prepare the tp-aware int4 artifact from ``--seed`` with the
``serve prepare`` CLI in a CPU-only child process, load it into the
serving engine, serve a few requests over HTTP with the Pallas
dequant-GEMM backend, and check the first-step logits against the ``ref``
backend on the same params.

The child runs before this process touches the chip.  The raw f32 init
(17.6 GB) does not fit a v5e's 16 GB of HBM, so it is made in host RAM,
and the TPU runtime itself holds about 13 GB of host RSS once started: on
a 40 GiB host the two do not fit in one process at once.

Four chips: the tp=4 artifact served over a (1, 4) mesh with the psum
epilogue, against the tp=1 artifact from the same seed on one chip of the
host.  Greedy token ids must be identical and first-step logits within
the same bound; the MLP weights must be spread a quarter per chip.
``--layers N`` keeps the first N of the 36 layers, at full width.

Earlier lines say what each phase did; the last line is the JSON result.
A failed phase exits non-zero before it.  Times printed here are smoke
timings of one run, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import shutil
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
sys.path.insert(0, SRC)

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen3-4b"
#: the ``serve prepare`` plan: int4 MLPs, tp-aware layout, psum epilogue
PLAN_ARGS = ("--arch", ARCH, "--scheme", "tp-aware", "--backend", "auto",
             "--collective", "psum")
#: relative L2 distance allowed between two engines' first-step logits.
#: The residual stream is bf16, so a last-bit difference inside a GEMM
#: can flip one bf16 rounding and grow over 36 layers; a wrong kernel
#: (a misplaced group, a bad tile) is off by O(1).
LOGIT_REL_BOUND = 2e-2
PROMPT_LEN = 24
MAX_NEW = 16
N_REQUESTS = 4
MAX_BATCH = 8        # fits one chip at MAX_SEQ (tests/test_tpu_compile.py)
MAX_SEQ = 1024
CHECK_SEQ = 64       # cache length of the logits-check engines
#: where the prepare children write their artifacts (gitignored; removed
#: once loaded)
ARTIFACT_DIR = os.path.join(ROOT, ".smoke_artifacts")


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def prompts_from_seed(cfg, seed: int) -> list:
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size,
                        size=(N_REQUESTS, PROMPT_LEN)).tolist()


def prepare_artifacts(tps, seed: int, layers) -> dict:
    """``serve prepare`` of the tp-aware int4 plan for each TP degree in
    ``tps``, one CPU-only child process each, run side by side; returns
    ``{tp: artifact directory}``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    paths = {tp: os.path.join(ARTIFACT_DIR, f"{ARCH}-tp{tp}-seed{seed}")
             for tp in tps}
    t0 = time.perf_counter()
    # minutes of CPU-bound work on every core: at a lower priority, so the
    # host's other processes stay responsive meanwhile
    procs = {tp: subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "prepare", *PLAN_ARGS,
         "--tp", str(tp), "--seed", str(seed), "--out", path,
         *(("--num-layers", str(layers)) if layers else ())], env=env,
        preexec_fn=lambda: os.nice(10))
        for tp, path in paths.items()}
    try:
        failed = [tp for tp, p in procs.items() if p.wait() != 0]
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
                p.wait()
    if failed:
        fail(f"prepare failed for tp={failed}")
    rss = resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss * 1024
    log(f"prepare tp={list(tps)}: {time.perf_counter() - t0:.1f} s wall, "
        f"largest child's peak RSS {rss} B (host CPU)")
    return paths


def load_engine(path: str, max_seq: int, **kw):
    """The serving engine over a prepared artifact directory, its config
    rebuilt from the manifest as ``serve --artifact`` does."""
    from repro.launch.serve import config_from_manifest
    from repro.plan import DeploymentArtifact
    from repro.runtime.serve import make_engine

    cfg = config_from_manifest(DeploymentArtifact.load_manifest(path))
    t0 = time.perf_counter()
    engine = make_engine(cfg, max_seq=max_seq, artifact=path, **kw)
    jax.block_until_ready(engine.params)
    log(f"loaded {os.path.basename(path)} in "
        f"{time.perf_counter() - t0:.1f} s")
    return cfg, engine


def check_placed(params) -> None:
    leaves = jax.tree.leaves(params)
    stray = [leaf for leaf in leaves
             if any(d.platform != "tpu" for d in leaf.devices())]
    if stray:
        fail(f"{len(stray)} of {len(leaves)} param leaves are not on the "
             f"TPU: {sorted({str(d) for s in stray for d in s.devices()})}")
    nbytes = sum(leaf.nbytes for leaf in leaves)
    log(f"params: {len(leaves)} leaves, {nbytes} B, all on the TPU")


def compile_decode(engine, batch: int):
    """Compile the scheduler's step program ahead of its first call;
    report compile seconds, Pallas custom calls and memory."""
    cache = jax.eval_shape(lambda: engine.init_cache(batch))
    vec = jax.ShapeDtypeStruct((batch,), jnp.int32)
    t0 = time.perf_counter()
    compiled = engine._decode.lower(engine.params, cache, vec, vec).compile()
    dt = time.perf_counter() - t0
    n_kernels = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()
    log(f"decode step batch={batch} max_seq={engine.max_seq}: compiled in "
        f"{dt:.1f} s, {n_kernels} tpu_custom_call, argument "
        f"{mem.argument_size_in_bytes} B, temp {mem.temp_size_in_bytes} B "
        f"per device")
    return n_kernels, mem


def serve_over_http(engine, prompts) -> list:
    """Answer ``prompts`` through the HTTP/SSE front end (greedy), one
    client thread per request; returns each request's token ids."""
    from repro.runtime.sampling import SamplingConfig
    from repro.serving import ServingServer

    srv = ServingServer(engine, port=0, max_batch=MAX_BATCH,
                        prompt_budget=PROMPT_LEN,
                        scfg=SamplingConfig(temperature=0.0)).start()
    url = f"http://127.0.0.1:{srv.port}/v1/generate"
    out = [None] * len(prompts)

    def client(i):
        body = json.dumps({"prompt": prompts[i],
                           "max_new_tokens": MAX_NEW}).encode()
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        toks, event, done = [], None, False
        with urllib.request.urlopen(req, timeout=900) as resp:
            for raw in resp:
                line = raw.decode().strip()
                if line.startswith("event: "):
                    event = line[len("event: "):]
                elif line.startswith("data: "):
                    data = json.loads(line[len("data: "):])
                    if event == "token":
                        toks.append(data["token"])
                    elif event == "done":
                        done = True
        out[i] = toks if done else None

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    srv.shutdown()
    missing = [i for i, toks in enumerate(out)
               if toks is None or len(toks) != MAX_NEW]
    if missing:
        fail(f"HTTP requests {missing} did not complete with {MAX_NEW} "
             f"tokens")
    for i, toks in enumerate(out):
        log(f"http req {i}: prompt {len(prompts[i])} -> {toks}")
    log(f"served {len(prompts)} requests x {MAX_NEW} tokens over HTTP in "
        f"{dt:.2f} s (smoke timing, not a benchmark)")
    return out


def first_step_logits(engine, prompts) -> np.ndarray:
    """Logits after the prompt, the ones the first token is drawn from,
    via the scheduler's own step program: per-slot positions, the batch
    padded to ``MAX_BATCH`` rows with repeats of the prompts."""
    rows = [prompts[i % len(prompts)] for i in range(MAX_BATCH)]
    toks = jnp.asarray(np.asarray(rows, np.int32))
    b, p = toks.shape
    cache = engine.init_cache(b)
    for t in range(p):
        logits, cache = engine._decode(engine.params, cache, toks[:, t],
                                       jnp.full((b,), t, jnp.int32))
    return np.asarray(logits, np.float32)[:len(prompts)]


def rel_l2(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def peak_hbm(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def run_one_chip(path: str, seed: int) -> None:
    from repro.runtime.serve import Engine

    cfg, engine = load_engine(path, MAX_SEQ)
    prompts = prompts_from_seed(cfg, seed)
    log(f"engine: {cfg.arch_id} L={cfg.num_layers} d_model={cfg.d_model} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} scheme="
        f"{engine.policy.scheme} backend={engine.policy.backend} "
        f"collective={engine.policy.collective.shorthand()}")
    if engine.policy.backend != "pallas":
        fail(f"backend auto resolved to {engine.policy.backend!r}, "
             f"not 'pallas'")
    check_placed(engine.params)
    n_kernels, _ = compile_decode(engine, MAX_BATCH)
    if n_kernels < 3:
        fail(f"{n_kernels} tpu_custom_call in the decode program; the "
             f"layer body's gate, up and down GEMMs need 3")
    serve_over_http(engine, prompts)

    # pallas vs ref on the same params, f32 matmuls everywhere else so
    # the dequant-GEMM kernel is the only difference
    with jax.default_matmul_precision("highest"):
        logits = {b: first_step_logits(
            Engine(model=engine.model, params=engine.params,
                   max_seq=CHECK_SEQ,
                   policy=engine.policy.with_(backend=b)), prompts)
            for b in ("pallas", "ref")}
    err = rel_l2(logits["pallas"], logits["ref"])
    if not np.isfinite(logits["pallas"]).all():
        fail("non-finite pallas logits")
    log(f"first-step logits pallas vs ref: shape {logits['ref'].shape}, "
        f"rel L2 {err:.3e} (bound {LOGIT_REL_BOUND:g})")
    if not err < LOGIT_REL_BOUND:
        fail(f"pallas logits off the ref backend by {err:.3e}")
    log(f"peak HBM in use: {peak_hbm(jax.devices()[0])} B")


def run_four_chips(paths: dict, seed: int) -> None:
    from repro.launch.mesh import make_mesh
    from repro.models.common import ParallelContext
    from repro.train import checkpoint

    devices = jax.devices()
    if len(devices) != 4:
        fail(f"--chips 4 needs 4 devices, found {len(devices)}")
    runs = {}
    for tp in (1, 4):
        kw = {}
        if tp == 4:
            # the per-rank loader: each chip's shard goes straight to it
            mesh = make_mesh((1, 4), ("data", "model"), devices)
            kw = {"ctx": ParallelContext(mesh=mesh, batch_axes=("data",)),
                  "per_rank": True}
        cfg, engine = load_engine(paths[tp], CHECK_SEQ, **kw)
        prompts = prompts_from_seed(cfg, seed)
        check_placed(engine.params)
        log(f"tp={tp}: {cfg.arch_id} L={cfg.num_layers} d_model="
            f"{cfg.d_model} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
            f"backend={engine.policy.backend} collective="
            f"{engine.policy.collective.shorthand()} devices="
            f"{sorted({str(d) for x in jax.tree.leaves(engine.params) for d in x.devices()})}")
        mlp = [leaf for key, leaf in
               checkpoint.flatten_keys(engine.params).items()
               if "||mlp||" in key]
        on_first = sum(s.data.nbytes for leaf in mlp
                       for s in leaf.addressable_shards
                       if s.device == devices[0])
        share = on_first / sum(leaf.nbytes for leaf in mlp)
        _, mem = compile_decode(engine, MAX_BATCH)
        log(f"tp={tp}: MLP bytes on {devices[0]}: {on_first} "
            f"({share:.4f} of the model's MLP)")
        if tp == 4 and not 0.2 < share < 0.3:
            fail(f"tp=4 puts {share:.4f} of the MLP bytes on one chip, "
                 f"not about a quarter")
        runs[tp] = {"logits": first_step_logits(engine, prompts),
                    "tokens": serve_over_http(engine, prompts),
                    "args": mem.argument_size_in_bytes}
        del engine
    if runs[1]["tokens"] != runs[4]["tokens"]:
        fail("tp=4 greedy token ids differ from tp=1")
    err = rel_l2(runs[4]["logits"], runs[1]["logits"])
    log(f"tp=4 vs tp=1: token ids identical; first-step logits rel L2 "
        f"{err:.3e} (bound {LOGIT_REL_BOUND:g}); decode argument bytes "
        f"per device {runs[4]['args']} vs {runs[1]['args']}")
    if not err < LOGIT_REL_BOUND:
        fail(f"tp=4 logits off tp=1 by {err:.3e}")
    for d in devices:
        log(f"peak HBM in use on {d}: {peak_hbm(d)} B")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve on one chip; 4: tp=4 over four chips "
                         "against tp=1 on one")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the prompts")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep only the first N layers (default: all), "
                         "at full width")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "tpu" not in platforms.split(","):
        fail(f"no TPU: JAX_PLATFORMS={platforms!r} leaves it out")
    log(f"compile cache: {enable_compile_cache()}")
    try:
        paths = prepare_artifacts((1, 4) if args.chips == 4 else (1,),
                                  args.seed, args.layers)
        # only now does this process start the TPU runtime
        devices = jax.devices()
        dev = devices[0]
        log(f"jax {jax.__version__}; devices {devices}; "
            f"kind {dev.device_kind!r}")
        if dev.platform != "tpu":
            fail(f"no TPU: JAX's default backend is {dev.platform!r}")
        if args.chips == 4:
            run_four_chips(paths, args.seed)
        else:
            run_one_chip(paths[1], args.seed)
    finally:
        shutil.rmtree(ARTIFACT_DIR, ignore_errors=True)
    log(f"total wall {time.perf_counter() - t0:.1f} s "
        f"(smoke timing, not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
