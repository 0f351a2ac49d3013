"""Serving entrypoint: quantized deployment with the paper's schemes.

Three lifecycles:

* one-shot (compile in memory at startup):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --smoke \
        --scheme tp-aware --requests 8

* prepare-once / serve-many (the paper's a-priori plan, made literal):

    PYTHONPATH=src python -m repro.launch.serve prepare \
        --arch qwen3-4b --smoke --scheme tp-aware --tp 2 --out /tmp/plan
    PYTHONPATH=src python -m repro.launch.serve --artifact /tmp/plan \
        --tp 2 --requests 8

  ``prepare`` runs the offline plan compiler (quantize -> reorder/fold ->
  TP pre-shard) and writes a ``DeploymentArtifact``; serving from it
  never invokes GPTQ or the layout planner — the manifest is validated
  against the reconstructed config/policy/mesh so a stale or mismatched
  plan refuses to serve instead of silently computing the wrong thing.

* network front end (``repro.serving``, DESIGN.md §8) — instead of the
  built-in synthetic request batch, expose the engine over HTTP/SSE:

    PYTHONPATH=src python -m repro.launch.serve --artifact /tmp/plan \
        --tp 2 --http :8100
    curl -N localhost:8100/v1/generate -d '{"text": "hi", \
        "max_new_tokens": 8}'

  Ctrl-C drains: the admission queue closes (new requests get 503),
  in-flight requests finish, then the server exits.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.comm import (CollectivePlan, dispatch as comm_dispatch,
                        parse_collective)
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core.policy import ExecutionPolicy
from repro.dist import MeshPlan
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models.common import ParallelContext, REPLICATED
from repro.runtime.sampling import SamplingConfig
from repro.runtime.scheduler import Request, Scheduler
from repro.runtime.serve import make_engine


def _mesh_plan(value: str) -> MeshPlan:
    """argparse type: a ``dp2xtp4``-style device-grid shorthand."""
    try:
        return MeshPlan.parse(value)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e)) from None


def _dist_args(ap: argparse.ArgumentParser):
    """Multi-process launch flags (DESIGN.md §11): every process runs the
    same command with its own ``--process-id``."""
    ap.add_argument("--mesh", type=_mesh_plan, default=None,
                    help="device-grid plan, e.g. dp1xtp2 (axes data x "
                         "model over ALL processes' devices); implies "
                         "per-rank artifact loading — each process reads "
                         "only its own rank_NN.npz shards")
    ap.add_argument("--coordinator", default="127.0.0.1:9911",
                    help="host:port of process 0 (multi-process launch)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)


def _collective(value: str) -> str:
    """argparse type: validate against the comm registry, keep the string
    (the config stores the shorthand; the policy parses it once).
    Accepts bare specs and per-layer plans alike."""
    try:
        parse_collective(value)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e)) from None
    return value


def _plan_args(ap: argparse.ArgumentParser):
    ap.add_argument("--arch", default="qwen3-4b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--scheme", default="tp-aware",
                    choices=["naive-actorder", "exllama", "tp-aware"])
    ap.add_argument("--backend", default="auto",
                    help="dequant-GEMM kernel (auto | any backend "
                         "registered in kernels.dispatch)")
    ap.add_argument("--collective", default="psum", type=_collective,
                    help="row-TP epilogue collective spec; any strategy "
                         "registered in comm.dispatch: "
                         + ", ".join(comm_dispatch.strategies())
                         + " (parameterized shorthands like cast:float16, "
                           "quant-int8:64 or quant-int4:32 also accepted), "
                           "or a per-layer plan 'per-layer:<glob>=<spec>"
                           ",...,*=<default>' (e.g. per-layer:*.mlp="
                           "quant-int8:128,*=psum)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-page-size", type=int, default=None,
                    help="turn on the paged KV cache with this page size "
                         "in tokens (DESIGN.md §9); default: dense "
                         "per-slot rows")
    ap.add_argument("--kv-bits", type=int, default=None, choices=[8, 4],
                    help="quantize page payloads blockwise to int8/int4 "
                         "(requires --kv-page-size)")


def _build_cfg(args):
    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    # the whole deployment plan lives on the config; the policy below is
    # derived from it and flows unchanged to the kernels
    return cfg.with_quant(mode="mlp", scheme=args.scheme,
                          backend=args.backend, collective=args.collective,
                          kv_page_size=args.kv_page_size,
                          kv_bits=args.kv_bits)


def prepare(argv=None):
    """Offline compile: write a ``DeploymentArtifact`` directory."""
    from repro.plan import compiler

    ap = argparse.ArgumentParser(prog="repro.launch.serve prepare")
    _plan_args(ap)
    ap.add_argument("--tp", type=int, default=1,
                    help="target model-axis degree the shards are pre-"
                         "split for (serving must use the same)")
    ap.add_argument("--out", required=True, help="artifact directory")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="keep only the first N layers, at full width "
                         "(smoke runs of a large model); the manifest "
                         "records it and serving rebuilds the same config")
    ap.add_argument("--autotune-collectives", action="store_true",
                    help="score every full-output collective per pair "
                         "site (analytic wire bytes + calibration error "
                         "probe; plan/tuner.py) and compile the chosen "
                         "per-layer CollectivePlan into the artifact "
                         "(overrides --collective's epilogue choice)")
    ap.add_argument("--tune-budget", type=float, default=None,
                    help="max relative activation error a tuned "
                         "collective may introduce (default: the "
                         "tuner's DEFAULT_BUDGET, 0.05)")
    args = ap.parse_args(argv)

    cfg = _build_cfg(args)
    if args.num_layers is not None:
        cfg = cfg.with_(num_layers=args.num_layers)
    # record the intended grid in the manifest (provenance: validate pins
    # only the TP degree, so serving may widen dp without re-preparing)
    policy = ExecutionPolicy.from_config(cfg).with_(
        mesh=MeshPlan(dp=1, tp=args.tp))
    t0 = time.time()
    art = compiler.prepare(cfg, tp=args.tp, seed=args.seed, policy=policy,
                           extra_manifest={"smoke": bool(args.smoke),
                                           "num_layers": cfg.num_layers},
                           autotune=args.autotune_collectives,
                           tune_budget=args.tune_budget)
    path = art.save(args.out)
    dt = time.time() - t0
    n_pairs = len(art.manifest["pairs"])
    print(f"prepared {args.arch} (scheme={args.scheme} "
          f"collective={art.manifest['policy']['collective']} "
          f"mesh={policy.mesh.shorthand()} "
          f"tp={args.tp}) -> {path}: {n_pairs} planned pair(s), "
          f"{len(art.manifest['leaf_shards'])} leaves, {dt:.1f}s")
    for site in art.manifest.get("collective_tuner", ()):
        # attn_vo sites are the V->O fold epilogues
        print(f"  tuned {site['path']} [{site.get('kind', 'pair')}]: "
              f"{site['chosen']} ({site['status']})")
    return path


def config_from_manifest(man: dict):
    """The model config an artifact was prepared for, rebuilt from its
    manifest (smoke variant and ``--num-layers`` cut included)."""
    cfg = (get_smoke_config(man["arch_id"]) if man.get("smoke")
           else get_config(man["arch_id"]))
    cfg = cfg.with_(num_layers=man.get("num_layers", cfg.num_layers))
    return cfg.with_quant(**man["quant"])


def _load_artifact(args, *, manifest_only: bool = False):
    """Reconstruct (cfg, policy, artifact) from an artifact directory.

    The manifest is the single source of truth for the plan: the CLI's
    plan flags (--scheme/--backend/--collective/--arch) are ignored, and
    --tp defaults to the artifact's degree (an explicit --tp > 1 that
    disagrees fails ``validate``).  To serve a different plan, re-run
    ``prepare``.

    ``manifest_only`` (mesh mode): read just ``manifest.json`` and return
    a shell artifact with no rank pytrees — the engine loads this
    process's shards per-rank later, so the launcher never materializes
    ranks it doesn't own.
    """
    from repro.plan import DeploymentArtifact

    if manifest_only:
        art = DeploymentArtifact(
            manifest=DeploymentArtifact.load_manifest(args.artifact))
    else:
        art = DeploymentArtifact.load(args.artifact)
    man = art.manifest
    cfg = config_from_manifest(man)
    policy = art.policy()
    # cache layout is runtime-only (excluded from validate): CLI kv flags
    # override the manifest's recorded layout on the POLICY, never on cfg
    # (mutating cfg would break the config-hash check against a plan that
    # is identical either way)
    if args.kv_page_size is not None or args.kv_bits is not None:
        from repro.cache import PageSpec

        policy = policy.with_(kv=PageSpec(page_size=args.kv_page_size,
                                          bits=args.kv_bits))
    tp = args.tp if args.tp > 1 else art.tp
    art.validate(cfg=cfg, policy=policy, tp=tp)
    return cfg, policy, art, tp


def _run_multiprocess(args, cfg, engine, tp):
    """Synthetic-batch generation for multi-controller launches.

    The Scheduler/HTTP front ends are single-controller (host-side
    per-request admission and slot bookkeeping); under
    ``jax.distributed`` every process must instead step the same
    lockstep program — one padded batch through ``engine.generate``.
    Sampling happens host-side on replicated logits with identical rngs,
    so every process emits identical tokens (the printed ``first=``
    prefix can be diffed across processes as a cheap coherence check).
    The batch must be divisible by the mesh's dp degree.
    """
    rng = np.random.default_rng(args.seed)
    b = args.max_batch
    plen = min(max(4, args.prompt_budget // 2), args.prompt_budget)
    tokens = rng.integers(0, cfg.vocab_size, size=(b, plen)).astype(np.int32)
    prompt_len = np.full((b,), plen, np.int32)
    t0 = time.time()
    toks = np.asarray(engine.generate(
        jax.random.PRNGKey(args.seed), {"tokens": tokens}, prompt_len,
        max_new_tokens=args.max_new))
    dt = time.time() - t0
    total = toks.shape[0] * toks.shape[1]
    print(f"process {jax.process_index()}/{jax.process_count()}: "
          f"generated {toks.shape[0]}x{toks.shape[1]} tokens in {dt:.1f}s "
          f"({total / dt:.1f} tok/s) first={toks[0, :8].tolist()}",
          flush=True)


def verify(argv=None):
    """``serve verify --artifact DIR``: static audit of a prepared
    artifact — the offline manifest lint (``repro.analysis``, MF rules)
    plus the collective dtype/shape contracts for exactly the specs the
    artifact's plan resolves, at the artifact's TP degree.  No model is
    built and no FLOPs are spent; exit 1 on error-severity findings."""
    ap = argparse.ArgumentParser(prog="repro.launch.serve verify")
    ap.add_argument("--artifact", required=True,
                    help="prepared DeploymentArtifact directory")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write findings as JSON")
    args = ap.parse_args(argv)

    from repro.analysis import contracts, manifest_lint
    from repro.analysis.findings import has_errors, to_json_text
    from repro.comm.spec import parse_collective
    from repro.plan import DeploymentArtifact

    manifest = DeploymentArtifact.load_manifest(args.artifact)
    findings = manifest_lint.run(artifact=args.artifact)
    coll = parse_collective(manifest["policy"]["collective"])
    tp = int(manifest["tp"])
    tps = tuple(t for t in (1, tp) if t <= jax.device_count())
    findings += contracts.lint_collectives(
        specs=[s.shorthand() for s in coll.specs()], tps=tps)
    for f in findings:
        print(f"  {f}")
    errs = sum(1 for f in findings if f.severity == "error")
    print(f"verify {args.artifact}: {len(findings)} finding(s), "
          f"{errs} error(s)")
    if args.json:
        with open(args.json, "w") as fh:
            fh.write(to_json_text(findings))
    return 1 if has_errors(findings) else 0


def main(argv=None):
    import sys

    enable_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "prepare":
        return prepare(argv[1:])
    if argv and argv[0] == "verify":
        return verify(argv[1:])

    ap = argparse.ArgumentParser()
    _plan_args(ap)
    _dist_args(ap)
    ap.add_argument("--artifact", default=None,
                    help="serve a prepared DeploymentArtifact directory "
                         "(skips quantize/plan at startup; the manifest "
                         "defines arch/scheme/backend/collective — plan "
                         "flags are ignored — and is validated against "
                         "the reconstructed config, policy, and mesh)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-budget", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--http", default=None, metavar="[HOST]:PORT",
                    help="serve over HTTP/SSE instead of the built-in "
                         "synthetic batch: POST /v1/generate streams "
                         "token events, GET /v1/health, GET /v1/stats "
                         "(':0' binds an ephemeral port)")
    ap.add_argument("--queue-capacity", type=int, default=64,
                    help="admission queue bound; a full wait line "
                         "answers 429 + Retry-After (HTTP mode)")
    args = ap.parse_args(argv)

    # multi-controller join MUST precede the first device/backend touch
    # (artifact loading already puts leaves on device)
    mesh_lib.init_distributed(args.coordinator, args.num_processes,
                              args.process_id)

    if args.mesh is not None and args.tp <= 1:
        args.tp = args.mesh.tp

    if args.artifact:
        cfg, policy, artifact, tp = _load_artifact(
            args, manifest_only=args.mesh is not None)
        if args.mesh is not None:
            # engine loads this process's shards per-rank from the path
            artifact = args.artifact
    else:
        cfg = _build_cfg(args)
        policy = ExecutionPolicy.from_config(cfg)
        artifact, tp = None, args.tp

    if isinstance(policy.collective, CollectivePlan):
        # name where the per-layer plan came from, and what it resolves to
        src = ("artifact manifest" if args.artifact
               else "--collective flag")
        plan = policy.collective
        print(f"per-layer collective plan ({src}): "
              + ", ".join(f"{pat} -> {spec.shorthand()}"
                          for pat, spec in plan.entries)
              + f", default -> {plan.default.shorthand()}")

    if args.mesh is not None:
        if args.mesh.tp != tp:
            raise SystemExit(
                f"--mesh {args.mesh.shorthand()} (tp={args.mesh.tp}) "
                f"disagrees with the plan's TP degree {tp}")
        # downstream BENCH_* snapshots record the serving grid
        os.environ["REPRO_MESH"] = args.mesh.shorthand()
        policy = policy.with_(mesh=args.mesh)
        mesh = args.mesh.build_mesh()
        ctx = ParallelContext(mesh=mesh, batch_axes=("data",),
                              policy=policy)
    elif tp > 1:
        mesh = mesh_lib.make_host_mesh(model=tp)
        ctx = ParallelContext(mesh=mesh, batch_axes=("data",),
                              policy=policy)
    else:
        ctx = REPLICATED

    max_seq = args.prompt_budget + args.max_new + 1
    engine = make_engine(cfg, jax.random.PRNGKey(args.seed), ctx=ctx,
                         max_seq=max_seq, policy=policy, artifact=artifact,
                         per_rank=True if (args.mesh is not None
                                           and args.artifact) else None)

    if args.mesh is not None:
        st = engine.load_stats
        resident = (f"resident_artifact_bytes="
                    f"{st.file_bytes_loaded}/{st.file_bytes_total} "
                    f"ranks={list(st.ranks)}" if st is not None
                    else "resident_artifact_bytes=n/a (in-memory plan)")
        print(f"mesh={args.mesh.shorthand()} "
              f"process={jax.process_index()}/{jax.process_count()} "
              f"{resident}", flush=True)

    if jax.process_count() > 1:
        return _run_multiprocess(args, cfg, engine, tp)

    if args.http is not None:
        from repro.serving import ServingServer

        host, _, port = args.http.rpartition(":")
        srv = ServingServer(
            engine, host=host or "127.0.0.1", port=int(port or 0),
            max_batch=args.max_batch, prompt_budget=args.prompt_budget,
            scfg=SamplingConfig(temperature=args.temperature, top_k=40),
            seed=args.seed, queue_capacity=args.queue_capacity)
        src = (f"artifact={args.artifact}" if args.artifact
               else "in-memory plan")
        print(f"serving {cfg.arch_id} on http://{srv.address[0]}:"
              f"{srv.port} [scheme={policy.scheme} "
              f"backend={policy.backend} "
              f"collective={policy.collective.shorthand()} "
              f"kv={policy.kv.shorthand()} tp={tp} "
              f"max_batch={args.max_batch} "
              f"queue={args.queue_capacity} {src}]", flush=True)
        srv.serve_forever()
        return

    sched = Scheduler(engine, max_batch=args.max_batch,
                      prompt_budget=args.prompt_budget,
                      scfg=SamplingConfig(temperature=args.temperature,
                                          top_k=40),
                      seed=args.seed)

    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for i in range(args.requests):
        plen = int(rng.integers(4, args.prompt_budget))
        sched.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32),
            max_new_tokens=args.max_new))
    done = sched.run()
    dt = time.time() - t0
    total_new = sum(len(r.output) for r in done.values())
    for rid, r in sorted(done.items()):
        print(f"req {rid}: prompt {len(r.prompt):3d} -> {r.output[:8]}...")
    src = f"artifact={args.artifact}" if args.artifact else "in-memory plan"
    print(f"\n{len(done)} requests, {total_new} tokens in {dt:.1f}s "
          f"({total_new / dt:.1f} tok/s) [scheme={policy.scheme} "
          f"backend={policy.backend} "
          f"collective={policy.collective.shorthand()} {src}]")


if __name__ == "__main__":
    main()
