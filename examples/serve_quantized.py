"""Serve a quantized model with batched requests through the scheduler —
the prepare-once / serve-many lifecycle under a (data=2, model=4) host
mesh.

Step 1 (offline, once per deployment): the plan compiler quantizes,
reorders/folds, and pre-shards the weights for the target TP degree,
freezing a ``DeploymentArtifact`` directory.

Step 2 (every server start): load + validate the artifact and serve.  No
GPTQ, no ``plan_pair`` at startup — the manifest guarantees the plan
matches the config, policy, and mesh.

Run:  PYTHONPATH=src python examples/serve_quantized.py [--arch qwen3-4b]
      (add --one-shot to compile in memory instead, the old flow;
       add --http to front the same engine with the streaming HTTP/SSE
       server from DESIGN.md §8 and replay the requests over the wire)
"""

import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_smoke_config
from repro.core.policy import ExecutionPolicy
from repro.launch.mesh import make_mesh
from repro.models.common import ParallelContext
from repro.plan import DeploymentArtifact, compiler
from repro.runtime.sampling import SamplingConfig
from repro.runtime.scheduler import Request, Scheduler
from repro.runtime.serve import make_engine

TP = 4


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=ARCH_IDS)
    ap.add_argument("--scheme", default="tp-aware")
    ap.add_argument("--collective", default="psum",
                    help="trailing collective spec (comm.dispatch registry "
                         "shorthand, e.g. psum, psum_scatter, "
                         "cast:bfloat16, quant-int8, quant-int4) or a "
                         "per-layer plan, e.g. "
                         "'per-layer:*.mlp=quant-int8:128,*=psum'")
    ap.add_argument("--autotune-collectives", action="store_true",
                    help="let the plan compiler pick a per-layer "
                         "CollectivePlan (analytic bytes + calibration "
                         "error probe; overrides --collective) — only "
                         "meaningful with the prepare/serve two-step")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--artifact", default=None,
                    help="reuse an existing artifact dir (skips prepare)")
    ap.add_argument("--one-shot", action="store_true",
                    help="compile the plan in memory at startup instead "
                         "of the prepare/serve two-step")
    ap.add_argument("--http", action="store_true",
                    help="serve over the HTTP/SSE front end (ephemeral "
                         "port) and stream the requests as SSE events "
                         "instead of driving the scheduler directly")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch).with_quant(mode="mlp",
                                                 scheme=args.scheme,
                                                 collective=args.collective)
    # the deployment plan, derived once from the config and threaded
    # through the engine to every quantized GEMM
    policy = ExecutionPolicy.from_config(cfg)

    artifact = None
    if not args.one_shot:
        # ---- step 1: prepare (offline compile; skipped when an artifact
        # directory is supplied) --------------------------------------------
        art_dir = args.artifact
        if art_dir is None:
            art_dir = os.path.join(tempfile.mkdtemp(prefix="repro_plan_"),
                                   args.arch)
            t0 = time.time()
            compiler.prepare(cfg, tp=TP, seed=0, policy=policy,
                             extra_manifest={"smoke": True},
                             autotune=args.autotune_collectives
                             ).save(art_dir)
            print(f"prepared artifact in {time.time() - t0:.1f}s "
                  f"-> {art_dir}")
        # ---- step 2: load + validate (no quantization from here on) -------
        artifact = DeploymentArtifact.load(art_dir)
        # the manifest is the source of truth for the plan (it may carry
        # a tuned per-layer CollectivePlan the CLI flags don't know)
        policy = artifact.policy()

    mesh = make_mesh((2, TP), ("data", "model"))
    ctx = ParallelContext(mesh=mesh, batch_axes=("data",), policy=policy)
    print(f"arch={args.arch} scheme={args.scheme} backend={policy.backend} "
          f"collective={policy.collective.shorthand()} "
          f"mesh=2x{TP} (data x model) "
          f"{'one-shot compile' if args.one_shot else 'from artifact'}")
    if artifact is not None:
        for site in artifact.manifest.get("collective_tuner", ()):
            print(f"  site {site['path']} [{site.get('kind', 'pair')}] -> "
                  f"{site['chosen']}")
        if artifact.aux:
            print(f"  aux plans: {', '.join(artifact.aux)} "
                  "(attention V->O folds served)")

    with mesh:
        engine = make_engine(cfg, jax.random.PRNGKey(0), ctx=ctx,
                             max_seq=48, policy=policy, artifact=artifact)
        if args.http:
            return _serve_http(engine, cfg, args)
        sched = Scheduler(engine, max_batch=4, prompt_budget=16,
                          scfg=SamplingConfig(temperature=0.7, top_k=40))
        rng = np.random.default_rng(0)
        t0 = time.time()
        for i in range(args.requests):
            plen = int(rng.integers(3, 16))
            sched.submit(Request(
                rid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    size=plen).astype(np.int32),
                max_new_tokens=args.max_new))
        done = sched.run()
    dt = time.time() - t0
    tokens = sum(len(r.output) for r in done.values())
    mid = sum(1 for step, _ in sched.admissions if step > 0)
    for rid, r in sorted(done.items()):
        print(f"  req {rid}: prompt[{len(r.prompt):2d}] -> {r.output}")
    print(f"\n{len(done)} requests ({mid} admitted mid-stream), "
          f"{tokens} new tokens, {dt:.1f}s "
          f"({tokens / dt:.1f} tok/s on CPU interpret)")


def _serve_http(engine, cfg, args):
    """Front the engine with the SSE server and replay the synthetic
    requests over real HTTP connections (one thread per client)."""
    import http.client
    import json
    import threading

    from repro.runtime.sampling import SamplingConfig
    from repro.serving import ServingServer

    srv = ServingServer(engine, max_batch=4, prompt_budget=16,
                        scfg=SamplingConfig(temperature=0.7, top_k=40),
                        queue_capacity=8).start()
    print(f"HTTP/SSE front end on http://127.0.0.1:{srv.port} "
          "(POST /v1/generate, GET /v1/health, GET /v1/stats)")
    rng = np.random.default_rng(0)
    bodies = []
    for i in range(args.requests):
        plen = int(rng.integers(3, 16))
        bodies.append({"prompt": rng.integers(0, cfg.vocab_size,
                                              size=plen).tolist(),
                       "max_new_tokens": args.max_new, "seed": i})
    t0 = time.time()

    def one(i):
        body = bodies[i]
        plen = len(body["prompt"])
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=300)
        conn.request("POST", "/v1/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        toks = []
        for line in conn.getresponse():
            if line.startswith(b"data: "):
                payload = json.loads(line[6:])
                if "token" in payload:
                    toks.append(payload["token"])
                elif "usage" in payload:
                    u = payload["usage"]
                    print(f"  req {i}: prompt[{plen:2d}] -> {toks} "
                          f"(ttft {u['ttft_ms']:.0f}ms)")
        conn.close()
        return len(toks)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(args.requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = srv.loop.stats()
    srv.shutdown()
    dt = time.time() - t0
    tok = stats["tokens"]["generated"]
    print(f"\n{stats['requests']['completed']} requests over HTTP, "
          f"{tok} new tokens, {dt:.1f}s ({tok / dt:.1f} tok/s), "
          f"ttft p50 {stats['latency_ms']['ttft'].get('p50')}ms")


if __name__ == "__main__":
    main()
