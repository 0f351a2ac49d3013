"""Paper Tables 1-28: Naive Algorithm (Alg. 2) vs TP-Aware (Alg. 3) on the
paper's MLP problem sizes, swept over batch size and TP degree.

Two measurements per point:
* CPU wall time (relative only — this container has no TPU; the paper's
  absolute ms are not reproducible, the *trend* speedup-grows-with-TP is)
* collective bytes from the lowered shard_map HLO (exact, hardware-
  independent — the quantity the paper's speedup is made of), and the
  derived TPU-model speedup  t_naive/t_tpaware with
  t = max(t_compute, t_memory) + t_collective on v5e constants.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import PAPER_BATCH_SIZES, PAPER_PROBLEMS
from repro.core import reorder
from repro.core.policy import ExecutionPolicy
from repro.launch import roofline


def _plan(k1, n1, n2, scheme, gs=128):
    rng = jax.random.PRNGKey(0)
    r = jax.random.split(rng, 2)
    # paper benchmarks the up->down pair without gate (section 3)
    w_up = jax.random.normal(r[0], (k1, n1), jnp.float32) * 0.02
    w_down = jax.random.normal(r[1], (n1, n2), jnp.float32) * 0.02
    return reorder.plan_pair(w_up, w_down, scheme=scheme,
                             group_size_up=gs, group_size_down=gs, rng=rng)


def _mesh(tp):
    from repro.launch.mesh import make_mesh

    n = len(jax.devices())
    return make_mesh((max(n // tp, 1), tp), ("data", "model"))


def _bench_wall(fn, *args, iters=3):
    y = fn(*args)
    jax.block_until_ready(y)
    t0 = time.perf_counter()
    for _ in range(iters):
        y = fn(*args)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / iters * 1e6    # us


def _collective_bytes(fn, args, mesh):
    lowered = jax.jit(fn).lower(*args)
    txt = lowered.compile().as_text()
    return roofline.parse_collective_bytes(txt, chips=mesh.devices.size)


def tpu_model_time(m, k1, n1, n2, tp, coll_per_dev):
    """v5e single-chip model: max(compute, weight-read) + collective."""
    flops = 2 * m * (k1 * n1 + n1 * n2) / tp
    wbytes = (k1 * n1 + n1 * n2) / 2 / tp          # int4 weights
    t_c = flops / roofline.PEAK_FLOPS
    t_m = wbytes / roofline.HBM_BW
    t_coll = coll_per_dev / roofline.ICI_BW
    return max(t_c, t_m) + t_coll


def run(out_lines: list):
    title = "# bench_mlp: paper problem sizes, Naive(Alg.2) vs TP-Aware(Alg.3)"
    print(title)
    out_lines.append(title)
    title = f"# devices: {len(jax.devices())}"
    print(title)
    out_lines.append(title)
    header = ("problem,M,TP,scheme,wall_us,coll_bytes_per_dev,"
              "tpu_model_ms,tpu_model_speedup")
    print(header)
    out_lines.append(header)

    for pname, (k1, n1, n2) in PAPER_PROBLEMS.items():
        # quantize once per scheme (paper: offline), reuse across TP/M
        plans = {s: jax.block_until_ready(_plan(k1, n1, n2, s))
                 for s in ("exllama", "tp-aware")}
        for tp in (1, 2, 4, 8):
            if tp > len(jax.devices()):
                continue
            mesh = _mesh(tp)
            for m in PAPER_BATCH_SIZES:
                x = jax.random.normal(jax.random.PRNGKey(1), (m, k1),
                                      jnp.float32)
                res = {}
                for scheme, pp in plans.items():
                    pol = ExecutionPolicy(scheme=scheme, backend="jnp",
                                          compute_dtype=jnp.float32)
                    # pp passed as a jit ARGUMENT (not closure) so XLA
                    # cannot constant-fold the dequantization at compile
                    with mesh:
                        fn = lambda xx, p, pol=pol: p.forward(
                            xx, pol, mesh, activation=None)
                        coll = _collective_bytes(fn, (x, pp), mesh)
                        wall = (_bench_wall(jax.jit(fn), x, pp)
                                if m == 8 else float("nan"))
                    t_model = tpu_model_time(
                        m, k1, n1, n2, tp, coll["total_per_device"])
                    res[scheme] = (wall, coll["total_per_device"], t_model)
                sp = res["exllama"][2] / res["tp-aware"][2]
                for scheme in ("exllama", "tp-aware"):
                    wall, coll_b, t_model = res[scheme]
                    line = (f"{pname},{m},{tp},{scheme},{wall:.0f},"
                            f"{coll_b:.0f},{t_model * 1e3:.4f},"
                            f"{sp if scheme == 'tp-aware' else 1.0:.2f}")
                    print(line)
                    out_lines.append(line)


if __name__ == "__main__":
    run([])
