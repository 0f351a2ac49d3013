"""Collective-bytes accounting (the paper's Figure 5-8 mechanism, measured
exactly from lowered HLO rather than wall time) — three tables:

1. **per scheme × TP degree**: the paper's claim — the Naive Algorithm's
   AllGather cost grows with rank count while TP-Aware pays only the
   (unavoidable) trailing AllReduce, so their ratio is the comm-side
   speedup upper bound.

2. **per collective strategy × TP degree** (comm/dispatch registry): what
   the trailing collective itself costs under each registered
   ``CollectiveSpec`` — measured HLO bytes, the strategy's analytic
   ``bytes_on_wire`` model, their relative disagreement
   (``hlo_vs_model``: exactly 0 for psum/psum_scatter/quant-*; ``cast``
   reads 1.0 on CPU only, where XLA promotes the bf16 all-reduce to f32
   — the wire stays bf16 on TPU), the ratio vs the f32 ``psum``
   baseline, and the output's relative error vs the single-device
   reference.  This is the communication-compression table:
   ``quant-int8`` lands at ~(1 + 2/block)/4 ≈ 25% of the f32 psum bytes.

3. **per pair path under a ``CollectivePlan``**: the per-layer selection
   table — each pair resolves its own collective from the plan's glob
   map, shown with the lowered HLO's collective instruction counts
   (quant epilogues lower to all_to_all + all_gather phases, psum/cast
   to one all-reduce) proving the resolution happens per pair, plus
   measured and analytic bytes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import (CollectivePlan, CollectiveSpec,
                        dispatch as comm_dispatch)
from repro.configs import PAPER_PROBLEMS
from repro.core.policy import ExecutionPolicy

from benchmarks.bench_mlp import _mesh, _plan, _collective_bytes


def _scheme_table(out_lines: list, m: int):
    title = "# bench_comm: per-device ICI bytes by scheme (M=8)"
    print(title)
    out_lines.append(title)
    header = ("problem,TP,scheme,allgather_B,allreduce_B,total_B,"
              "vs_tpaware")
    print(header)
    out_lines.append(header)
    for pname, (k1, n1, n2) in PAPER_PROBLEMS.items():
        plans = {s: _plan(k1, n1, n2, s)
                 for s in ("naive-actorder", "exllama", "tp-aware")}
        for tp in (2, 4, 8):
            if tp > len(jax.devices()):
                continue
            mesh = _mesh(tp)
            x = jax.random.normal(jax.random.PRNGKey(1), (m, k1))
            res = {}
            for scheme, pp in plans.items():
                pol = ExecutionPolicy(scheme=scheme, backend="jnp",
                                      compute_dtype=jnp.float32)
                with mesh:
                    fn = lambda xx, p, pol=pol: p.forward(
                        xx, pol, mesh, activation=None)
                    coll = _collective_bytes(fn, (x, pp), mesh)
                res[scheme] = coll
            base = res["tp-aware"]["total_per_device"]
            for scheme, coll in res.items():
                line = (f"{pname},{tp},{scheme},{coll['all-gather']:.0f},"
                        f"{coll['all-reduce']:.0f},"
                        f"{coll['total_per_device']:.0f},"
                        f"{coll['total_per_device'] / max(base, 1):.2f}")
                print(line)
                out_lines.append(line)


def _strategy_table(out_lines: list, m: int):
    """Trailing-collective cost/error per registered strategy (tp-aware
    layout, so the epilogue is the ONLY collective in the program).

    ``hlo_B`` is parsed from the compiled program, ``model_B`` is the
    strategy's analytic ``bytes_on_wire``; ``hlo_vs_model`` is their
    relative disagreement — exactly 0 for psum / psum_scatter /
    quant-int8 / quant-int4 (tiling and non-tiling dims alike: both the
    implementation and the accounting are the padded two-phase ring).
    For ``cast`` the CPU backend promotes the bf16 all-reduce to f32
    (hlo_vs_model = 1.0) — on TPU the wire stays bf16, which is what
    the model column accounts."""
    title = "# bench_comm: trailing collective by strategy (M=8, tp-aware)"
    print(title)
    out_lines.append(title)
    header = ("problem,TP,collective,hlo_B,model_B,hlo_vs_model,"
              "vs_psum,rel_err")
    print(header)
    out_lines.append(header)
    for pname, (k1, n1, n2) in PAPER_PROBLEMS.items():
        pp = _plan(k1, n1, n2, "tp-aware")
        x = jax.random.normal(jax.random.PRNGKey(1), (m, k1))
        ref = None
        for tp in (2, 4, 8):
            if tp > len(jax.devices()):
                continue
            mesh = _mesh(tp)
            psum_model = CollectiveSpec(name="psum").bytes_on_wire(
                (m, n2), tp)
            for name in comm_dispatch.strategies():
                spec = CollectiveSpec.parse(name)
                pol = ExecutionPolicy(scheme="tp-aware", backend="jnp",
                                      compute_dtype=jnp.float32,
                                      collective=spec)
                with mesh:
                    fn = lambda xx, p, pol=pol: p.forward(
                        xx, pol, mesh, activation=None)
                    coll = _collective_bytes(fn, (x, pp), mesh)
                    if name == "none":
                        err = float("nan")   # partial sums by design
                    else:
                        y = np.asarray(jax.jit(fn)(x, pp), dtype=np.float32)
                        if ref is None:
                            ref = np.asarray(
                                pp.forward(x, activation=None),
                                dtype=np.float32)
                        err = (np.abs(y - ref).max()
                               / max(np.abs(ref).max(), 1e-9))
                model = spec.bytes_on_wire((m, n2), tp)
                hvm = (abs(coll["total_per_device"] - model)
                       / max(model, 1.0))
                line = (f"{pname},{tp},{name},"
                        f"{coll['total_per_device']:.0f},{model:.0f},"
                        f"{hvm:.3f},"
                        f"{model / max(psum_model, 1):.3f},{err:.1e}")
                print(line)
                out_lines.append(line)


#: the demo per-layer plan the third table resolves pairs against —
#: mirrors what `prepare --autotune-collectives` compiles into artifacts
PER_LAYER_PLAN = ("per-layer:*.mlp=quant-int8:128,"
                  "*.attn=cast:bfloat16,*=psum")


def _per_layer_table(out_lines: list, m: int):
    """Per-pair collective resolution under one ``CollectivePlan``.

    Two pair sites share one policy; each resolves its own spec by its
    dotted path.  ``hlo_counts`` lists the lowered collective
    instructions — the structural proof that ``layers.mlp`` runs the
    quantized all_to_all/all_gather epilogue while ``layers.attn`` runs
    a cast all-reduce and anything else falls back to psum, all within
    a single deployment plan."""
    plan = CollectivePlan.parse(PER_LAYER_PLAN)
    pol = ExecutionPolicy(scheme="tp-aware", backend="jnp",
                          compute_dtype=jnp.float32, collective=plan)
    title = f"# bench_comm: per-layer collective plan ({PER_LAYER_PLAN})"
    print(title)
    out_lines.append(title)
    header = ("problem,TP,pair_path,resolved,hlo_B,model_B,hlo_counts")
    print(header)
    out_lines.append(header)
    pname, (k1, n1, n2) = next(iter(PAPER_PROBLEMS.items()))
    pp = _plan(k1, n1, n2, "tp-aware")
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k1))
    for tp in (2, 4, 8):
        if tp > len(jax.devices()):
            continue
        mesh = _mesh(tp)
        for path in ("layers.mlp", "layers.attn", "layers.moe.experts"):
            spec = plan.resolve(path)
            with mesh:
                fn = lambda xx, p, path=path: p.forward(
                    xx, pol, mesh, activation=None, pair_path=path)
                coll = _collective_bytes(fn, (x, pp), mesh)
            model = spec.bytes_on_wire((m, n2), tp)
            counts = "+".join(f"{k}:{v}"
                              for k, v in coll["counts"].items() if v)
            line = (f"{pname},{tp},{path},{spec.shorthand()},"
                    f"{coll['total_per_device']:.0f},{model:.0f},{counts}")
            print(line)
            out_lines.append(line)


def run(out_lines: list):
    m = 8
    _scheme_table(out_lines, m)
    _strategy_table(out_lines, m)
    _per_layer_table(out_lines, m)


if __name__ == "__main__":
    run([])
