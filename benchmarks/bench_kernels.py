"""Dequant-GEMM kernel locality accounting: ordered (Algorithm 1 layout)
vs unordered (naive Eq.-3 g_idx gather).

interpret=True wall time on CPU is not TPU-meaningful, so the primary
metric is the *modeled VMEM metadata traffic* per output tile, computed
from the BlockSpecs — the quantity the paper's data-locality argument is
about: ordered layouts load ``bk/gs`` scale rows per K-tile; the unordered
layout must keep the whole (G, bn) table resident and gather per-row.
"""

from __future__ import annotations

import time

import jax

from repro.core import quantization as qz
from repro.core.policy import ExecutionPolicy
from repro.kernels import dequant_matmul as dk, dispatch


def metadata_traffic(k, n, gs, bm, bn, bk, m, *, ordered: bool) -> int:
    """Bytes of scale/zero VMEM traffic for the whole GEMM (one pass)."""
    g = k // gs
    tiles = (m // bm) * (n // bn) * (k // bk)
    if ordered:
        per_tile = (bk // gs) * bn * 4 * 2          # bk/gs rows, scales+zeros
    else:
        per_tile = g * bn * 4 * 2                   # FULL table per tile
    return tiles * per_tile


def run(out_lines: list):
    title = "# bench_kernels: metadata VMEM traffic, ordered vs g_idx"
    print(title)
    out_lines.append(title)
    header = ("M,K,N,gs,layout,meta_bytes,ratio,interp_wall_ms")
    print(header)
    out_lines.append(header)
    for (m, k, n, gs) in [(16, 4096, 4096, 128), (16, 8192, 1792, 128),
                          (128, 4096, 4096, 128)]:
        rng = jax.random.PRNGKey(0)
        w = jax.random.normal(rng, (k, n // 16))   # small N slice for CPU
        res = qz.quantize(w, gs, act_order=True, rng=rng)
        x = jax.random.normal(rng, (m, k))
        bm, bn = min(128, m), 128
        bk = dk.pick_block_k(k, gs)

        # both layouts resolve through the dispatch registry, exactly the
        # path the deployed policy takes (backend="pallas")
        pol = ExecutionPolicy(backend="pallas").with_tiling(
            block_m=bm, block_n=bn)
        for layout, ql in (("ordered", res.ordered), ("gidx", res.naive)):
            kernel = dispatch.resolve(ql.kind, pol.backend)
            t0 = time.perf_counter()
            y = kernel(x, ql, pol)
            jax.block_until_ready(y)
            wall = (time.perf_counter() - t0) * 1e3
            meta = metadata_traffic(k, n, gs, bm, bn, bk, m,
                                    ordered=(layout == "ordered"))
            base = metadata_traffic(k, n, gs, bm, bn, bk, m, ordered=True)
            line = (f"{m},{k},{n},{gs},{layout},{meta},"
                    f"{meta / base:.1f},{wall:.1f}")
            print(line)
            out_lines.append(line)


if __name__ == "__main__":
    run([])
