"""Pallas TPU flash attention (online-softmax, causal/windowed).

Why it's here: the dry-run roofline (EXPERIMENTS.md §Roofline) shows the
32k-prefill memory term dominated by S×T score-tile HBM round-trips —
unfused attention writes/reads the (S, T) f32 scores several times.  The
flash formulation keeps score tiles in VMEM and carries online-softmax
statistics across K-blocks, so HBM traffic drops to the q/k/v reads and
the output write (accounted analytically in §Perf — XLA's cost_analysis
cannot see inside a pallas_call).

Layout: q (B, H, S, D), k/v (B, H, T, D) — GQA callers repeat/broadcast KV
heads before the call (XLA fuses the broadcast into the DMA on TPU).

Grid: (B*H, S/bq, T/bk) with the K dimension innermost ("arbitrary"
semantics); VMEM scratch carries (acc, m, l) across K-blocks — the same
accumulator pattern as the dequant GEMM kernels.  Causal masking skips
whole blocks above the diagonal via pl.when (no wasted MXU work beyond
the diagonal block) and masks elementwise on the diagonal.

Validated on CPU with interpret=True against ``ref.flash_attention``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  bq: int, bk: int, scale: float, causal: bool,
                  window: Optional[int], seq_q: int, seq_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0 = qi * bq
    k0 = ki * bk

    # causal block skip: the whole K-block is above the diagonal when its
    # first key index exceeds the last query index of this Q-block
    run = True
    if causal:
        run = k0 <= q0 + bq - 1
    if window is not None:
        run = jnp.logical_and(run, k0 + bk - 1 > q0 - window)

    @pl.when(run)
    def _block():
        q = q_ref[0].astype(jnp.float32)             # (bq, d)
        k = k_ref[0].astype(jnp.float32)             # (bk, d)
        v = v_ref[0].astype(jnp.float32)             # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                # (bq, bk)

        iq = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        ik = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = mask & (ik <= iq)
        if window is not None:
            mask = mask & (ik > iq - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                       # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)              # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        # rows with no valid keys (shouldn't happen causally) keep l=0;
        # guard the divide anyway.
        l = jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...])
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,            # (B, H, S, D)
    k: jax.Array,            # (B, H, T, D)
    v: jax.Array,            # (B, H, T, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    b, h, s, d = q.shape
    t = k.shape[2]
    bq = min(block_q, s)
    bk = min(block_k, t)
    if s % bq or t % bk:
        raise ValueError(f"S={s}/T={t} must tile by ({bq}, {bk})")
    scale = d ** -0.5

    bh = b * h
    q3 = q.reshape(bh, s, d)
    k3 = k.reshape(bh, t, d)
    v3 = v.reshape(bh, t, d)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, scale=scale, causal=causal,
        window=window, seq_q=s, seq_k=t)
    out = pl.pallas_call(
        kernel,
        grid=(bh, s // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(b, h, s, d)


def hbm_traffic_bytes(b, h, s, t, d, *, dtype_bytes=2) -> dict:
    """Analytic HBM traffic of the flash kernel vs the unfused path.

    Flash: q,k,v read once per K-pass... on TPU the K-blocks re-stream k/v
    per Q-block: k/v read S/bq times; q and out touched once.
    Unfused: scores (S, T) f32 written+read ~3x (mask, softmax, av).
    """
    flash = (b * h * s * d * dtype_bytes          # q
             + 2 * b * h * t * d * dtype_bytes * (s // 128)  # k,v re-read
             + b * h * s * d * dtype_bytes)       # out
    unfused = (b * h * s * d * dtype_bytes * 2
               + 2 * b * h * t * d * dtype_bytes
               + 3 * b * h * s * t * 4)           # f32 score round-trips
    return {"flash": flash, "unfused": unfused,
            "ratio": unfused / max(flash, 1)}
