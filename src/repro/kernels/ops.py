"""Public jit'd wrappers around the Pallas dequant kernels.

Handles the impedance between model code and kernel constraints:
* arbitrary leading batch dims (flattened to M),
* M/N padding to tile multiples (zero-padded, sliced off),
* dispatch on ``QuantizedLinear.kind`` (ordered vs g_idx gather),
* a stacked linear's ``layer``: read in place by the ordered kernel,
  sliced out for the others,
* interpret mode, resolved by ``core.policy.interpret_mode``: compiled
  Mosaic on a TPU, the Pallas interpreter elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.policy import interpret_mode
from repro.core.quantization import QuantizedLinear
from repro.kernels import dequant_matmul as dk


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("compute_dtype", "block_m",
                                             "block_n", "block_k",
                                             "interpret"))
def dequant_matmul(
    x: jax.Array,
    ql: QuantizedLinear,
    *,
    compute_dtype=jnp.float32,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``x @ dequantize(ql)`` with the fused Pallas kernel.

    ``x``: (..., K).  Returns (..., N) in ``compute_dtype``.
    """
    interpret = interpret_mode(interpret)
    *lead, k = x.shape
    if k != ql.k:
        raise ValueError(f"x K={k} != weight K={ql.k}")
    n = ql.n
    m = 1
    for d in lead:
        m *= d
    # x enters the kernel in the compute dtype, so its row block is whole
    # (sublane-count) tiles of that dtype
    x2 = x.reshape(m, k).astype(compute_dtype)

    bm = min(block_m, max(dk.sublanes(compute_dtype), m))
    x2 = _pad_to(x2, bm, 0)
    bn = min(block_n, n)
    if ql.kind != "ordered":
        ql = ql.sliced()    # the g_idx kernel takes one matrix
    qweight, scales, zeros = ql.qweight, ql.scales, ql.zeros
    if n % bn:
        qweight = _pad_to(qweight, bn, -1)
        scales = _pad_to(scales, bn, -1)
        zeros = _pad_to(zeros, bn, -1)

    bk_kw = {} if block_k is None else {"block_k": block_k}
    if ql.kind == "ordered":
        # a stacked linear is read in place at its layer
        y = dk.dequant_matmul_ordered(
            x2, qweight, scales, zeros, group_size=ql.group_size,
            layer=ql.layer, block_m=bm, block_n=bn,
            compute_dtype=compute_dtype, interpret=interpret, **bk_kw)
    else:
        y = dk.dequant_matmul_gidx(
            x2, qweight, scales, zeros, ql.g_idx,
            block_m=bm, block_n=bn, compute_dtype=compute_dtype,
            interpret=interpret, **bk_kw)
    return y[:m, :n].reshape(*lead, n)


def pallas_dequant_matmul_ordered(x, ql, *, compute_dtype=jnp.float32,
                                  block_m: int = 128, block_n: int = 128,
                                  block_k: int | None = None,
                                  interpret: bool | None = None):
    """Algorithm-1 (ordered-groups) fused kernel; dispatch-registry entry
    for ``("ordered", "pallas")`` — see ``kernels/dispatch.py``."""
    if ql.kind != "ordered":
        raise ValueError(f"ordered kernel got layout kind {ql.kind!r}")
    return dequant_matmul(x, ql, compute_dtype=compute_dtype,
                          block_m=block_m, block_n=block_n,
                          block_k=block_k, interpret=interpret)


def pallas_dequant_matmul_gidx(x, ql, *, compute_dtype=jnp.float32,
                               block_m: int = 128, block_n: int = 128,
                               block_k: int | None = None,
                               interpret: bool | None = None):
    """Naive g_idx-gather fused kernel; dispatch-registry entry for
    ``("naive", "pallas")``."""
    if ql.kind != "naive":
        raise ValueError(f"g_idx kernel got layout kind {ql.kind!r}")
    return dequant_matmul(x, ql, compute_dtype=compute_dtype,
                          block_m=block_m, block_n=block_n,
                          block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def dequantize(ql: QuantizedLinear, *, out_dtype=jnp.float32,
               interpret: bool | None = None) -> jax.Array:
    """Materialize the fp weight with the standalone dequant kernel."""
    interpret = interpret_mode(interpret)
    ql = ql.sliced()
    if ql.kind != "ordered":
        # unordered materialization has no locality to exploit; use ref path
        from repro.kernels import ref

        return ref.dequantize(ql).astype(out_dtype)
    return dk.dequantize_ordered(
        ql.qweight, ql.scales, ql.zeros, group_size=ql.group_size,
        out_dtype=out_dtype, interpret=interpret)


def flash_attention(q, k, v, *, causal=True, window=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """Fused flash attention (B, H, S, D); see kernels/flash_attention.py."""
    from repro.kernels import flash_attention as fa

    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret_mode(interpret))
