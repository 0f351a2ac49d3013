"""Pallas TPU kernels: fused int4 dequantize + GEMM.

TPU adaptation of the ExllamaV2 dequant GEMM (see DESIGN.md §2).  The unit
of locality on GPU is a warp's shared-memory staging of scales; on TPU it is
the VMEM residency of a ``(bk/gs, bn)`` metadata tile that is reused across
the whole ``(bm, bn)`` output tile.

Two variants, structurally mirroring the paper's two memory-access regimes:

* ``ordered`` — Algorithm-1 layout: quant groups are contiguous along K, so
  the K-block of size ``bk`` (a multiple of ``group_size``) touches exactly
  ``bk/gs`` metadata rows, streamed as a small VMEM tile.  This is the
  locality-friendly path.
* ``gidx`` — the naive Eq.-3 layout: rows belong to arbitrary groups, so the
  *entire* ``(G, bn)`` scale/zero table must stay VMEM-resident per N-tile
  and every row performs a dynamic gather.  This reproduces (structurally)
  the metadata-reload penalty the paper describes.

Packing: 8 int4 nibbles per uint32 along K (``quantization.pack_int4``); a
``(bk, bn)`` logical weight tile is a ``(bk/8, bn)`` uint32 VMEM tile,
unpacked with VPU shifts/masks and fed to the MXU in the compute dtype with
f32 accumulation.

A model's layers hold their packed weights, scales and zeros stacked as
``(L, K/8, N)`` and ``(L, G, N)``.  ``dequant_matmul_ordered`` reads
layer ``l`` of those stacks in place: ``l`` is a scalar-prefetch operand,
and the weight-side blocks have a squeezed leading dim whose index map
returns it.  Handed a slice instead, XLA would copy it first: a custom
call's operand cannot be a view into a larger buffer (a dot's slice XLA
fuses into the dot), so each layer's 26-44 MB of packed weights would be
written out once and read again by the kernel.

Every block obeys the TPU tiling rule (last two block dims divisible by
the dtype's sublane count and 128, or equal to the array's), which
``check_tiling`` states once for the entry points and the dispatcher.
The same ``pallas_call`` runs compiled on a TPU and interpreted
(``interpret=True``) on CPU, where the tests check it against ``ref.py``.
"""

from __future__ import annotations

import functools
from math import gcd

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PACK = 8

#: Mosaic's default scoped-VMEM budget on TPU v5e.  ``check_tiling`` keeps
#: a kernel's double-buffered blocks plus its dequant temporaries under it.
VMEM_LIMIT_BYTES = 16 * 1024 * 1024


def _lcm(a: int, b: int) -> int:
    return a * b // gcd(a, b)


def sublanes(dtype) -> int:
    """Rows of one (sublane, 128) VMEM tile: 8 for 32-bit, 16 for 16-bit."""
    return 32 // jnp.dtype(dtype).itemsize


def pick_block_k(k: int, group_size: int, target: int = 2048) -> int:
    """K-tile of the ordered kernels.

    A K-tile below K must keep three blocks on the (8, 128) grid: x
    ``(bm, bk)`` (bk % 128), packed weights ``(bk/8, bn)`` (bk/8 % 8) and
    metadata ``(bk/gs, bn)`` (bk/gs % 8).  So bk is a multiple of
    ``lcm(8*gs, 128)`` dividing K: the largest one not above
    ``max(target, lcm)``.  Where none divides K the whole K is one tile
    (each block then spans its array's full dim), e.g. K=2560 at gs=128.
    """
    if k % group_size or k % PACK:
        raise ValueError(f"K={k} is not a multiple of group_size="
                         f"{group_size} and of the packing factor {PACK}")
    base = _lcm(PACK * group_size, 128)
    cap = max(target, base)
    best = None
    for bk in range(base, min(k, cap) + 1, base):
        if k % bk == 0:
            best = bk
    return best or k


def _vmem_bytes(bm: int, bk: int, bn: int, group_size: int) -> int:
    """VMEM the ordered GEMM holds per grid step (f32 words): the
    double-buffered x / packed-weight / scales / zeros / output blocks,
    the accumulator, and the five (bk, bn) dequant temporaries (nibbles,
    codes, scales, zeros, weights)."""
    blocks = bm * bk + bk // PACK * bn + 2 * (bk // group_size) * bn \
        + bm * bn
    return 4 * (2 * blocks + bm * bn + 5 * bk * bn)


def check_tiling(m: int, k: int, n: int, group_size: int, bm: int, bn: int,
                 bk: int, x_dtype=jnp.float32) -> None:
    """Raise ``ValueError`` unless ``(bm, bn, bk)`` is a tiling the
    compiled ordered kernel accepts for an ``(m, k) @ (k, n)`` problem —
    the one statement of its constraints (grid divisibility, group
    alignment, the (8, 128) block rule, the VMEM budget)."""
    why = []
    if m % bm or n % bn or k % bk:
        why.append("blocks do not divide the problem")
    if bk % group_size or bk % PACK:
        why.append(f"bk is not a multiple of group_size and of {PACK}")
    if bm != m and bm % sublanes(x_dtype):
        why.append(f"bm is not a multiple of {sublanes(x_dtype)}")
    if bn != n and bn % 128:
        why.append("bn is not a multiple of 128")
    if bk != k and (bk % 128 or (bk // PACK) % 8
                    or (bk // group_size) % 8):
        why.append(f"bk is not a multiple of lcm(8*{group_size}, 128)")
    if not why and _vmem_bytes(bm, bk, bn, group_size) > VMEM_LIMIT_BYTES:
        why.append(f"{_vmem_bytes(bm, bk, bn, group_size)} B of VMEM > "
                   f"{VMEM_LIMIT_BYTES}")
    if why:
        raise ValueError(
            f"bad tiling m={m},n={n},k={k} bm={bm},bn={bn},bk={bk} "
            f"(group_size={group_size}): " + "; ".join(why))


def _unpack(qw_ref, bk: int):
    """``(bk/8, bn)`` packed uint32 block -> ``(bk, bn)`` f32 codes in
    [0, 15].  Shifts run on int32 (Mosaic has no uint32 -> f32 convert;
    the masked nibbles fit either way)."""
    qw = jax.lax.bitcast_convert_type(qw_ref[...], jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, PACK, 1), 1) * 4
    nibbles = (qw[:, None, :] >> shifts) & 0xF
    return nibbles.reshape(bk, qw.shape[-1]).astype(jnp.float32)


def _expand_groups(meta_ref, group_size: int):
    """``(bk/gs, bn)`` metadata block -> ``(bk, bn)`` rows: each group's
    row repeated ``group_size`` times by broadcast + reshape (no gather)."""
    meta = meta_ref[...].astype(jnp.float32)
    g, bn = meta.shape
    return jnp.broadcast_to(meta[:, None, :],
                            (g, group_size, bn)).reshape(g * group_size, bn)


# ---------------------------------------------------------------------------
# ordered-groups kernel
# ---------------------------------------------------------------------------

def _dequant_matmul_ordered_kernel(layer_ref, x_ref, qw_ref, s_ref, z_ref,
                                   o_ref, acc_ref, *, group_size: int,
                                   bk: int, compute_dtype):
    """Grid (M/bm, N/bn, K/bk); K innermost so acc_ref carries the sum.
    ``layer_ref`` (scalar prefetch) only steers the weight-side index
    maps."""
    del layer_ref
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = _unpack(qw_ref, bk)
    # one metadata row per quant group in this K-tile (VMEM-resident, reused
    # across the whole (bm, bn) tile — the TPU form of the locality win)
    s = _expand_groups(s_ref, group_size)
    z = _expand_groups(z_ref, group_size)
    w = ((q - z) * s).astype(compute_dtype)

    # an f32 compute dtype means f32 products: pin the contraction
    # precision instead of leaving it to Mosaic's default
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(compute_dtype), w,
        (((1,), (0,)), ((), ())),
        precision=(jax.lax.Precision.HIGHEST
                   if jnp.dtype(compute_dtype) == jnp.float32 else None),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def dequant_matmul_ordered(
    x: jax.Array,           # (M, K)
    qweight: jax.Array,     # ([L,] K//8, N) uint32
    scales: jax.Array,      # ([L,] G, N)
    zeros: jax.Array,       # ([L,] G, N)
    *,
    group_size: int,
    layer: jax.Array | int | None = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int | None = None,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: bool,
) -> jax.Array:
    """``x @ dequantize(weight)``.  Stacked ``(L, ...)`` weight arrays are
    read in place at ``layer``: the index is a scalar-prefetch operand
    that the weight-side index maps return as their squeezed leading
    block index, so the kernel's operands are the stacks themselves."""
    if qweight.ndim == 2:   # one matrix: a stack of one
        qweight, scales, zeros = qweight[None], scales[None], zeros[None]
        layer = 0
    m, k = x.shape
    n = qweight.shape[-1]
    bk = block_k or pick_block_k(k, group_size)
    bm = min(block_m, m)
    bn = min(block_n, n)
    check_tiling(m, k, n, group_size, bm, bn, bk, x.dtype)
    out_dtype = out_dtype or compute_dtype

    kernel = functools.partial(
        _dequant_matmul_ordered_kernel, group_size=group_size, bk=bk,
        compute_dtype=compute_dtype)
    gk = bk // group_size
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk, l: (i, kk)),
            pl.BlockSpec((None, bk // PACK, bn),
                         lambda i, j, kk, l: (l[0], kk, j)),
            pl.BlockSpec((None, gk, bn), lambda i, j, kk, l: (l[0], kk, j)),
            pl.BlockSpec((None, gk, bn), lambda i, j, kk, l: (l[0], kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, l: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), x, qweight, scales, zeros)


# ---------------------------------------------------------------------------
# unordered (g_idx gather) kernel — the naive-actorder path
# ---------------------------------------------------------------------------

def _dequant_matmul_gidx_kernel(g_ref, x_ref, qw_ref, s_ref, z_ref, o_ref,
                                acc_ref, *, bk: int, compute_dtype):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = _unpack(qw_ref, bk)

    # per-row dynamic gather from the FULL (G, bn) metadata tile — the
    # locality penalty of the unordered layout, reproduced structurally.
    rows = g_ref[pl.dslice(kk * bk, bk)][:, None]
    s = jnp.take_along_axis(s_ref[...].astype(jnp.float32), rows, axis=0)
    z = jnp.take_along_axis(z_ref[...].astype(jnp.float32), rows, axis=0)
    w = ((q - z) * s).astype(compute_dtype)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(compute_dtype), w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def dequant_matmul_gidx(
    x: jax.Array,           # (M, K)
    qweight: jax.Array,     # (K//8, N) uint32
    scales: jax.Array,      # (G, N)
    zeros: jax.Array,       # (G, N)
    g_idx: jax.Array,       # (K,) int32 — unordered group ids
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 256,
    compute_dtype=jnp.float32,
    out_dtype=None,
    interpret: bool,
) -> jax.Array:
    m, k = x.shape
    n = qweight.shape[1]
    g = scales.shape[0]
    bm = min(block_m, m)
    bn = min(block_n, n)
    bk = min(block_k, k)
    while k % bk:
        bk //= 2
    if bk % PACK or m % bm or n % bn:
        raise ValueError(f"bad tiling m={m},n={n},k={k} bm={bm},bn={bn},bk={bk}")
    out_dtype = out_dtype or compute_dtype

    grid = (m // bm, n // bn, k // bk)
    kernel = functools.partial(
        _dequant_matmul_gidx_kernel, bk=bk, compute_dtype=compute_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # NB: with scalar prefetch, index maps get the prefetch ref too.
            pl.BlockSpec((bm, bk), lambda i, j, kk, g_ref: (i, kk)),
            pl.BlockSpec((bk // PACK, bn), lambda i, j, kk, g_ref: (kk, j)),
            pl.BlockSpec((g, bn), lambda i, j, kk, g_ref: (0, j)),  # FULL G
            pl.BlockSpec((g, bn), lambda i, j, kk, g_ref: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, g_ref: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(g_idx, x, qweight, scales, zeros)


# ---------------------------------------------------------------------------
# standalone dequantize kernel (weight materialization, e.g. for conversion)
# ---------------------------------------------------------------------------

def _dequant_kernel(qw_ref, s_ref, z_ref, o_ref, *, group_size: int, bk: int):
    q = _unpack(qw_ref, bk)
    s = _expand_groups(s_ref, group_size)
    z = _expand_groups(z_ref, group_size)
    o_ref[...] = ((q - z) * s).astype(o_ref.dtype)


def dequantize_ordered(
    qweight: jax.Array, scales: jax.Array, zeros: jax.Array, *,
    group_size: int, block_n: int = 256, block_k: int | None = None,
    out_dtype=jnp.float32, interpret: bool,
) -> jax.Array:
    k = qweight.shape[0] * PACK
    n = qweight.shape[1]
    bk = block_k or pick_block_k(k, group_size)
    # the widest 128-multiple N-tile up to block_n that divides N, else N
    bn = next((b for b in range(block_n - block_n % 128, 0, -128)
               if n % b == 0), n)
    # no x operand: check the weight-side blocks under a one-tile M
    check_tiling(PACK, k, n, group_size, PACK, bn, bk)
    kernel = functools.partial(_dequant_kernel, group_size=group_size, bk=bk)
    return pl.pallas_call(
        kernel,
        grid=(k // bk, n // bn),
        in_specs=[
            pl.BlockSpec((bk // PACK, bn), lambda kk, j: (kk, j)),
            pl.BlockSpec((bk // group_size, bn), lambda kk, j: (kk, j)),
            pl.BlockSpec((bk // group_size, bn), lambda kk, j: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda kk, j: (kk, j)),
        out_shape=jax.ShapeDtypeStruct((k, n), out_dtype),
        interpret=interpret,
    )(qweight, scales, zeros)
