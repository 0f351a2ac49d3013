"""GPTQ-style group quantization with activation-order support.

Implements the quantization substrate the paper builds on:

* group-wise asymmetric int4 quantization: every ``group_size`` input
  channels (rows of the ``(K, N)`` weight matrix) share one
  ``(scale, zero)`` pair per output channel,
* the ``act_order`` (``desc_act``) optimization (paper Eq. 3): rows are
  *processed* in importance order, so the row->group mapping becomes the
  unordered group-index array ``g_idx``,
* GPTQ error compensation (Frantar et al. 2023) with static groups — the
  variant AutoGPTQ uses when ``static_groups=True`` together with
  ``desc_act=True``, which is exactly the setting the paper's deployment
  story assumes (metadata computed up-front, rows re-orderable offline),
* int4 <-> int32 packing (8 nibbles per 32-bit word along K), the storage
  format consumed by the Pallas dequant kernels.

Layout convention used across the repo: ``W`` is ``(K, N)`` with ``K`` the
input-feature (reduction) dim — ``Y = X @ W``.  GPTQ groups run along K.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

QMAX = 15  # int4: quantized values live in [0, 15]
PACK = 8   # 8 int4 values per uint32 along K


def choose_group_size(k: int, preferred: int = 128) -> int:
    """Largest divisor of ``k`` that is ``<= preferred``.

    Under TP the per-shard K extent may not be divisible by the preferred
    group size (e.g. arctic's d_ff/16 = 304); the deployment plan then falls
    back to the largest group size that tiles the shard exactly.
    """
    if k <= 0:
        raise ValueError(f"k must be positive, got {k}")
    g = min(preferred, k)
    while k % g != 0:
        g -= 1
    return g


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantizedLinear:
    """A GPTQ-quantized ``(K, N)`` weight in deployment layout.

    ``kind`` (static):
      * ``"naive"``   — rows in original order; ``g_idx`` is the unordered
        Eq.-3 array and MUST be used to gather metadata (poor locality).
      * ``"ordered"`` — rows permuted by ``P = argsort(g_idx)`` (Algorithm 1);
        groups are contiguous: row ``i`` belongs to group ``i // group_size``;
        ``g_idx`` is None.  The caller must feed ``X[:, P]``.

    ``layer`` (traced int32 scalar, or None): set, the arrays keep the
    leading ``L`` dim of a layer stack and the linear is their layer
    ``layer``, which the layer scan (``models/common.scan_layers``) hands
    over in place of a slice.  A kernel that can address the stack reads
    it there; every other reader takes ``sliced()``.
    """

    qweight: jax.Array                  # ([L,] K // 8, N) uint32 packed int4
    scales: jax.Array                   # ([L,] G, N)
    zeros: jax.Array                    # ([L,] G, N)  (float zero-points)
    g_idx: Optional[jax.Array]          # ([L,] K) int32 — only kind="naive"
    group_size: int = dataclasses.field(metadata=dict(static=True))
    kind: str = dataclasses.field(metadata=dict(static=True))
    layer: Optional[jax.Array] = None   # () int32: the layer of the stack

    @property
    def k(self) -> int:
        return self.qweight.shape[-2] * PACK

    @property
    def n(self) -> int:
        return self.qweight.shape[-1]

    @property
    def num_groups(self) -> int:
        return self.scales.shape[-2]

    def sliced(self) -> "QuantizedLinear":
        """The 2-D linear: itself, or layer ``layer`` of its stack read
        with ``lax.dynamic_index_in_dim`` (XLA fuses that read into a
        consumer it compiles; a custom call gets a copy)."""
        if self.layer is None:
            return self

        def take(a):
            return (None if a is None else jax.lax.dynamic_index_in_dim(
                a, self.layer, 0, keepdims=False))

        return dataclasses.replace(
            self, qweight=take(self.qweight), scales=take(self.scales),
            zeros=take(self.zeros), g_idx=take(self.g_idx), layer=None)


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------

def pack_int4(q: jax.Array) -> jax.Array:
    """Pack ``(K, N)`` int values in [0, 15] into ``(K//8, N)`` uint32."""
    k, n = q.shape
    if k % PACK != 0:
        raise ValueError(f"K={k} must be a multiple of {PACK}")
    q = q.astype(jnp.uint32).reshape(k // PACK, PACK, n)
    shifts = (jnp.arange(PACK, dtype=jnp.uint32) * 4)[None, :, None]
    return jnp.sum(q << shifts, axis=1, dtype=jnp.uint32)


def unpack_int4(qw: jax.Array) -> jax.Array:
    """Unpack ``(K//8, N)`` uint32 into ``(K, N)`` int32 values in [0, 15]."""
    k8, n = qw.shape
    shifts = (jnp.arange(PACK, dtype=jnp.uint32) * 4)[None, :, None]
    vals = (qw[:, None, :] >> shifts) & jnp.uint32(0xF)
    return vals.reshape(k8 * PACK, n).astype(jnp.int32)


# ---------------------------------------------------------------------------
# group metadata (static groups, computed up-front from W)
# ---------------------------------------------------------------------------

def _group_metadata(w_grouped: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Asymmetric min/max scales+zeros for ``(G, gs, N)`` grouped weights."""
    wmax = jnp.max(w_grouped, axis=1)
    wmin = jnp.min(w_grouped, axis=1)
    # guarantee 0 is representable and avoid zero scales
    wmax = jnp.maximum(wmax, 0.0)
    wmin = jnp.minimum(wmin, 0.0)
    scales = (wmax - wmin) / QMAX
    scales = jnp.where(scales <= 0, 1.0, scales)
    zeros = jnp.clip(jnp.round(-wmin / scales), 0, QMAX)
    return scales, zeros


def quantize_rtn(w: jax.Array, scales: jax.Array, zeros: jax.Array,
                 group_size: int) -> jax.Array:
    """Round-to-nearest int4 codes for ``(K, N)`` w given group metadata."""
    k, n = w.shape
    g = k // group_size
    wg = w.reshape(g, group_size, n)
    q = jnp.round(wg / scales[:, None, :] + zeros[:, None, :])
    return jnp.clip(q, 0, QMAX).astype(jnp.int32).reshape(k, n)


# ---------------------------------------------------------------------------
# GPTQ error compensation (static groups)
# ---------------------------------------------------------------------------

def _gptq_codes(w: jax.Array, scales: jax.Array, zeros: jax.Array,
                group_size: int, hinv_u: jax.Array) -> jax.Array:
    """Sequential GPTQ quantization with error feedback.

    ``w`` is already in *processing order* (rows pre-permuted by importance
    when act_order is on).  ``hinv_u`` is the upper-Cholesky factor of the
    inverse (permuted, damped) Hessian, as in Frantar et al.

    Returns int codes in processing order.
    """
    k, n = w.shape
    row_group = jnp.arange(k, dtype=jnp.int32) // group_size

    def body(w_work, i):
        g = row_group[i]
        s = scales[g]
        z = zeros[g]
        row = w_work[i]
        q = jnp.clip(jnp.round(row / s + z), 0, QMAX)
        dq = (q - z) * s
        d = hinv_u[i, i]
        err = (row - dq) / d
        # propagate error to not-yet-quantized rows (j > i)
        mask = (jnp.arange(k) > i).astype(w_work.dtype)[:, None]
        w_work = w_work - mask * hinv_u[i][:, None] * err[None, :]
        return w_work, q.astype(jnp.int32)

    _, q_rows = jax.lax.scan(body, w, jnp.arange(k))
    return q_rows


def cholesky_hinv_upper(h: jax.Array, damp_frac: float = 0.01) -> jax.Array:
    """Upper-triangular U with ``H^-1 = U^T U`` (GPTQ's ``Hinv``)."""
    k = h.shape[0]
    damp = damp_frac * jnp.mean(jnp.diag(h)) + 1e-8
    h = h + damp * jnp.eye(k, dtype=h.dtype)
    hinv = jnp.linalg.inv(h)
    # cholesky gives lower L with hinv = L L^T; the GPTQ factor is the
    # upper U = L^T (hinv = U^T U), so row i of U only touches cols j >= i.
    return jnp.linalg.cholesky(hinv).T


# ---------------------------------------------------------------------------
# top-level quantizer
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantResult:
    """Offline quantization artifact (the on-disk format + plan inputs).

    Registered as a pytree so the plan compiler's quantize stage can emit
    it under ``vmap`` (stacked layers/experts) and hand it to the layout
    stage as an intermediate ``PlanState`` value.
    """

    naive: QuantizedLinear          # disk layout: original row order + g_idx
    ordered: QuantizedLinear        # Algorithm-1 layout: rows sorted by group
    perm: jax.Array                 # P (K,) int32 — argsort(g_idx), stable
    g_idx: jax.Array                # (K,) unordered Eq.-3 group index array


def quantize(
    w: jax.Array,
    group_size: int = 128,
    act_order: bool = True,
    importance: Optional[jax.Array] = None,
    hessian: Optional[jax.Array] = None,
    use_gptq: bool = False,
    rng: Optional[jax.Array] = None,
    proc_order: Optional[jax.Array] = None,
) -> QuantResult:
    """Quantize ``W (K, N)`` and emit both deployment layouts.

    * ``importance``: per-input-channel importance (e.g. ``diag(H)``). With
      ``act_order=True`` rows are processed in descending-importance order;
      if None and ``rng`` given, a random permutation emulates an arbitrary
      reordering (paper Eq. 2); if both None, identity importance is used.
    * ``hessian``: (K, K) calibration Hessian for GPTQ error compensation.
    * ``use_gptq``: run the sequential error-feedback pass (slower, more
      accurate) rather than plain RTN.
    """
    k, n = w.shape
    if k % group_size != 0:
        raise ValueError(f"K={k} not divisible by group_size={group_size}")
    w = w.astype(jnp.float32)

    if proc_order is not None:
        pass  # caller-supplied processing order (e.g. block-constrained)
    elif act_order:
        if importance is not None:
            proc_order = jnp.argsort(-importance, stable=True)
        elif hessian is not None:
            proc_order = jnp.argsort(-jnp.diag(hessian), stable=True)
        elif rng is not None:
            proc_order = jax.random.permutation(rng, k)
        else:
            proc_order = jnp.arange(k)
    else:
        proc_order = jnp.arange(k)
    proc_order = proc_order.astype(jnp.int32)

    # Eq. 3: row (original index) proc_order[j] is processed at position j,
    # hence belongs to group j // G.
    inv = jnp.zeros(k, dtype=jnp.int32).at[proc_order].set(
        jnp.arange(k, dtype=jnp.int32))
    g_idx = inv // group_size                      # unordered (original order)

    w_proc = w[proc_order]                         # processing order
    scales, zeros = _group_metadata(
        w_proc.reshape(k // group_size, group_size, n))

    if use_gptq:
        if hessian is None:
            hessian = jnp.eye(k, dtype=jnp.float32)
        hperm = hessian[proc_order][:, proc_order]
        hinv_u = cholesky_hinv_upper(hperm)
        q_proc = _gptq_codes(w_proc, scales, zeros, group_size, hinv_u)
    else:
        q_proc = quantize_rtn(w_proc, scales, zeros, group_size)

    # --- naive (disk) layout: original row order, unordered g_idx ----------
    q_orig = jnp.zeros_like(q_proc).at[proc_order].set(q_proc)
    naive = QuantizedLinear(
        qweight=pack_int4(q_orig), scales=scales, zeros=zeros,
        g_idx=g_idx, group_size=group_size, kind="naive")

    # --- Algorithm 1: P = argsort(g_idx), rows sorted by group -------------
    perm = jnp.argsort(g_idx, stable=True).astype(jnp.int32)
    # rows sorted by group == processing order up to stable intra-group order;
    # re-derive codes from q_orig to stay layout-exact.
    q_sorted = q_orig[perm]
    ordered = QuantizedLinear(
        qweight=pack_int4(q_sorted), scales=scales, zeros=zeros,
        g_idx=None, group_size=group_size, kind="ordered")

    return QuantResult(naive=naive, ordered=ordered, perm=perm, g_idx=g_idx)


# ---------------------------------------------------------------------------
# dequantization (pure-jnp reference paths; kernels/ has the TPU versions)
# ---------------------------------------------------------------------------

def dequantize(ql: QuantizedLinear, dtype=jnp.float32) -> jax.Array:
    """Materialize the fp weight ``(K, N)`` in the linear's own row layout."""
    ql = ql.sliced()
    q = unpack_int4(ql.qweight).astype(jnp.float32)
    if ql.kind == "ordered":
        g_idx = jnp.arange(ql.k, dtype=jnp.int32) // ql.group_size
    else:
        g_idx = ql.g_idx
    s = jnp.take(ql.scales, g_idx, axis=0)
    z = jnp.take(ql.zeros, g_idx, axis=0)
    return ((q - z) * s).astype(dtype)


def permute_columns(ql: QuantizedLinear, p: jax.Array) -> QuantizedLinear:
    """Offline column permutation (the TP-aware fold, paper Algorithm 3).

    Column permutations commute with K-grouped quantization: packing runs
    along K and metadata is per-(group, column), so permuting columns of
    ``qweight``/``scales``/``zeros`` jointly is exact.
    """
    return dataclasses.replace(
        ql,
        qweight=ql.qweight[:, p],
        scales=ql.scales[:, p],
        zeros=ql.zeros[:, p],
    )


def quant_error(ql: QuantizedLinear, w: jax.Array,
                perm: Optional[jax.Array] = None) -> jax.Array:
    """Mean |W - dq(q(W))| against the original-layout W (debug/tests)."""
    dq = dequantize(ql)
    if ql.kind == "ordered":
        assert perm is not None
        dq = jnp.zeros_like(dq).at[perm].set(dq)
    return jnp.mean(jnp.abs(w - dq))


def make_hessian(x_cal: jax.Array, damp: float = 0.0) -> jax.Array:
    """Calibration Hessian ``2 X^T X`` (GPTQ) from ``(B, K)`` activations."""
    x = x_cal.astype(jnp.float32)
    h = 2.0 * x.T @ x
    if damp:
        h = h + damp * jnp.eye(h.shape[0], dtype=h.dtype)
    return h
