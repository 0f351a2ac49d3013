"""Kernel dispatch: ``(layout kind, backend)`` -> dequant-GEMM callable.

This registry is the ONLY place in the repo that maps backend names to
kernel implementations.  ``schemes.qmatmul`` (and therefore every scheme
forward, model MLP, and serving path) resolves its kernel here from the
``ExecutionPolicy.backend`` field; new backends register themselves with
the ``@register`` decorator and immediately become valid policy values —
no stringly-typed branching at the call sites.

Kernel contract: ``fn(x, ql, policy) -> y`` with ``x: (..., K)``,
``ql: QuantizedLinear`` (whose static ``kind`` selected the entry), and
``policy: ExecutionPolicy`` supplying dtypes and tiling.  Returns
``(..., N)`` in ``policy.compute_dtype``.

Seed entries (see DESIGN.md §1):

* ``ref``    — pure-jnp oracle (``kernels/ref.py``), both layouts.
* ``jnp``    — dequantize + ``jnp.matmul``; XLA fuses the dequant into the
  GEMM epilogue on TPU, and the dry-run lowers this path so cost_analysis
  sees real FLOPs/bytes.
* ``pallas`` — the fused kernels: Algorithm-1 ordered layout
  (``pallas-ordered``) and the naive g_idx gather (``pallas-gidx``).
* ``pallas-fused`` — the fused WIRE-epilogue kernel (ordered layout
  only, DESIGN.md §10): its output contract is the quantized-collective
  wire tuple ``(payload, scales[, zeros])``, not a dense ``y_partial``.
  It is never selected by ``ExecutionPolicy.backend``; the per-site
  ``CollectivePlan`` opts in via a ``:fused`` quant spec and
  ``schemes._pair_local_forward`` calls ``qmatmul_wire``.

A site the Pallas kernels cannot tile raises ``ValueError`` with the
reason (``dequant_matmul.check_tiling``) on every platform: the pallas
backend never swaps in another kernel behind the caller's back.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import quantization as qz
from repro.core.policy import ExecutionPolicy
from repro.core.quantization import PACK, QuantizedLinear

KernelFn = Callable[[jax.Array, QuantizedLinear, ExecutionPolicy], jax.Array]

_REGISTRY: dict[tuple[str, str], KernelFn] = {}

KINDS = ("ordered", "naive")

#: backends whose output is a wire tuple, not a dense (..., N) array —
#: resolvable via the same registry but excluded from ``qmatmul``.
WIRE_BACKENDS = ("pallas-fused",)


def register(kind: str, backend: str):
    """Decorator: register ``fn(x, ql, policy)`` for a (kind, backend)."""
    if kind not in KINDS:
        raise ValueError(f"unknown layout kind {kind!r}, expected {KINDS}")

    def deco(fn: KernelFn) -> KernelFn:
        _REGISTRY[(kind, backend)] = fn
        return fn

    return deco


def backends(kind: Optional[str] = None) -> tuple[str, ...]:
    """Registered backend names (optionally restricted to one layout kind)."""
    return tuple(sorted({b for (k, b) in _REGISTRY
                         if kind is None or k == kind}))


def resolve(kind: str, backend: str) -> KernelFn:
    """Look up the kernel for a (layout kind, backend) pair."""
    try:
        return _REGISTRY[(kind, backend)]
    except KeyError:
        raise ValueError(
            f"no kernel registered for layout kind={kind!r} "
            f"backend={backend!r}; registered backends for this kind: "
            f"{list(backends(kind))}") from None


def qmatmul(x: jax.Array, ql: QuantizedLinear,
            policy: ExecutionPolicy) -> jax.Array:
    """``x @ dequantize(ql)`` via the policy-selected kernel."""
    if policy.backend in WIRE_BACKENDS:
        raise ValueError(
            f"backend {policy.backend!r} emits a wire payload, not a dense "
            f"output; it is selected per site by a ':fused' collective spec "
            f"(CollectivePlan), not by ExecutionPolicy.backend")
    return resolve(ql.kind, policy.backend)(x, ql, policy)


def qmatmul_wire(x: jax.Array, ql: QuantizedLinear, policy: ExecutionPolicy,
                 *, spec, tp: int):
    """Fused GEMM + wire quantize -> ``comm.wire.WirePayload`` ready for
    ``comm.apply_wire`` (ring phase 1 starts from the kernel output).
    ``spec`` is the resolved quant-int8/int4 ``CollectiveSpec`` with
    ``fused=True``; caller guarantees ``supports_wire(ql, spec, tp)``."""
    from repro.comm.wire import WirePayload, wire_params

    payload, scales, zeros = resolve(ql.kind, "pallas-fused")(
        x, ql, policy, spec=spec, tp=tp)
    _, _, bs = wire_params(ql.n, tp, spec.bits, spec.block_size)
    return WirePayload(payload, scales, zeros, n=ql.n, tp=tp,
                       bits=spec.bits, block=bs,
                       out_dtype=policy.compute_dtype)


def supports_wire(ql: QuantizedLinear, spec, tp: int) -> bool:
    """True when the fused wire epilogue CAN serve this GEMM site: a
    quantized full-output collective, a real ring (``tp > 1``), the
    ordered layout, and a Pallas-tileable K.  The tuner uses this to
    decide whether to mark a chosen spec ``fused``; the runtime gate in
    ``schemes._pair_local_forward`` re-checks it (plus ``spec.fused``),
    so a compiled ``:fused`` plan never dies at forward time."""
    return wire_support(ql, spec, tp)[0]


def wire_support(ql: QuantizedLinear, spec, tp: int) -> tuple[bool, str]:
    """``supports_wire`` with the reason it fails — ``(True, "")`` when
    the wire kernel applies, else ``(False, why)``.  The reason string is
    shape/layout-derived (never trace-dependent), which is what
    ``schemes._warn_unfusable`` keys its once-per-(site, reason) cache
    on."""
    name = getattr(spec, "name", None)
    if name not in ("quant-int8", "quant-int4"):
        return False, f"collective {name!r} has no wire payload form"
    if tp <= 1:
        return False, "tp=1 (no ring to feed)"
    if ql.kind != "ordered" or ("ordered", "pallas-fused") not in _REGISTRY:
        return False, f"layout {ql.kind!r} has no wire-epilogue kernel"
    return _tileable(ql)


# ---------------------------------------------------------------------------
# Pallas tileability
# ---------------------------------------------------------------------------

def _tileable(ql: QuantizedLinear) -> tuple[bool, str]:
    """Can the Pallas grid tile this layout's K?  ``(True, "")`` or
    ``(False, why)``.  Ordered: exactly ``dequant_matmul.pick_block_k``'s
    precondition (it then always returns a legal K-tile, the whole K at
    worst).  g_idx: a power-of-two K-tile that is a multiple of 8."""
    from repro.kernels import dequant_matmul as dk

    if ql.kind == "ordered":
        try:
            dk.pick_block_k(ql.k, ql.group_size)
        except ValueError as e:
            return False, str(e)
    else:
        bk = min(256, ql.k)
        while bk > 1 and ql.k % bk:
            bk //= 2
        if ql.k % bk or bk % PACK:
            return (False, f"K={ql.k} has no power-of-two tile that is a "
                           f"multiple of {PACK}")
    return True, ""


def _require_tileable(ql: QuantizedLinear) -> None:
    ok, reason = _tileable(ql)
    if not ok:
        raise ValueError(f"pallas {ql.kind} kernel cannot tile K={ql.k}, "
                         f"N={ql.n}: {reason}")


# ---------------------------------------------------------------------------
# seed entries
# ---------------------------------------------------------------------------

@register("ordered", "ref")
@register("naive", "ref")
def _ref_dequant_matmul(x, ql, policy):
    from repro.kernels import ref

    return ref.dequant_matmul(x, ql, compute_dtype=policy.compute_dtype)


@register("ordered", "jnp")
@register("naive", "jnp")
def _jnp_dequant_matmul(x, ql, policy):
    w = qz.dequantize(ql, dtype=policy.compute_dtype)
    return jnp.matmul(x.astype(policy.compute_dtype), w)


@register("ordered", "pallas")
def _pallas_ordered(x, ql, policy):
    from repro.kernels import ops

    _require_tileable(ql)
    t = policy.tiling
    return ops.pallas_dequant_matmul_ordered(
        x, ql, compute_dtype=policy.compute_dtype,
        block_m=t.block_m, block_n=t.block_n, block_k=t.block_k,
        interpret=t.interpret)


@register("naive", "pallas")
def _pallas_gidx(x, ql, policy):
    from repro.kernels import ops

    _require_tileable(ql)
    t = policy.tiling
    return ops.pallas_dequant_matmul_gidx(
        x, ql, compute_dtype=policy.compute_dtype,
        block_m=t.block_m, block_n=t.block_n, block_k=t.block_k,
        interpret=t.interpret)


@register("ordered", "pallas-fused")
def _pallas_fused_wire(x, ql, policy, *, spec, tp):
    """Wire-contract entry (DESIGN.md §10): returns ``(payload, scales,
    zeros-or-None)`` over the ring-padded width instead of a dense
    ``y_partial`` — use via ``qmatmul_wire``, never ``qmatmul``."""
    from repro.kernels import ops

    t = policy.tiling
    return ops.dequant_matmul_wire(
        x, ql, tp=tp, wire_bits=spec.bits, wire_block=spec.block_size,
        compute_dtype=policy.compute_dtype,
        block_m=t.block_m, block_n=t.block_n, block_k=t.block_k,
        interpret=t.interpret)
