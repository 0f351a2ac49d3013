"""Kernel dispatch: ``(layout kind, backend)`` -> dequant-GEMM callable.

This registry is the ONLY place in the repo that maps backend names to
kernel implementations.  ``schemes.qmatmul`` (and therefore every scheme
forward, model MLP, and serving path) resolves its kernel here from the
``ExecutionPolicy.backend`` field; new backends register themselves with
the ``@register`` decorator and immediately become valid policy values —
no stringly-typed branching at the call sites.

Kernel contract: ``fn(x, ql, policy) -> y`` with ``x: (..., K)``,
``ql: QuantizedLinear`` (whose static ``kind`` selected the entry; with
``ql.layer`` set, one layer of a stack), and
``policy: ExecutionPolicy`` supplying dtypes and tiling.  Returns
``(..., N)`` in ``policy.compute_dtype``.

Seed entries (see DESIGN.md §1):

* ``ref``    — pure-jnp oracle (``kernels/ref.py``), both layouts.
* ``jnp``    — dequantize + ``jnp.matmul``; XLA fuses the dequant into the
  GEMM epilogue on TPU, and the dry-run lowers this path so cost_analysis
  sees real FLOPs/bytes.
* ``pallas`` — the fused kernels: Algorithm-1 ordered layout
  (``pallas-ordered``) and the naive g_idx gather (``pallas-gidx``).

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
#: the entries whose kernel reads a stacked linear's layer in place
_IN_PLACE: set[tuple[str, str]] = set()

KINDS = ("ordered", "naive")


def register(kind: str, backend: str, *, in_place: bool = False):
    """Decorator: register ``fn(x, ql, policy)`` for a (kind, backend).
    ``in_place``: the kernel reads a stacked ``ql`` (``ql.layer`` set)
    from the stack itself; the others take ``ql.sliced()``."""
    if kind not in KINDS:
        raise ValueError(f"unknown layout kind {kind!r}, expected {KINDS}")

    def deco(fn: KernelFn) -> KernelFn:
        _REGISTRY[(kind, backend)] = fn
        if in_place:
            _IN_PLACE.add((kind, backend))
        return fn

    return deco


def reads_in_place(kind: str, backend: str) -> bool:
    """Does the (kind, backend) kernel read a stacked linear in place?"""
    return (kind, backend) in _IN_PLACE


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
    return resolve(ql.kind, policy.backend)(x, ql, policy)


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


@register("ordered", "pallas", in_place=True)
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
