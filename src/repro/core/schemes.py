"""Runtime dequant-GEMM deployment schemes (paper Algorithms 2 and 3).

Three schemes, one arithmetic result (property-tested):

* ``naive-actorder`` — unordered Eq.-3 metadata gather.  TP: no extra
  collectives (chunks align naturally) but poor metadata locality.
* ``exllama`` — Algorithm-1 sorted layout.  TP (**paper's "Naive
  Algorithm"**, Algorithm 2): AllGather Y1 -> permute by P2 -> chunk.
* ``tp-aware`` — Algorithm 3: the P2 fold happened offline, so the TP path
  is GEMM -> GEMM -> trailing collective.  Strictly fewer collectives.

All functions are shape-polymorphic over leading batch dims: ``x`` is
``(..., K1)``.

Runtime knobs arrive as one ``ExecutionPolicy`` (``core/policy.py``);
``PlannedPair.forward(x, policy, mesh=...)`` is the canonical entry
point.  The kernel half of the plan dispatches through
``kernels/dispatch.py`` (``policy.backend``); the collective half through
``comm/dispatch.py`` (``policy.collective``) — no epilogue branching
happens here.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm import dispatch as comm
from repro.core.policy import ExecutionPolicy, resolve_policy
from repro.core.quantization import QuantizedLinear
from repro.core.reorder import PlannedPair


def _silu(x):
    return x * jax.nn.sigmoid(x)


ACTIVATIONS: dict[str, Callable] = {
    "silu": _silu,
    "gelu": functools.partial(jax.nn.gelu, approximate=True),
    "relu": jax.nn.relu,
    "identity": lambda x: x,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


def qmatmul(x: jax.Array, ql: QuantizedLinear,
            policy: Optional[ExecutionPolicy] = None) -> jax.Array:
    """``x @ dequantize(ql)`` via the policy-selected kernel.

    The kernel is resolved from ``(ql.kind, policy.backend)`` by the
    registry in ``kernels/dispatch.py`` — ``"jnp"`` materializes the fp
    weight (XLA fuses the dequant into the GEMM epilogue on TPU; also what
    the dry-run lowers so cost_analysis sees real FLOPs/bytes),
    ``"pallas"`` is the fused kernel (TPU hot path; interpret=True on
    CPU), ``"ref"`` the pure-jnp oracle.
    """
    policy = resolve_policy(policy)
    from repro.kernels import dispatch  # lazy: kernels optional at import

    return dispatch.qmatmul(x, ql, policy)


# ---------------------------------------------------------------------------
# single-device reference forwards
# ---------------------------------------------------------------------------

def pair_forward_reference(
    x: jax.Array,
    pp: PlannedPair,
    policy: Optional[ExecutionPolicy] = None,
    *,
    activation: Optional[str] = None,
) -> jax.Array:
    """Single-device forward of a planned pair; ground truth for TP tests."""
    policy = resolve_policy(policy)
    act = ACTIVATIONS[activation or "identity"]
    mm = functools.partial(qmatmul, policy=policy)

    if pp.scheme == "naive-actorder":
        y1 = mm(x, pp.up)
        if pp.gate is not None:
            y1 = act(mm(x, pp.gate)) * y1
        elif activation:
            y1 = act(y1)
        return mm(y1, pp.down)

    # exllama & tp-aware share the column-TP step: gather X by P1 first.
    xg = jnp.take(x, pp.p1_up, axis=-1)
    y1 = mm(xg, pp.up)
    if pp.gate is not None:
        # p1_gate None => gate shares p1_up (one gather, used twice)
        g = act(mm(xg if pp.p1_gate is None
                   else jnp.take(x, pp.p1_gate, axis=-1), pp.gate))
        y1 = g * y1
    elif activation:
        y1 = act(y1)
    if pp.scheme == "exllama":
        y1 = jnp.take(y1, pp.p2, axis=-1)   # runtime P2 permute (Alg. 2 l.3)
    # tp-aware: columns were folded by P2 offline — nothing to do.
    return mm(y1, pp.down)


# ---------------------------------------------------------------------------
# TP forwards (explicit collectives under shard_map)
# ---------------------------------------------------------------------------

def pair_pspecs(pp: PlannedPair, axis: str, x_batch_axes=()) -> PlannedPair:
    """PartitionSpec pytree matching ``pp`` for the model-TP axis ``axis``.
    A linear read in place (``layer`` set) keeps its stack dim whole and
    its layer index replicated."""

    def specs(ql: QuantizedLinear, **arrays) -> QuantizedLinear:
        lead = () if ql.layer is None else (None,)
        return dataclasses.replace(
            ql, layer=None if ql.layer is None else P(),
            **{f: None if s is None else P(*lead, *s)
               for f, s in arrays.items()})

    def col_specs(ql: QuantizedLinear) -> QuantizedLinear:
        col = (None, axis)
        return specs(ql, qweight=col, scales=col, zeros=col,
                     g_idx=(None,) if ql.g_idx is not None else None)

    def row_specs(ql: QuantizedLinear) -> QuantizedLinear:
        if ql.kind == "naive":
            return specs(ql, qweight=(axis, None), scales=(None, None),
                         zeros=(None, None), g_idx=(axis,))
        return specs(ql, qweight=(axis, None), scales=(axis, None),
                     zeros=(axis, None), g_idx=None)

    return dataclasses.replace(
        pp,
        up=col_specs(pp.up),
        gate=(col_specs(pp.gate) if pp.gate is not None else None),
        down=row_specs(pp.down),
        p1_up=(P(None) if pp.p1_up is not None else None),
        p1_gate=(P(None) if pp.p1_gate is not None else None),
        p2=(P(axis) if pp.p2 is not None else None),
    )


def _pair_local_forward(
    x: jax.Array,
    pp: PlannedPair,
    *,
    axis: str,
    activation: Optional[str],
    policy: ExecutionPolicy,
    pair_path: Optional[str] = None,
) -> jax.Array:
    """Per-rank body executed under shard_map.

    ``x`` is the local batch shard, replicated along ``axis``; the planned
    pair holds this rank's weight shards (column shards for up/gate, row
    shard for down, local P2 chunk for exllama).  The trailing collective
    is whatever ``policy.collective`` resolves to for this pair's dotted
    path (``pair_path``; a bare ``CollectiveSpec`` resolves to itself, a
    ``CollectivePlan`` does the per-layer glob lookup) — dispatched by the
    ``comm/dispatch.py`` registry, never branched here.
    """
    act = ACTIVATIONS[activation or "identity"]
    mm = functools.partial(qmatmul, policy=policy)

    if pp.scheme == "naive-actorder":
        # Original-order columns: local Y1 chunk already feeds the matching
        # down row-shard.  Comm: trailing collective only.  (Slow metadata
        # path.)
        y1 = mm(x, pp.up)
        if pp.gate is not None:
            y1 = act(mm(x, pp.gate)) * y1
        elif activation:
            y1 = act(y1)
    elif pp.scheme == "exllama":
        # Paper Algorithm 2 (the "Naive Algorithm" under TP).
        xg = jnp.take(x, pp.p1_up, axis=-1)
        y1 = mm(xg, pp.up)                                       # l.1 GEMM
        if pp.gate is not None:
            g = act(mm(xg if pp.p1_gate is None
                       else jnp.take(x, pp.p1_gate, axis=-1), pp.gate))
            y1 = g * y1
        elif activation:
            y1 = act(y1)
        y1_full = comm.all_gather_cols(y1, axis)                 # l.2
        y1 = jnp.take(y1_full, pp.p2, axis=-1)            # l.3+l.4 fused:
        # local P2 chunk both permutes and chunks the gathered tensor.
    elif pp.scheme == "tp-aware":
        # Paper Algorithm 3: offline fold removed the gather entirely.
        xg = jnp.take(x, pp.p1_up, axis=-1)
        y1 = mm(xg, pp.up)                                       # l.1 GEMM
        if pp.gate is not None:
            g = act(mm(xg if pp.p1_gate is None
                       else jnp.take(x, pp.p1_gate, axis=-1), pp.gate))
            y1 = g * y1
        elif activation:
            y1 = act(y1)
    else:
        raise ValueError(f"unknown scheme {pp.scheme!r}")

    y2 = mm(y1, pp.down)                             # l.2 / l.5 down GEMM
    # l.6 / l.3: close the row-TP layer with the planned collective, under
    # the "epilogue" scope, which names its ops in the compiled program's
    # op_name metadata.
    spec = policy.collective.resolve(pair_path)
    with jax.named_scope("epilogue"):
        return comm.apply(y2, axis, spec, policy)


def pair_forward_tp(
    x: jax.Array,
    pp: PlannedPair,
    mesh: jax.sharding.Mesh,
    policy: Optional[ExecutionPolicy] = None,
    *,
    axis: str = "model",
    batch_axes: tuple = (),
    activation: Optional[str] = None,
    pair_path: Optional[str] = None,
) -> jax.Array:
    """Tensor-parallel forward over mesh axis ``axis``.

    ``x``: (..., K1), sharded over ``batch_axes`` on its leading dim (if
    given), replicated along ``axis``.  Weights are consumed with the
    canonical TP sharding (see ``pair_pspecs``); under jit, GSPMD moves the
    globally-laid-out arrays into place, or callers pass pre-sharded arrays.
    ``pair_path`` names this pair in the deployment plan (dotted param
    path) so a per-layer ``CollectivePlan`` resolves the right epilogue.
    """
    policy = resolve_policy(policy)
    bspec = (batch_axes if batch_axes else None,) + (None,) * (x.ndim - 1)
    x_spec = P(*bspec)
    spec = policy.collective.resolve(pair_path)
    out_last = axis if comm.scatters_output(spec) else None
    out_spec = P(*((bspec[0],) + (None,) * (x.ndim - 2) + (out_last,)))

    fn = functools.partial(
        _pair_local_forward, axis=axis, activation=activation,
        policy=policy, pair_path=pair_path)
    # replication checking off: the body's outputs are partial sums or
    # sharded mid-epilogue by design
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(x_spec, pair_pspecs(pp, axis)),
        out_specs=out_spec, check_vma=False,
    )(x, pp)
