"""Collective dispatch: ``CollectiveSpec.name`` -> TP epilogue strategy.

Mirror of ``kernels/dispatch.py`` for the *communication* half of the
deployment plan: this registry is the ONLY place in the repo that maps
collective names to implementations.  ``schemes._pair_local_forward``
(and therefore every TP scheme forward, model MLP, and serving path)
closes its row-TP layer here from the ``ExecutionPolicy.collective``
spec; new strategies register themselves with the ``@register`` decorator
and immediately become valid spec names — no stringly-typed branching at
the call sites.

Strategy contract (``y_partial`` is one rank's full-size partial sum of
the row-TP output, executing inside ``shard_map`` over mesh axis
``axis``):

* ``apply(y_partial, axis, spec, policy) -> y`` — run the collective.
  **Dtype contract**: the result dtype is the INPUT dtype for every
  strategy — wire dtypes (bf16 words, int8/int4 payloads) never leak
  into the caller's residual stream, and at ``tp == 1`` every strategy
  is the identity.  (``cast`` historically returned its wire dtype,
  which compounded bf16 rounding per layer in an f32 stream — fixed,
  see ``_Cast``.)
* ``bytes_on_wire(shape, tp, spec) -> float`` — analytic per-device ICI
  bytes under the same ring cost model as ``launch/roofline.py``, so
  ``bench_comm`` accounts each strategy without compiling it,
* ``scatters_output`` — True when the result stays sharded along its
  last dim (the caller's out_specs must match).

Seed strategies (see DESIGN.md §1):

* ``psum``         — f32 all-reduce; bit-exact with the historical path.
* ``psum_scatter`` — reduce-scatter; output sharded, half the ICI bytes.
* ``cast``         — all-reduce in a low-bit wire dtype (default bf16);
  absorbs the old ad-hoc ``reduce_dtype`` cast.
* ``quant-int8``   — blockwise symmetric int8 quantized all-reduce
  (quantize -> exchange int8 payloads + f16 scales -> local
  dequant-accumulate), after Hansen-Palmus et al. 2024 / Dong et
  al. 2024: ~4x fewer wire bytes than f32 ``psum``.
* ``quant-int4``   — blockwise asymmetric int4: the wire payload is the
  weights' own storage format (``quantization.pack_int4``, 8 nibbles per
  uint32) plus f16 scale+zero per block — ~8x fewer payload bytes than
  f32 ``psum`` (``bench_comm``'s strategy table reports the measured and
  analytic bytes alongside the other registry entries).
* ``none``         — no collective: the paper's TP-aware
  gather-elimination made explicit (caller handles the partials).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.comm.spec import CollectiveSpec
from repro.core.quantization import (PACK, choose_group_size, pack_int4,
                                     unpack_int4)

_REGISTRY: dict[str, "CollectiveStrategy"] = {}


class CollectiveStrategy:
    """Base class: one named way to close a row-TP layer."""

    #: True when ``apply`` returns a result sharded along its last dim.
    scatters_output: bool = False

    def apply(self, y: jax.Array, axis: str, spec: CollectiveSpec,
              policy) -> jax.Array:
        raise NotImplementedError

    def bytes_on_wire(self, shape: tuple, tp: int,
                      spec: CollectiveSpec) -> float:
        raise NotImplementedError


def register(name: str):
    """Decorator: register a ``CollectiveStrategy`` subclass under ``name``."""

    def deco(cls):
        _REGISTRY[name] = cls()
        return cls

    return deco


def strategies() -> tuple[str, ...]:
    """Registered collective strategy names."""
    return tuple(sorted(_REGISTRY))


def resolve(name: str) -> CollectiveStrategy:
    """Look up the strategy for a collective name."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"no collective strategy registered for {name!r}; "
            f"registered strategies: {list(strategies())}") from None


def apply(y: jax.Array, axis: str, spec: CollectiveSpec, policy=None):
    """Close a row-TP layer: run ``spec`` on one rank's partial sums."""
    return resolve(spec.name).apply(y, axis, spec, policy)


def scatters_output(spec: CollectiveSpec) -> bool:
    return resolve(spec.name).scatters_output


def bytes_on_wire(spec: CollectiveSpec, shape, tp: int) -> float:
    return resolve(spec.name).bytes_on_wire(tuple(shape), int(tp), spec)


# ---------------------------------------------------------------------------
# raw-primitive facade
# ---------------------------------------------------------------------------
# The only sanctioned spellings of ``jax.lax`` collectives outside comm/
# and dist/ (``repro.analysis.ast_lint`` rule AS001): scheme and model
# code goes through these wrappers, so every cross-rank byte traces to a
# site the roofline cost model and the plan compiler account for.

def raw_psum(y: jax.Array, axis: str) -> jax.Array:
    """Full-precision all-reduce outside the strategy registry — for
    epilogues whose output contract is structural (e.g. MoE within-expert
    reduction), not a tunable quality/bytes trade-off."""
    return jax.lax.psum(y, axis)


def all_gather_cols(y: jax.Array, axis: str) -> jax.Array:
    """Gather last-dim shards into the full tensor (tiled) — the naive
    scheme's Algorithm-2 line-2 gather."""
    return jax.lax.all_gather(y, axis, axis=y.ndim - 1, tiled=True)


def all_to_all(x: jax.Array, axis: str, *, split_axis: int,
               concat_axis: int) -> jax.Array:
    """Tiled all_to_all (the MoE dispatch/return token shuffle)."""
    return jax.lax.all_to_all(x, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _full_bytes(shape, dtype) -> float:
    return math.prod(shape) * jnp.dtype(dtype).itemsize


def _wire_dtype(spec: CollectiveSpec):
    return spec.wire_dtype if spec.wire_dtype is not None else jnp.float32


def _blockwise_quantize(v: jax.Array, bs: int):
    """Symmetric int8 quantization over size-``bs`` blocks of the last dim.

    Returns ``(q int8 same-shape, scales f16 (..., n // bs))`` — the two
    wire payloads of the compressed collectives.
    """
    vb = v.reshape(*v.shape[:-1], v.shape[-1] // bs, bs)
    s = jnp.max(jnp.abs(vb), axis=-1) / 127.0
    s = jnp.maximum(s, jnp.finfo(jnp.float32).tiny)
    q = jnp.clip(jnp.round(vb / s[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(v.shape), s.astype(jnp.float16)


def _blockwise_dequantize(q: jax.Array, s: jax.Array, bs: int) -> jax.Array:
    qb = q.reshape(*q.shape[:-1], q.shape[-1] // bs, bs).astype(jnp.float32)
    return (qb * s.astype(jnp.float32)[..., None]).reshape(q.shape)


# ---------------------------------------------------------------------------
# seed strategies
# ---------------------------------------------------------------------------

@register("psum")
class _Psum(CollectiveStrategy):
    """Full-precision all-reduce — bit-exact with ``jax.lax.psum``."""

    def apply(self, y, axis, spec, policy):
        return jax.lax.psum(y, axis)

    def bytes_on_wire(self, shape, tp, spec):
        return _full_bytes(shape, _wire_dtype(spec)) * 2 * (tp - 1) / tp


@register("psum_scatter")
class _PsumScatter(CollectiveStrategy):
    """Reduce-scatter along the output dim; the caller keeps the output
    sharded (half the ICI bytes of an all-reduce)."""

    scatters_output = True

    def apply(self, y, axis, spec, policy):
        return jax.lax.psum_scatter(y, axis, scatter_dimension=y.ndim - 1,
                                    tiled=True)

    def bytes_on_wire(self, shape, tp, spec):
        return _full_bytes(shape, _wire_dtype(spec)) * (tp - 1) / tp


@register("cast")
class _Cast(CollectiveStrategy):
    """All-reduce in a low-bit wire dtype (default bf16): the per-rank f32
    partial sums are already complete, so only the cross-rank accumulation
    is lower-precision.  The result is cast BACK to the input dtype — the
    wire dtype is a transport detail, not an output contract (returning
    bf16 into an f32 residual stream silently downgraded every subsequent
    layer, compounding per layer; the quantized strategies already
    restored ``y.dtype``, so this makes the contract uniform)."""

    def apply(self, y, axis, spec, policy):
        if jax.lax.psum(1, axis) == 1:
            return y
        return jax.lax.psum(y.astype(spec.wire_dtype), axis).astype(y.dtype)

    def bytes_on_wire(self, shape, tp, spec):
        return _full_bytes(shape, spec.wire_dtype) * 2 * (tp - 1) / tp


@register("none")
class _NoCollective(CollectiveStrategy):
    """No epilogue collective: return this rank's partial sums.  The
    paper's TP-aware gather-elimination made explicit — used when the
    caller fuses the reduction into a later op (or measures compute
    alone)."""

    def apply(self, y, axis, spec, policy):
        return y

    def bytes_on_wire(self, shape, tp, spec):
        return 0.0


@register("quant-int8")
class _QuantInt8(CollectiveStrategy):
    """Blockwise-int8 quantized all-reduce (communication compression).

    Both phases of the ring all-reduce carry int8 payloads + f16 scales
    instead of f32 words (Hansen-Palmus et al. 2024; Dong et al. 2024):

    1. chunk the local partial along the output dim into ``tp`` pieces,
       quantize blockwise, ``all_to_all`` so each rank receives every
       rank's int8 copy of the chunk it owns,
    2. dequant-accumulate the owned chunk in f32 (the only full-precision
       arithmetic — quantization error does not compound across ranks),
    3. re-quantize the reduced chunk and ``all_gather`` payloads + scales;
       every rank dequantizes the assembled result locally.

    When the output dim does not tile ``tp``, the partial is zero-padded
    on the wire up to the next multiple of ``tp`` and sliced after — the
    SAME two-phase ring runs for every shape.  (The old one-phase
    fallback all-gathered every rank's full-size payload, ``payload *
    (tp - 1)`` per-device bytes vs the ring's ``2 * payload *
    (tp - 1) / tp`` — up to ``tp/2``× the wire traffic — while
    ``bytes_on_wire`` charged the two paths inconsistently, inflating
    ``bench_comm`` vs_psum ratios on non-tiling dims.  Both the
    implementation and the accounting are now the ring model.)
    """

    def apply(self, y, axis, spec, policy):
        tp = jax.lax.psum(1, axis)
        if tp == 1:
            return y
        n = y.shape[-1]
        out_dtype = y.dtype
        y32 = y.astype(jnp.float32)
        pad = (-n) % tp
        if pad:
            y32 = jnp.pad(y32, [(0, 0)] * (y32.ndim - 1) + [(0, pad)])
        chunk = (n + pad) // tp
        bs = choose_group_size(chunk, spec.block_size)
        yc = jnp.moveaxis(y32.reshape(*y32.shape[:-1], tp, chunk), -2, 0)
        q, s = _blockwise_quantize(yc, bs)
        # phase 1: exchange the (tp, ..., chunk) payload, dequant-accumulate
        q = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                               tiled=True)
        s = jax.lax.all_to_all(s, axis, split_axis=0, concat_axis=0,
                               tiled=True)
        red = jnp.sum(_blockwise_dequantize(q, s, bs), axis=0)
        # phase 2: re-quantize, gather, dequantize locally
        q2, s2 = _blockwise_quantize(red, bs)
        qg = jax.lax.all_gather(q2, axis, axis=q2.ndim - 1, tiled=True)
        sg = jax.lax.all_gather(s2, axis, axis=s2.ndim - 1, tiled=True)
        out = _blockwise_dequantize(qg, sg, bs)
        return (out[..., :n] if pad else out).astype(out_dtype)

    def bytes_on_wire(self, shape, tp, spec):
        if tp <= 1:
            return 0.0
        n_pad = shape[-1] + (-shape[-1]) % tp      # zero-padded on the wire
        n_elts = math.prod(shape[:-1]) * n_pad
        bs = choose_group_size(n_pad // tp, spec.block_size)
        payload = n_elts * 1 + (n_elts / bs) * 2   # int8 + f16 scales
        # all_to_all phase + all_gather phase, each (tp-1)/tp of payload
        return 2 * payload * (tp - 1) / tp


# ---------------------------------------------------------------------------
# int4 payload (packed like the weights)
# ---------------------------------------------------------------------------

def _pack4_last(q: jax.Array) -> jax.Array:
    """Pack int values in [0, 15] along the LAST dim via the weights'
    ``pack_int4`` layout (8 nibbles per uint32): (..., n) -> (..., n//8)."""
    moved = jnp.moveaxis(q, -1, 0)                        # (n, ...)
    flat = moved.reshape(moved.shape[0], -1)              # (n, rest)
    packed = pack_int4(flat)                              # (n//8, rest)
    return jnp.moveaxis(packed.reshape(moved.shape[0] // PACK,
                                       *moved.shape[1:]), 0, -1)


def _unpack4_last(qp: jax.Array) -> jax.Array:
    """Inverse of ``_pack4_last``: (..., n//8) uint32 -> (..., n) int32."""
    moved = jnp.moveaxis(qp, -1, 0)                       # (n//8, ...)
    flat = moved.reshape(moved.shape[0], -1)
    vals = unpack_int4(flat)                              # (n, rest)
    return jnp.moveaxis(vals.reshape(moved.shape[0] * PACK,
                                     *moved.shape[1:]), 0, -1)


def _blockwise_quantize_int4(v: jax.Array, bs: int):
    """Asymmetric int4 quantization over size-``bs`` blocks of the last
    dim — the same min/max formulation the weight quantizer uses.

    Returns ``(q int32 in [0,15] same-shape, scales f16 (..., n // bs),
    zeros f16)``."""
    vb = v.reshape(*v.shape[:-1], v.shape[-1] // bs, bs)
    vmax = jnp.maximum(jnp.max(vb, axis=-1), 0.0)
    vmin = jnp.minimum(jnp.min(vb, axis=-1), 0.0)
    s = (vmax - vmin) / 15.0
    s = jnp.where(s <= 0, 1.0, s)
    z = jnp.clip(jnp.round(-vmin / s), 0, 15)
    q = jnp.clip(jnp.round(vb / s[..., None] + z[..., None]), 0, 15)
    return (q.astype(jnp.int32).reshape(v.shape),
            s.astype(jnp.float16), z.astype(jnp.float16))


def _blockwise_dequantize_int4(q: jax.Array, s: jax.Array, z: jax.Array,
                               bs: int) -> jax.Array:
    qb = q.reshape(*q.shape[:-1], q.shape[-1] // bs, bs).astype(jnp.float32)
    s32 = s.astype(jnp.float32)[..., None]
    z32 = z.astype(jnp.float32)[..., None]
    return ((qb - z32) * s32).reshape(q.shape)


@register("quant-int4")
class _QuantInt4(CollectiveStrategy):
    """Blockwise-int4 quantized all-reduce (the ROADMAP PR-2 follow-up).

    Same two-phase ring structure as ``quant-int8``, but the wire payload
    is nibble-packed with the weights' own storage format
    (``quantization.pack_int4``: 8 values per uint32) plus an f16
    (scale, zero) pair per block — asymmetric, because 15 levels waste
    too much range on the symmetric variant's unused negative tail.
    When the output dim does not tile ``tp * 8`` (packing needs whole
    uint32 words per chunk), the partial is zero-padded on the wire up
    to the next such multiple and sliced after — the same padded ring
    (and the same ring ``bytes_on_wire`` accounting) as ``quant-int8``;
    the old full-payload one-phase all-gather fallback is gone.
    """

    def apply(self, y, axis, spec, policy):
        tp = jax.lax.psum(1, axis)
        if tp == 1:
            return y
        n = y.shape[-1]
        out_dtype = y.dtype
        y32 = y.astype(jnp.float32)
        pad = (-n) % (tp * PACK)
        if pad:
            y32 = jnp.pad(y32, [(0, 0)] * (y32.ndim - 1) + [(0, pad)])
        chunk = (n + pad) // tp
        bs = choose_group_size(chunk, spec.block_size)
        yc = jnp.moveaxis(y32.reshape(*y32.shape[:-1], tp, chunk), -2, 0)
        q, s, z = _blockwise_quantize_int4(yc, bs)
        # phase 1: exchange the packed (tp, ..., chunk//8) payload,
        # dequant-accumulate
        qp = jax.lax.all_to_all(_pack4_last(q), axis, split_axis=0,
                                concat_axis=0, tiled=True)
        s = jax.lax.all_to_all(s, axis, split_axis=0, concat_axis=0,
                               tiled=True)
        z = jax.lax.all_to_all(z, axis, split_axis=0, concat_axis=0,
                               tiled=True)
        red = jnp.sum(_blockwise_dequantize_int4(
            _unpack4_last(qp), s, z, bs), axis=0)
        # phase 2: re-quantize, pack, gather, dequantize locally
        q2, s2, z2 = _blockwise_quantize_int4(red, bs)
        qp2 = _pack4_last(q2)
        qg = jax.lax.all_gather(qp2, axis, axis=qp2.ndim - 1, tiled=True)
        sg = jax.lax.all_gather(s2, axis, axis=s2.ndim - 1, tiled=True)
        zg = jax.lax.all_gather(z2, axis, axis=z2.ndim - 1, tiled=True)
        out = _blockwise_dequantize_int4(_unpack4_last(qg), sg, zg, bs)
        return (out[..., :n] if pad else out).astype(out_dtype)

    def bytes_on_wire(self, shape, tp, spec):
        if tp <= 1:
            return 0.0
        n = shape[-1]
        n_pad = n + (-n) % (tp * PACK)             # whole words per chunk
        n_elts = math.prod(shape[:-1]) * n_pad
        bs = choose_group_size(n_pad // tp, spec.block_size)
        # nibble-packed payload + f16 (scale, zero) per block
        payload = n_elts * 0.5 + (n_elts / bs) * 4
        return 2 * payload * (tp - 1) / tp
