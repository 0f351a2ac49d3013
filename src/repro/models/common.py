"""Shared model substrate: norms, RoPE, GQA attention, parallel MLP.

All models are pure functions over nested-dict param pytrees.  Layers are
stacked along a leading L dim and driven by ``jax.lax.scan`` so that a
100-layer full config traces/lower as one layer.

Parallelism is carried by a ``ParallelContext``:
* ``mesh is None`` — single-device reference semantics (smoke tests),
* otherwise GSPMD sharding constraints are applied throughout, and the
  quantized MLP pairs run the paper's explicit-collective ``shard_map``
  schemes over the ``model`` axis.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import schemes
from repro.core.policy import DEFAULT_POLICY, ExecutionPolicy
from repro.core.reorder import PlannedPair


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    mesh: Optional[jax.sharding.Mesh] = None
    model_axis: str = "model"
    batch_axes: tuple = ("data",)
    shard_map_mlp: bool = True     # paper's explicit-collective MLP path
    remat: bool = False
    # The deployment plan the quantized MLP pairs execute under (kernel
    # backend, compute dtype, collective spec).  None means the historical
    # defaults (DEFAULT_POLICY: tp-aware / jnp / f32 / psum).
    policy: Optional[ExecutionPolicy] = None
    # Long-seq attention Q-chunking: lax.scan over chunks (True, memory-
    # bounded — the deployment default) or a python-unrolled loop (False —
    # used by the dry-run cost probes, because XLA's cost_analysis counts a
    # scan body only once).
    chunk_scan: bool = True
    # attention backend: "xla" (einsum path, used by the dry-run so
    # cost_analysis sees the FLOPs) or "flash" (fused Pallas kernel —
    # the TPU deployment path; interpret=True on CPU)
    attn_backend: str = "xla"

    @property
    def execution_policy(self) -> ExecutionPolicy:
        """The effective deployment plan: ``policy`` when set, else the
        historical defaults."""
        return self.policy if self.policy is not None else DEFAULT_POLICY

    def shard(self, x: jax.Array, *spec) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec)))

    @property
    def batch_spec(self):
        return self.batch_axes if self.batch_axes else None

    def axis_size(self, name: str) -> int:
        if self.mesh is None or name not in self.mesh.axis_names:
            return 1
        return dict(zip(self.mesh.axis_names,
                        self.mesh.devices.shape))[name]

    @property
    def ep_axis(self):
        """Expert-parallel axis: the innermost batch axis; falls back to
        'data' when the batch itself is unsharded (e.g. batch=1 decode) —
        EP sharding of the expert *weights* never requires a sharded
        batch."""
        if self.batch_axes:
            return self.batch_axes[-1]
        if self.mesh is not None and "data" in self.mesh.axis_names:
            return "data"
        return None


REPLICATED = ParallelContext()


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(rng, shape, scale: Optional[float] = None, dtype=jnp.float32):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(rng, shape) * scale).astype(dtype)


def split_rngs(rng, names):
    keys = jax.random.split(rng, len(names))
    return dict(zip(names, keys))


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------

def norm_params(cfg: ModelConfig, shape=None):
    d = shape or (cfg.d_model,)
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones(d), "bias": jnp.zeros(d)}
    return {"scale": jnp.ones(d)}


def apply_norm(cfg: ModelConfig, p, x):
    x32 = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm":
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"]
    return y.astype(x.dtype)


def rms_head_norm(x, scale, eps):
    """Per-head RMS norm (qwen3 qk_norm); x: (..., D), scale: (D,)."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: (B, S, H, D), positions: (S,) or (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, half)
        ang = ang[None, :, None, :]
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,half)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def head_grid(cfg: ModelConfig) -> tuple[int, int, int]:
    """(kv_pad, g_pad, h_pad): the deployed (KV, group) head grid.

    Without ``cfg.attn_tp_pad``, this is the logical (kv, g, h).  With it,
    the grid is minimally padded so ``h_pad % attn_tp_pad == 0`` — e.g.
    starcoder2's (2, 12, 24) becomes (2, 16, 32) on a 16-way axis.  Padded
    q/kv heads carry zero weights and zero wo rows, so the computed
    function is exactly the logical architecture's (see DESIGN.md §4).
    """
    kv, h = cfg.n_kv_heads, cfg.n_heads
    g = h // kv
    tp = cfg.attn_tp_pad
    if not tp or h % tp == 0:
        return kv, g, h
    best = None
    for gp in range(g, g + tp + 1):
        for kvp in range(kv, kv + tp + 1):
            if (kvp * gp) % tp == 0:
                if best is None or kvp * gp < best[0] * best[1]:
                    best = (kvp, gp)
                break
    kvp, gp = best
    return kvp, gp, kvp * gp


def _pad_heads(w: jax.Array, d: int, n_real: int, n_pad: int, hd: int,
               *, axis_last: bool = True) -> jax.Array:
    """Zero-pad a (d, n_real*hd) projection to (d, n_pad*hd) head-wise."""
    if n_real == n_pad:
        return w
    if axis_last:
        w = w.reshape(d, n_real, hd)
        w = jnp.pad(w, ((0, 0), (0, n_pad - n_real), (0, 0)))
        return w.reshape(d, n_pad * hd)
    w = w.reshape(n_real, hd, d)
    w = jnp.pad(w, ((0, n_pad - n_real), (0, 0), (0, 0)))
    return w.reshape(n_pad * hd, d)


def attention_params(cfg: ModelConfig, rng, d_in: Optional[int] = None):
    d = d_in or cfg.d_model
    hd, h, kv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    kvp, gp, hp = head_grid(cfg)
    g = h // kv
    r = split_rngs(rng, ["q", "k", "v", "o", "qn", "kn"])
    # init the logical heads, zero-pad to the deployed grid (kv-major
    # blocks: q head (kv_i, g_j) pairs with kv head kv_i after repeat)
    wq = dense_init(r["q"], (d, kv, g, hd)).reshape(d, h * hd)
    if (kvp, gp) != (kv, g):
        wq4 = wq.reshape(d, kv, g, hd)
        wq4 = jnp.pad(wq4, ((0, 0), (0, kvp - kv), (0, gp - g), (0, 0)))
        wq = wq4.reshape(d, hp * hd)
    wo = dense_init(r["o"], (kv, g, hd, d)).reshape(h * hd, d)
    if (kvp, gp) != (kv, g):
        wo4 = wo.reshape(kv, g, hd, d)
        wo4 = jnp.pad(wo4, ((0, kvp - kv), (0, gp - g), (0, 0), (0, 0)))
        wo = wo4.reshape(hp * hd, d)
    p = {
        "wq": wq,
        "wk": _pad_heads(dense_init(r["k"], (d, kv * hd)), d, kv, kvp, hd),
        "wv": _pad_heads(dense_init(r["v"], (d, kv * hd)), d, kv, kvp, hd),
        "wo": wo,
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones(hd)
        p["k_norm"] = jnp.ones(hd)
    return p


def attention_specs(cfg: ModelConfig, axis="model", stacked=True):
    lead = (None,) if stacked else ()
    p = {
        "wq": P(*lead, None, axis), "wk": P(*lead, None, axis),
        "wv": P(*lead, None, axis), "wo": P(*lead, axis, None),
    }
    if cfg.qk_norm:
        p["q_norm"] = P(*lead, None)
        p["k_norm"] = P(*lead, None)
    return p


def _sdpa(cfg: ModelConfig, ctx: ParallelContext, q, k, v, mask):
    """Scaled-dot-product attention in flat-head (Megatron head-TP) form.

    q: (B, S, H, D); k/v: (B, T, KV, D); mask: broadcastable to (B,?,S,T).

    GQA KV heads are broadcast to H before the einsums so the *head* dim is
    the contraction-free dim everywhere — it then shards cleanly over the
    model axis (GSPMD pads when H % tp != 0, e.g. whisper's 20 heads on a
    16-way axis).  Keeping the (group, kv) split instead would leave score
    tensors with dims 12/8/2... that a 16-way axis cannot shard at all,
    replicating the S×T score tile on every rank — 16× redundant FLOPs and
    an HBM blow-up at 32k prefill (measured; see DESIGN.md §4).  XLA fuses
    the jnp.repeat broadcast into the dots, so no repeated KV is
    materialized.
    """
    b, s, h, d = q.shape
    kvh = k.shape[2]
    if kvh != h:
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k) / (d ** 0.5)
    scores = scores.astype(jnp.float32)
    if s == 1:
        # decode: key-parallel — scores shard over the cache/T dim so the
        # (long) KV cache is never gathered across the model axis; the
        # trailing partial-sum all-reduce on out is tiny (one token).
        scores = ctx.shard(scores, ctx.batch_spec, None, None,
                           ctx.model_axis)
    else:
        # prefill/train: head-parallel (the padded grid shards exactly)
        scores = ctx.shard(scores, ctx.batch_spec, ctx.model_axis, None,
                           None)
    if mask is not None:
        scores = jnp.where(mask[:, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bthd->bshd", w, v).reshape(b, s, h * d)
    return out


def _flash_sdpa(cfg: ModelConfig, ctx: ParallelContext, q, k, v, *,
                causal: bool, window):
    """Fused flash-attention path (Pallas kernel; kernels/flash_attention).

    Embarrassingly parallel over (batch, heads) after head-grid padding, so
    under a mesh it runs inside shard_map with batch over the data axes and
    heads over the model axis — zero attention collectives, no S×T score
    HBM round-trip (the memory-term hillclimb; EXPERIMENTS.md §Perf).
    """
    from repro.kernels import ops

    b, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    # repeat KV to the full (padded) head grid BEFORE sharding so each
    # rank's q-head slice pairs with its own kv copies (kv-major layout)
    qt = q.transpose(0, 2, 1, 3)                         # (b, h, s, d)
    kt = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1)
    vt = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1)
    bq = min(128, s)

    def local(qb, kb, vb):
        return ops.flash_attention(qb, kb, vb, causal=causal,
                                   window=window, block_q=bq, block_k=bq)

    if ctx.mesh is None:
        out = local(qt, kt, vt)
    else:
        spec = P(ctx.batch_spec, ctx.model_axis, None, None)
        out = jax.shard_map(
            local, mesh=ctx.mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False,
        )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3).reshape(b, s, h * hd)


#: Q-chunk size for long-sequence attention: the (qc, T) score tile is the
#: only S×T-scaling temp, so prefill at 32k fits VMEM/HBM.  Chunking runs a
#: *python* loop (unrolled HLO), so the dry-run's cost analysis counts every
#: chunk — a lax.scan here would be invisible to cost_analysis.
Q_CHUNK = 2048
Q_CHUNK_MIN_SEQ = 8192


def project(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` for a dense attention projection, accumulated and returned
    in f32.  ``x`` is read in ``w``'s dtype: where the plan compiler stored
    ``w`` in bf16 (``plan.compiler.stage_attention_dtype``) both operands
    enter the MXU as bf16, as a default-precision f32 dot rounds them; with
    an f32 ``w`` this computes what ``x @ w`` computes."""
    return jnp.matmul(x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _vo_project_v(vo: PlannedPair, src, policy) -> jax.Array:
    """V projection through a precompiled V->O fold (``attention_fold``):
    gather the input by P1, run the folded quantized up GEMM.  The output
    channels are permuted *within each KV-head block* — attention mixes
    tokens, never channels, so the mix commutes and ``vo.down`` (whose
    sorted rows expect exactly this order) closes the pair."""
    xin = (jnp.take(src, vo.p1_up, axis=-1)
           if vo.p1_up is not None else src)
    return schemes.qmatmul(xin, vo.up, policy).astype(src.dtype)


def attention_forward(cfg: ModelConfig, p, x, ctx: ParallelContext, *,
                      positions=None, window=None, kv_x=None, causal=True,
                      vo: Optional[PlannedPair] = None):
    """Full-sequence attention (training / prefill / encoder / cross).

    ``kv_x``: source sequence for cross-attention (defaults to x).
    Long self-attention (S >= Q_CHUNK_MIN_SEQ) is Q-chunked: each chunk's
    softmax row sees the full key range, so the result is exact (no online
    rescaling needed), while the materialized score tile shrinks from
    (S, T) to (Q_CHUNK, T).

    ``vo``: optional precompiled V->O fold (``core/attention_fold``, the
    artifact's aux plans).  The V and O projections then run as quantized
    GEMMs over the folded layout instead of ``p["wv"]``/``p["wo"]`` —
    channel order inside each KV-head block is permuted, which attention's
    token-mixing commutes with, so the closed pair is the planned
    (quantized) function of the same architecture.
    """
    b, s, dm = x.shape
    hd = cfg.head_dim
    kvh, _, h = head_grid(cfg)          # deployed (possibly padded) grid
    src = kv_x if kv_x is not None else x
    t = src.shape[1]

    q = project(x, p["wq"]).reshape(b, s, h, hd)
    k = project(src, p["wk"]).reshape(b, t, kvh, hd)
    if vo is not None:
        v = _vo_project_v(vo, src, ctx.execution_policy)
        v = v.reshape(b, t, kvh, hd)
    else:
        v = project(src, p["wv"]).reshape(b, t, kvh, hd)
    q = ctx.shard(q, ctx.batch_spec, None, ctx.model_axis, None)
    k = ctx.shard(k, ctx.batch_spec, None, None, None)
    v = ctx.shard(v, ctx.batch_spec, None, None, None)

    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_head_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope and kv_x is None:
        if positions is None:
            positions = jnp.arange(s)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    # attend over K/V in the activation dtype, exactly as decode reads them
    # back from its cache (attention_decode): the f32 projections rounded
    # once, so prefill and token-by-token decode compute the same function
    k, v = k.astype(x.dtype), v.astype(x.dtype)

    def mask_rows(i0, rows: int):
        if not (causal and kv_x is None):
            return None
        i = (i0 + jnp.arange(rows))[:, None]
        j = jnp.arange(t)[None, :]
        m = j <= i
        if window is not None:
            m = m & (j > i - window)
        return jnp.broadcast_to(m, (b, rows, t))

    if ctx.attn_backend == "flash" and kv_x is None:
        out = _flash_sdpa(cfg, ctx, q, k, v, causal=causal, window=window)
    elif (causal and kv_x is None and s >= Q_CHUNK_MIN_SEQ
            and s % Q_CHUNK == 0):
        nc = s // Q_CHUNK
        if ctx.chunk_scan:
            qs = q.reshape(b, nc, Q_CHUNK, h, hd).swapaxes(0, 1)

            def chunk_body(carry, xs):
                ci, qch = xs
                o = _sdpa(cfg, ctx, qch, k, v, mask_rows(ci * Q_CHUNK,
                                                         Q_CHUNK))
                return carry, o

            _, outs = jax.lax.scan(chunk_body, None,
                                   (jnp.arange(nc), qs))
            out = outs.swapaxes(0, 1).reshape(b, s, -1)
        else:
            outs = [_sdpa(cfg, ctx, q[:, i0:i0 + Q_CHUNK], k, v,
                          mask_rows(i0, Q_CHUNK))
                    for i0 in range(0, s, Q_CHUNK)]
            out = jnp.concatenate(outs, axis=1)
    else:
        out = _sdpa(cfg, ctx, q, k, v, mask_rows(0, s))
    out = ctx.shard(out, ctx.batch_spec, None, ctx.model_axis)
    y = _attn_out_proj(p, out, vo, ctx, x.dtype)
    return ctx.shard(y, ctx.batch_spec, None, None)


def attention_decode(cfg: ModelConfig, p, x, cache, pos, ctx: ParallelContext,
                     *, window=None, pages=None,
                     vo: Optional[PlannedPair] = None):
    """One-token decode with KV cache.

    x: (B, 1, d); cache: {"k","v": (B, C, KV, D)} where C = cache capacity
    (full seq_len, or ``window`` for the ring-buffer variant); pos: the
    current position — a scalar (all requests in lockstep, the historical
    path) or a (B,) vector of *per-slot* positions (continuous batching:
    the scheduler admits a new request into a retired slot mid-stream, so
    each slot runs its own clock).  Returns (out, new_cache).

    Under a mesh whose model axis divides the KV heads
    (``kv_heads_local``), the dense cache is sharded by KV head
    (``kv_cache_specs``) and the cache write and the attention over it run
    inside a ``shard_map`` over heads: each rank holds its own KV heads
    and the query heads that read them.

    ``pages``: (B, Pmax) int32 per-slot page table — the cache is then a
    page *pool* {"k","v": (N_pages, page_size, KV, D)} (plus scale/zero
    leaves for quantized pages; ``repro.cache.paged``) instead of dense
    per-slot rows: the new token scatters into
    ``(pages[b, pos // ps], pos % ps)`` and K/V are gathered back by page
    index.  Masked gather columns (pos < j, including whole unallocated
    pages aliased to page 0) score -1e30, whose exp underflows to exactly
    0.0 in f32 — so the padded tail never contributes and paged decode is
    bit-identical to dense for fp pools, at any page size.
    """
    b = x.shape[0]
    hd = cfg.head_dim
    kvh, _, h = head_grid(cfg)          # deployed (possibly padded) grid
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1            # (B,) per-slot clocks

    q = project(x, p["wq"]).reshape(b, 1, h, hd)
    k = project(x, p["wk"]).reshape(b, 1, kvh, hd)
    if vo is not None:
        # folded V channels land in the cache; every read goes through
        # vo.down whose rows expect exactly this order (see
        # attention_forward) — so the cache layout is self-consistent.
        v = _vo_project_v(vo, x, ctx.execution_policy)
        v = v.reshape(b, 1, kvh, hd)
    else:
        v = project(x, p["wv"]).reshape(b, 1, kvh, hd)
    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_head_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.use_rope:
        posv = pos[:, None] if per_slot else jnp.full((1,), pos, jnp.int32)
        q = rope(q, posv, cfg.rope_theta)
        k = rope(k, posv, cfg.rope_theta)

    if pages is not None:
        from repro.cache import paged as paged_pool

        if window is not None:
            raise ValueError("paged decode does not take a ring-buffer "
                             "window (windowed caches are fixed-size per "
                             "slot and stay dense)")
        if not per_slot:
            raise ValueError("paged decode requires per-slot (B,) "
                             "positions (the page table is per slot)")
        new_cache = paged_pool.scatter_token(cache, k[:, 0], v[:, 0],
                                             pages, pos)
        kk, vv = paged_pool.gather(new_cache, pages)   # (B, T, KV, D)
        t = kk.shape[1]
        valid = jnp.arange(t)[None, :] <= pos[:, None]
        mask = jnp.broadcast_to(valid[:, None, :], (b, 1, t))
        kk = ctx.shard(kk, ctx.batch_spec, ctx.model_axis, None, None)
        vv = ctx.shard(vv, ctx.batch_spec, ctx.model_axis, None, None)
        q = ctx.shard(q, ctx.batch_spec, None, ctx.model_axis, None)
        out = _sdpa(cfg, ctx, q, kk.astype(x.dtype), vv.astype(x.dtype),
                    mask)
        y = _attn_out_proj(p, out, vo, ctx, x.dtype)
        return ctx.shard(y, ctx.batch_spec, None, None), new_cache

    if kv_heads_local(cfg, ctx):
        # head-local: each model-axis rank writes and reads its own KV
        # heads of the cache with the query heads that pair with them
        # (kv-major grid), so attention needs no collective; the only one
        # left is the wo all-reduce below
        heads = P(ctx.batch_spec, None, ctx.model_axis, None)
        out, new_cache = jax.shard_map(
            functools.partial(_dense_cache_attend, cfg, REPLICATED,
                              window=window, dtype=x.dtype),
            mesh=ctx.mesh,
            in_specs=(heads, heads, heads, {"k": heads, "v": heads},
                      P(ctx.batch_spec) if per_slot else P()),
            out_specs=(P(ctx.batch_spec, None, ctx.model_axis),
                       {"k": heads, "v": heads}),
            check_vma=False,
        )(q, k, v, cache, pos)
    else:
        out, new_cache = _dense_cache_attend(cfg, ctx, q, k, v, cache, pos,
                                             window=window, dtype=x.dtype)
    y = _attn_out_proj(p, out, vo, ctx, x.dtype)
    return ctx.shard(y, ctx.batch_spec, None, None), new_cache


def _dense_cache_attend(cfg: ModelConfig, ctx: ParallelContext, q, k, v,
                        cache, pos, *, window, dtype):
    """Write the new K/V rows into the dense cache, attend over it.

    q: (B, 1, H, D); k/v: (B, 1, KV, D); cache: {"k","v": (B, C, KV, D)}.
    Returns (out (B, 1, H*D), new_cache)."""
    b = q.shape[0]
    per_slot = pos.ndim == 1
    cap = cache["k"].shape[1]
    slot = pos % cap if window is not None else pos
    if per_slot:
        # per-slot scatter: each batch row writes its own cache position
        ck = cache["k"].at[jnp.arange(b), slot].set(
            k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[jnp.arange(b), slot].set(
            v[:, 0].astype(cache["v"].dtype))
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    # without head-local attention (KV heads fewer than, or not a multiple
    # of, the axis size) the cache shards along its (long) sequence dim
    ck = ctx.shard(ck, ctx.batch_spec, ctx.model_axis, None, None)
    cv = ctx.shard(cv, ctx.batch_spec, ctx.model_axis, None, None)

    j = jnp.arange(cap)
    pb = pos[:, None] if per_slot else pos          # (B, 1) | scalar
    if window is not None:
        # ring buffer: once pos >= cap every slot holds one of the last
        # `cap` positions; before that only slots <= pos are valid.
        valid = (j[None, :] <= pb) | jnp.broadcast_to(
            jnp.asarray(pb >= cap), (pb.shape[0] if per_slot else 1, cap))
    else:
        valid = jnp.broadcast_to(j[None, :] <= pb,
                                 (pb.shape[0] if per_slot else 1, cap))
    mask = jnp.broadcast_to(valid[:, None, :], (b, 1, cap))

    q = ctx.shard(q, ctx.batch_spec, None, ctx.model_axis, None)
    out = _sdpa(cfg, ctx, q, ck.astype(dtype), cv.astype(dtype), mask)
    return out, {"k": ck, "v": cv}


def _attn_out_proj(p, out, vo: Optional[PlannedPair], ctx, dtype):
    if vo is not None:
        return schemes.qmatmul(out, vo.down,
                               ctx.execution_policy).astype(dtype)
    return project(out, p["wo"])


def init_kv_cache(cfg: ModelConfig, num_layers: int, batch: int, seq_len: int,
                  *, window=None, dtype=jnp.bfloat16):
    cap = min(seq_len, window) if window else seq_len
    kvp, _, _ = head_grid(cfg)
    shape = (num_layers, batch, cap, kvp, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_kv_cache(cfg: ModelConfig, num_layers: int, n_pages: int,
                        page_size: int, *, bits=None, dtype=jnp.bfloat16):
    """Layer-stacked page pool replacing ``init_kv_cache``'s dense rows:
    leaves (L, N_pages, page_size, KVp, D) — see ``repro.cache.paged``."""
    from repro.cache import paged as paged_pool
    kvp, _, _ = head_grid(cfg)
    return paged_pool.init_pool((num_layers,), n_pages, page_size, kvp,
                                cfg.head_dim, dtype=dtype, bits=bits)


def kv_heads_local(cfg: ModelConfig, ctx: ParallelContext) -> bool:
    """Whether decode attention over the dense cache runs head-local under
    ``ctx``'s mesh: the model axis divides the deployed KV heads, so each
    rank holds whole KV heads and the query heads that read them."""
    if ctx.mesh is None:
        return False
    kvp, _, _ = head_grid(cfg)
    return kvp % ctx.axis_size(ctx.model_axis) == 0


def kv_cache_specs(cfg: ModelConfig, ctx: ParallelContext, lead: int = 1):
    """Specs of the dense cache leaves ``(*lead dims, B, C, KV, D)``: the KV
    heads over the model axis where attention runs head-local, else the
    sequence (KV heads may be fewer than the axis size, sequence never
    is)."""
    if kv_heads_local(cfg, ctx):
        s = P(*(None,) * lead, ctx.batch_spec, None, ctx.model_axis, None)
    else:
        s = P(*(None,) * lead, ctx.batch_spec, ctx.model_axis, None, None)
    return {"k": s, "v": s}


# ---------------------------------------------------------------------------
# MLP — the paper's subject
# ---------------------------------------------------------------------------

def mlp_params(cfg: ModelConfig, rng, *, d_ff=None):
    """One layer's raw (dense fp) MLP params.

    Model init always emits raw weights now — quantization and layout
    planning happen in ONE place, the offline plan compiler
    (``plan/compiler.py``), which ``registry.Model.init`` runs in memory
    when ``cfg.quant.mode == "mlp"`` (and which ``prepare`` runs ahead of
    time into a ``DeploymentArtifact``).  The 4-way rng split is kept so
    dense weights stay bit-identical to the historical init stream.
    """
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    r = split_rngs(rng, ["up", "gate", "down", "plan"])
    p = {"w_up": dense_init(r["up"], (d, ff)),
         "w_down": dense_init(r["down"], (ff, d))}
    if cfg.mlp_gated:
        p["w_gate"] = dense_init(r["gate"], (d, ff))
    return p


def mlp_specs(cfg: ModelConfig, params_like, axis="model", stacked=True,
              lead=None):
    """PartitionSpecs for one (possibly stacked) MLP param tree.

    ``lead``: explicit leading-dim spec entries (overrides ``stacked``) —
    e.g. ``(None, "data")`` for MoE experts stacked (L, E, ...) with E
    expert-parallel over the data axis.
    """
    if lead is None:
        lead = (None,) if stacked else ()
    if isinstance(params_like, PlannedPair):
        specs = schemes.pair_pspecs(params_like, axis)
        # prepend the stacking dim to every leaf spec
        def addlead(s):
            return P(*lead, *s) if isinstance(s, P) else s
        return jax.tree.map(addlead, specs,
                            is_leaf=lambda x: isinstance(x, P))
    out = {"w_up": P(*lead, None, axis), "w_down": P(*lead, axis, None)}
    if "w_gate" in params_like:
        out["w_gate"] = P(*lead, None, axis)
    return out


def mlp_forward(cfg: ModelConfig, p, x, ctx: ParallelContext, *,
                activation=None, path=None):
    """Apply an MLP block (quantized via the paper's schemes, or dense).

    ``path`` is the pair's dotted param path (e.g. ``"layers.mlp"``) —
    the key a per-layer ``CollectivePlan`` resolves this epilogue's
    collective by; model layer bodies pass the same path the plan
    compiler records in the artifact manifest."""
    act = activation or cfg.activation
    if isinstance(p, PlannedPair):
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        pol = ctx.execution_policy
        if ctx.mesh is not None and ctx.shard_map_mlp:
            y = p.forward(x2, pol, ctx.mesh, axis=ctx.model_axis,
                          batch_axes=ctx.batch_axes, activation=act,
                          pair_path=path)
        else:
            y = p.forward(x2, pol, activation=act)
        return y.reshape(*lead, -1).astype(x.dtype)
    a = schemes.ACTIVATIONS[act]
    h = x @ p["w_up"]
    if "w_gate" in p:
        h = a(x @ p["w_gate"]) * h
    else:
        h = a(h)
    h = ctx.shard(h, ctx.batch_spec, None, ctx.model_axis)
    y = h @ p["w_down"]
    return ctx.shard(y, ctx.batch_spec, None, None)


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------

def embed_params(cfg: ModelConfig, rng):
    r = split_rngs(rng, ["emb", "head"])
    v, vp = cfg.vocab_size, cfg.padded_vocab()
    emb = dense_init(r["emb"], (v, cfg.d_model), 1.0)
    head = dense_init(r["head"], (cfg.d_model, v))
    if vp != v:
        emb = jnp.pad(emb, ((0, vp - v), (0, 0)))
        head = jnp.pad(head, ((0, 0), (0, vp - v)))
    return {"embedding": emb, "lm_head": head}


def embed_specs(cfg: ModelConfig, axis="model", axis_size: int = 16):
    """Vocab-dim sharding when it divides the axis (jit *arguments* must
    shard exactly; intermediates may be padded); else shard d_model.
    With deployment vocab padding (cfg.padded_vocab) the vocab dim always
    shards — avoiding the full-logits psum the d_model fallback costs."""
    if cfg.padded_vocab() % axis_size == 0:
        return {"embedding": P(axis, None), "lm_head": P(None, axis)}
    return {"embedding": P(None, axis), "lm_head": P(axis, None)}


def embed_tokens(cfg, p, tokens, ctx: ParallelContext):
    x = jnp.take(p["embedding"], tokens, axis=0)
    return ctx.shard(x.astype(jnp.bfloat16)
                     if cfg.dtype == "bfloat16" else x,
                     ctx.batch_spec, None, None)


def lm_head(cfg, p, x, ctx: ParallelContext):
    logits = x.astype(jnp.float32) @ p["lm_head"].astype(jnp.float32)
    v, vp = cfg.vocab_size, cfg.padded_vocab()
    if vp != v:
        # mask padded vocab columns: exp(-1e30) == 0, softmax/loss exact
        mask = jnp.where(jnp.arange(vp) < v, 0.0, -1e30)
        logits = logits + mask
    return ctx.shard(logits, ctx.batch_spec, None, ctx.model_axis)


# ---------------------------------------------------------------------------
# layer scan helper
# ---------------------------------------------------------------------------

def scan_layers(body, x, stacked_params, ctx: ParallelContext, extra=None):
    """Scan ``body(x, layer_params, extra) -> x`` over stacked layers."""
    fn = body
    if ctx.remat:
        fn = jax.checkpoint(body)

    def step(carry, lp):
        # params may be f32 while activations are bf16; keep the carry dtype
        # stable so lax.scan typechecks (mixed-precision policy: activations
        # stay in the model compute dtype between layers).
        return fn(carry, lp, extra).astype(carry.dtype), None

    y, _ = jax.lax.scan(step, x, stacked_params)
    return y


def scan_layers_cache(body, x, stacked_params, stacked_cache, ctx, extra=None):
    """Like scan_layers but also threads per-layer cache: body returns
    (x, new_cache_l)."""
    fn = body
    if ctx.remat:
        fn = jax.checkpoint(body)

    def step(carry, xs):
        lp, lc = xs
        y, nc = fn(carry, lp, lc, extra)
        return y.astype(carry.dtype), nc

    y, new_cache = jax.lax.scan(step, x, (stacked_params, stacked_cache))
    return y, new_cache


#: layers one eager ``vmap`` of a layer init covers: bounds the random
#: generator's temporaries (about 4x its output) to a few layers' worth
INIT_CHUNK = 4


def stack_layer_params(make_layer, rng, n: int):
    """Initialize ``n`` layers stacked along a leading dim (vmapped so a
    100-layer full config traces one layer, not 100).

    Run eagerly (a full-width init on a host), the layers are generated
    ``INIT_CHUNK`` at a time and written into the stacked buffers in
    place: the same values as one vmap over all layers, at the peak
    memory of the result plus one chunk."""
    rngs = jax.random.split(rng, n)
    if isinstance(rngs, jax.core.Tracer) or n <= INIT_CHUNK:
        return jax.vmap(make_layer)(rngs)
    out = None
    for i in range(0, n, INIT_CHUNK):
        part = jax.vmap(make_layer)(rngs[i:i + INIT_CHUNK])
        if out is None:
            out = jax.tree.map(
                lambda a: jnp.zeros((n,) + a.shape[1:], a.dtype), part)
        out = _write_rows(out, part, i)
    return out


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(buf, part, start):
    return jax.tree.map(
        lambda b, p: jax.lax.dynamic_update_slice_in_dim(b, p, start, 0),
        buf, part)
