"""Mixture-of-Experts decoder (qwen3-moe, arctic).

Token-choice top-k routing with capacity-based gather/scatter dispatch:
the dispatch is expressed with gathers/scatters (memory ops), NOT one-hot
einsums, so the dry-run's cost_analysis reports honest FLOPs (a one-hot
dispatch einsum would claim T*E*C*d fake MACs).

Experts are quantized PlannedPairs stacked over E (and L); the paper's
act_order locality applies per-expert.  Experts are sharded over the
``data`` axis (EP) and the expert FFN runs per-shard; see DESIGN.md §5 for
why intra-expert TP-aware folding is a no-op under pure EP.

arctic: ``dense_residual=True`` adds a parallel dense (TP-sharded,
TP-aware-folded) MLP to every layer — that one exercises the paper's
technique directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm import CollectiveSpec, dispatch as comm_dispatch
from repro.core import schemes
from repro.core.policy import ExecutionPolicy

from repro.configs.base import ModelConfig
from repro.models import common as cm
from repro.models.common import ParallelContext

#: dotted pair paths matching the plan compiler's manifest entries — the
#: keys a per-layer ``CollectivePlan`` addresses these epilogues by
EXPERTS_PATH = "layers.moe.experts"
DENSE_MLP_PATH = "layers.moe.dense_mlp"


def _capacity(cfg: ModelConfig, tokens: int) -> int:
    c = int(cfg.capacity_factor * tokens * cfg.top_k / cfg.num_experts)
    return max(4, min(tokens, c))


def moe_block_params(cfg: ModelConfig, rng):
    r = cm.split_rngs(rng, ["router", "experts", "dense"])
    p = {
        "router": cm.dense_init(r["router"], (cfg.d_model, cfg.num_experts)),
        "experts": cm.stack_layer_params(
            lambda er: cm.mlp_params(cfg, er, d_ff=cfg.moe_dff),
            r["experts"], cfg.num_experts),
    }
    if cfg.dense_residual:
        p["dense_mlp"] = cm.mlp_params(cfg, r["dense"], d_ff=cfg.d_ff)
    return p


def moe_block_specs(cfg: ModelConfig, p, ctx: ParallelContext):
    # experts: E over the data axis (EP) AND the expert FFN's inner dims
    # over the model axis (TP within expert) — both are needed for the
    # big-MoE (arctic/qwen3-moe) weights to fit per-chip at scale.
    ep = ctx.ep_axis
    specs = {
        "router": P(None, None, None),
        "experts": cm.mlp_specs(cfg, p["experts"], ctx.model_axis,
                                lead=(None, ep)),
    }
    if cfg.dense_residual:
        specs["dense_mlp"] = cm.mlp_specs(cfg, p["dense_mlp"],
                                          ctx.model_axis)
    return specs


def _dispatch_local(cfg: ModelConfig, xt: jax.Array, router: jax.Array,
                    cap: int):
    """Token-choice top-k dispatch for a local token set.

    Returns (buf (E, cap, d), combine_fn(expert_out (E, cap, d)) -> (T, d)).
    """
    t, d = xt.shape
    e, k = cfg.num_experts, cfg.top_k
    scores = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    flat_e = idx.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = flat_pos < cap
    flat_tok = jnp.repeat(jnp.arange(t), k)

    buf = jnp.zeros((e, cap, d), dtype=xt.dtype)
    buf = buf.at[flat_e, jnp.where(keep, flat_pos, cap - 1)].add(
        jnp.where(keep[:, None], xt[flat_tok], 0).astype(xt.dtype),
        mode="drop")

    def combine(out):
        slots = out[flat_e, jnp.where(keep, flat_pos, 0)]
        slots = slots * (gate.reshape(-1)[:, None]
                         * keep[:, None]).astype(out.dtype)
        return jnp.zeros((t, d), out.dtype).at[flat_tok].add(slots)

    return buf, combine, (probs, idx)


def _expert_ffn_local(cfg: ModelConfig, experts, xs, tp_axis: str,
                      policy: ExecutionPolicy):
    """Per-rank expert FFN: ``xs (E_l, C, d)`` through this rank's expert
    shards (inner dims tp-sharded over ``tp_axis``); psum over tp."""
    from repro.core.reorder import PlannedPair

    if isinstance(experts, PlannedPair):
        # within-expert TP resolves its own spec from the deployment plan
        # (path "layers.moe.experts"), like every other epilogue — but the
        # EP combine needs every rank's COMPLETE expert output, so
        # strategies that scatter the result or skip the reduction fall
        # back to full-precision psum (compressed full-output strategies
        # like quant-int8 are fine: they still return the whole tensor).
        # The vmapped per-expert GEMMs stay on the jnp kernel — Pallas
        # under vmap-of-shard_map is not a supported lowering.
        spec = policy.collective.resolve(EXPERTS_PATH)
        if spec.name == "none" or comm_dispatch.scatters_output(spec):
            spec = CollectiveSpec(name="psum")
        pol = policy.with_(collective=spec, backend="jnp")
        fn = functools.partial(
            schemes._pair_local_forward, axis=tp_axis,
            activation=cfg.activation, policy=pol)
        return jax.vmap(fn)(xs, experts).astype(xs.dtype)

    act = schemes.ACTIVATIONS[cfg.activation]
    h = jnp.einsum("ecd,edf->ecf", xs, experts["w_up"].astype(xs.dtype))
    if "w_gate" in experts:
        h = act(jnp.einsum("ecd,edf->ecf", xs,
                           experts["w_gate"].astype(xs.dtype))) * h
    else:
        h = act(h)
    y = jnp.einsum("ecf,efd->ecd", h, experts["w_down"].astype(xs.dtype))
    return comm_dispatch.raw_psum(y, tp_axis)


def moe_forward_ep(cfg: ModelConfig, p, x, ctx: ParallelContext):
    """Explicit expert-parallel MoE layer (GShard-style) under shard_map.

    Why this exists: GSPMD cannot shard the scatter/gather dispatch of the
    auto-partitioned path — measured on qwen3-moe it *replicates* the
    expert GEMMs on all 256 chips (364x the ideal per-device FLOPs; see
    EXPERIMENTS.md §Perf).  Here the parallelism is explicit:

      tokens local per data rank -> local top-k dispatch into per-expert
      capacity buffers -> all_to_all over the data axis (tokens travel to
      the rank owning their expert) -> expert FFN with the within-expert
      dims tp-sharded over the model axis (+psum) -> all_to_all back ->
      local gate-weighted combine.
    """
    mesh = ctx.mesh
    dp = ctx.ep_axis
    tp = ctx.model_axis
    b, s, d = x.shape
    e = cfg.num_experts
    dsize = ctx.axis_size(dp)
    batch_sharded = bool(ctx.batch_axes) and b % dsize == 0

    x_spec = P(ctx.batch_spec if batch_sharded else None, None, None)
    especs = cm.mlp_specs(cfg, p["experts"], tp, lead=(dp,))
    in_specs = (x_spec, P(None, None), especs)

    t_local = (b // dsize if batch_sharded else b) * s
    cap = _capacity(cfg, t_local)

    pol = ctx.execution_policy

    def body(x_l, router, experts_l):
        bl, sl, _ = x_l.shape
        xt = x_l.reshape(bl * sl, d)
        buf, combine, _aux = _dispatch_local(cfg, xt, router, cap)
        # (E, cap, d) -> (E/D, D*cap, d): tokens travel to expert owners
        buf = comm_dispatch.all_to_all(buf, dp, split_axis=0,
                                       concat_axis=1)
        out = _expert_ffn_local(cfg, experts_l, buf, tp, pol)
        # (E/D, D*cap, d) -> (E, cap, d): results travel home
        out = comm_dispatch.all_to_all(out, dp, split_axis=1,
                                       concat_axis=0)
        return combine(out).reshape(bl, sl, d)

    y = jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=x_spec, check_vma=False,
    )(x, p["router"], p["experts"])

    if cfg.dense_residual:
        y = y + cm.mlp_forward(cfg, p["dense_mlp"], x, ctx,
                               path=DENSE_MLP_PATH)
    return y


def moe_forward(cfg: ModelConfig, p, x, ctx: ParallelContext,
                return_aux: bool = False):
    """x: (B, S, d) -> (B, S, d) [, aux load-balance loss]."""
    if (ctx.mesh is not None and ctx.shard_map_mlp and not return_aux
            and ctx.ep_axis is not None
            and cfg.num_experts % ctx.axis_size(ctx.ep_axis) == 0):
        return moe_forward_ep(cfg, p, x, ctx)
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    e, k = cfg.num_experts, cfg.top_k
    cap = _capacity(cfg, t)

    scores = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(scores, axis=-1)                       # (T, E)
    gate, idx = jax.lax.top_k(probs, k)                           # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)           # renorm

    # --- dispatch: position of each (token, slot) within its expert -------
    flat_e = idx.reshape(-1)                                      # (T*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)           # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - 1                          # (T*k, E)
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = flat_pos < cap
    flat_tok = jnp.repeat(jnp.arange(t), k)

    buf = jnp.zeros((e, cap, d), dtype=x.dtype)
    buf = buf.at[flat_e, jnp.where(keep, flat_pos, cap - 1)].add(
        jnp.where(keep[:, None], xt[flat_tok], 0), mode="drop")
    buf = ctx.shard(buf, ctx.ep_axis, None, None)

    # --- expert FFN (vmapped over E; quantized pairs keep act_order) ------
    def one_expert(ep, ex):
        return cm.mlp_forward(cfg, ep, ex[None], cm.REPLICATED)[0]

    out = jax.vmap(one_expert)(p["experts"], buf)                 # (E, C, d)
    out = ctx.shard(out, ctx.ep_axis, None, None)

    # --- combine -----------------------------------------------------------
    slots = out[flat_e, jnp.where(keep, flat_pos, 0)]             # (T*k, d)
    slots = slots * (gate.reshape(-1)[:, None] * keep[:, None]).astype(x.dtype)
    y = jnp.zeros((t, d), dtype=x.dtype).at[flat_tok].add(slots)
    y = y.reshape(b, s, d)

    if cfg.dense_residual:
        y = y + cm.mlp_forward(cfg, p["dense_mlp"], x, ctx,
                               path=DENSE_MLP_PATH)

    if return_aux:
        # Switch-style load-balance loss: E * sum_e f_e * P_e
        frac = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=(0, 1))
        pmean = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(frac * pmean)
        return y, aux
    return y


# ---------------------------------------------------------------------------
# full model: dense transformer skeleton with MoE blocks as the MLP
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, rng):
    r = cm.split_rngs(rng, ["embed", "layers", "norm"])

    def make_layer(lr):
        lrs = cm.split_rngs(lr, ["attn", "moe"])
        return {
            "ln1": cm.norm_params(cfg),
            "attn": cm.attention_params(cfg, lrs["attn"]),
            "ln2": cm.norm_params(cfg),
            "moe": moe_block_params(cfg, lrs["moe"]),
        }

    return {
        "embed": cm.embed_params(cfg, r["embed"]),
        "layers": cm.stack_layer_params(make_layer, r["layers"],
                                        cfg.num_layers),
        "final_norm": cm.norm_params(cfg),
    }


def param_specs(cfg: ModelConfig, params, ctx: ParallelContext):
    axis = ctx.model_axis
    norm = {"scale": P(None, None)} if cfg.norm_type == "rms" else \
        {"scale": P(None, None), "bias": P(None, None)}
    return {
        "embed": cm.embed_specs(cfg, axis, ctx.axis_size(axis)),
        "layers": {
            "ln1": dict(norm),
            "attn": cm.attention_specs(cfg, axis),
            "ln2": dict(norm),
            "moe": moe_block_specs(cfg, params["layers"]["moe"], ctx),
        },
        "final_norm": {k: P(None) for k in
                       (("scale", "bias") if cfg.norm_type == "layernorm"
                        else ("scale",))},
    }


def _layer(cfg, ctx, window, aux_acc=False):
    def body(x, lp, _):
        h = cm.attention_forward(cfg, lp["attn"],
                                 cm.apply_norm(cfg, lp["ln1"], x), ctx,
                                 window=window)
        x = x + h
        h = moe_forward(cfg, lp["moe"], cm.apply_norm(cfg, lp["ln2"], x), ctx)
        return x + h
    return body


def forward(cfg: ModelConfig, params, batch, ctx: ParallelContext, *,
            window=None):
    x = cm.embed_tokens(cfg, params["embed"], batch["tokens"], ctx)
    x = cm.scan_layers(_layer(cfg, ctx, window), x, params["layers"], ctx)
    x = cm.apply_norm(cfg, params["final_norm"], x)
    return cm.lm_head(cfg, params["embed"], x, ctx)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, *, window=None,
               dtype=jnp.bfloat16):
    return cm.init_kv_cache(cfg, cfg.num_layers, batch, seq_len,
                            window=window, dtype=dtype)


def init_paged_cache(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int, *, bits=None, dtype=jnp.bfloat16):
    del batch  # pure pool: per-slot state lives in the page table
    return cm.init_paged_kv_cache(cfg, cfg.num_layers, n_pages, page_size,
                                  bits=bits, dtype=dtype)


def cache_specs(cfg: ModelConfig, ctx: ParallelContext):
    return cm.kv_cache_specs(cfg, ctx)


def decode_step(cfg: ModelConfig, params, cache, tokens, pos,
                ctx: ParallelContext, *, window=None, pages=None):
    x = cm.embed_tokens(cfg, params["embed"], tokens[:, None], ctx)

    def body(x, lp, lc, _):
        h, nc = cm.attention_decode(cfg, lp["attn"],
                                    cm.apply_norm(cfg, lp["ln1"], x),
                                    lc, pos, ctx, window=window, pages=pages)
        x = x + h
        h = moe_forward(cfg, lp["moe"], cm.apply_norm(cfg, lp["ln2"], x), ctx)
        return x + h, nc

    x, new_cache = cm.scan_layers_cache(body, x, params["layers"], cache, ctx)
    x = cm.apply_norm(cfg, params["final_norm"], x)
    logits = cm.lm_head(cfg, params["embed"], x, ctx)
    return logits[:, 0], new_cache
