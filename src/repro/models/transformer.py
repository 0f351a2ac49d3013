"""Generic dense decoder-only transformer (llama/qwen/mistral/starcoder/
granite families): pre-norm GQA attention + (optionally quantized) MLP."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import common as cm
from repro.models.common import ParallelContext


def init_params(cfg: ModelConfig, rng):
    r = cm.split_rngs(rng, ["embed", "layers", "norm"])

    def make_layer(lr):
        lrs = cm.split_rngs(lr, ["attn", "mlp"])
        return {
            "ln1": cm.norm_params(cfg),
            "attn": cm.attention_params(cfg, lrs["attn"]),
            "ln2": cm.norm_params(cfg),
            "mlp": cm.mlp_params(cfg, lrs["mlp"]),
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
            "mlp": cm.mlp_specs(cfg, params["layers"]["mlp"], axis),
        },
        "final_norm": {k: P(None) for k in
                       (("scale", "bias") if cfg.norm_type == "layernorm"
                        else ("scale",))},
    }


#: this family consumes precompiled attention V->O folds (artifact aux
#: plans) — the registry only forwards ``aux`` to modules that declare it.
SUPPORTS_ATTN_VO = True

#: dotted path ``stage_fold_attention`` records this family's attention
#: dicts under (the key into the artifact's aux ``attn_plans``).
ATTN_VO_PATH = "layers.attn"


def _layer_vo(aux):
    """The stacked V->O ``PlannedPair`` for this family's layers, if the
    artifact carried one (scanned alongside the layer params)."""
    if not aux:
        return None
    return (aux.get("attn_plans") or {}).get(ATTN_VO_PATH)


def _layer(cfg, ctx, window, mlp_path="layers.mlp"):
    def body(x, lp, _):
        h = cm.attention_forward(cfg, lp["attn"],
                                 cm.apply_norm(cfg, lp["ln1"], x), ctx,
                                 window=window, causal=cfg.causal,
                                 vo=lp.get("attn_vo"))
        x = x + h
        h = cm.mlp_forward(cfg, lp["mlp"], cm.apply_norm(cfg, lp["ln2"], x),
                           ctx, path=mlp_path)
        return x + h
    return body


def forward(cfg: ModelConfig, params, batch, ctx: ParallelContext, *,
            window=None, aux=None):
    """Train/prefill forward: batch={"tokens": (B, S)} -> logits."""
    x = cm.embed_tokens(cfg, params["embed"], batch["tokens"], ctx)
    layers = params["layers"]
    vo = _layer_vo(aux)
    if vo is not None:
        layers = dict(layers, attn_vo=vo)
    x = cm.scan_layers(_layer(cfg, ctx, window), x, layers, ctx)
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
                ctx: ParallelContext, *, window=None, pages=None, aux=None):
    """One-token decode. tokens: (B,), pos: scalar -> (logits (B, V), cache)."""
    x = cm.embed_tokens(cfg, params["embed"], tokens[:, None], ctx)

    def body(x, lp, lc, _):
        xn = cm.apply_norm(cfg, lp["ln1"], x)
        # names the attention ops in the compiled step's op_name metadata
        with jax.named_scope("attention"):
            h, nc = cm.attention_decode(cfg, lp["attn"], xn, lc, pos, ctx,
                                        window=window, pages=pages,
                                        vo=lp.get("attn_vo"))
        x = x + h
        h = cm.mlp_forward(cfg, lp["mlp"], cm.apply_norm(cfg, lp["ln2"], x),
                           ctx, path="layers.mlp")
        return x + h, nc

    layers = params["layers"]
    vo = _layer_vo(aux)
    if vo is not None:
        layers = dict(layers, attn_vo=vo)
    x, new_cache = cm.scan_layers_cache(body, x, layers, cache, ctx)
    x = cm.apply_norm(cfg, params["final_norm"], x)
    logits = cm.lm_head(cfg, params["embed"], x, ctx)
    return logits[:, 0], new_cache
