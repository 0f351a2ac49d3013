"""Llama-3.2-Vision backbone: decoder with gated cross-attention image
layers every ``cross_attn_every`` layers (assignment: 100L = 80 self + 20
cross).  The ViT/SigLIP vision encoder + projector is a STUB:
``batch["patches"]`` carries precomputed patch embeddings
(B, vision_tokens, d_model).

Structure: scan over ``n_super = L / cross_attn_every`` superblocks, each =
(cross_attn_every - 1) self layers (inner scan) + 1 gated cross layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import common as cm
from repro.models import transformer as tfm
from repro.models.common import ParallelContext


#: decoder self-attention consumes precompiled V->O folds (artifact aux
#: plans) — the registry only forwards ``aux`` to modules declaring it.
#: Cross-attention layers are NOT folded into the runtime path: their
#: K/V is patch-derived and precomputed (``precompute_cross``), so the
#: fold's within-head-block permutation has nothing to commute with.
SUPPORTS_ATTN_VO = True

#: dotted path ``stage_fold_attention`` records the stacked
#: (n_super, n_self) decoder self-attention dicts under.
ATTN_VO_PATH = "super.self.attn"

#: folds the plan compiler produces but this runtime deliberately does
#: NOT consume, with the reason — ``repro.analysis`` (MF005) reports
#: these as waived instead of flagging them as dead aux weight.
ATTN_VO_WAIVED = {
    "super.cross.xattn": (
        "cross-attention K/V is precomputed from raw wv at prefill "
        "(precompute_cross); a folded V would disagree with the cached "
        "values"),
}


def _self_vo(aux):
    """The stacked (ns, nself) V->O ``PlannedPair`` for the decoder self
    layers, if the artifact carried one (scanned alongside the params:
    the outer scan peels ns, the inner scan peels nself)."""
    if not aux:
        return None
    return (aux.get("attn_plans") or {}).get(ATTN_VO_PATH)


def _n_super(cfg: ModelConfig):
    assert cfg.num_layers % cfg.cross_attn_every == 0
    return cfg.num_layers // cfg.cross_attn_every, cfg.cross_attn_every - 1


def _self_layer_params(cfg, lr):
    lrs = cm.split_rngs(lr, ["attn", "mlp"])
    return {
        "ln1": cm.norm_params(cfg),
        "attn": cm.attention_params(cfg, lrs["attn"]),
        "ln2": cm.norm_params(cfg),
        "mlp": cm.mlp_params(cfg, lrs["mlp"]),
    }


def _cross_layer_params(cfg, lr):
    lrs = cm.split_rngs(lr, ["xattn", "mlp"])
    return {
        "ln1": cm.norm_params(cfg),
        "xattn": cm.attention_params(cfg, lrs["xattn"]),
        "ln2": cm.norm_params(cfg),
        "mlp": cm.mlp_params(cfg, lrs["mlp"]),
        "gate_attn": jnp.zeros(()),
        "gate_mlp": jnp.zeros(()),
    }


def init_params(cfg: ModelConfig, rng):
    ns, nself = _n_super(cfg)
    r = cm.split_rngs(rng, ["embed", "super", "norm"])

    def make_super(lr):
        lrs = cm.split_rngs(lr, ["self", "cross"])
        return {
            "self": cm.stack_layer_params(
                lambda slr: _self_layer_params(cfg, slr), lrs["self"], nself),
            "cross": _cross_layer_params(cfg, lrs["cross"]),
        }

    return {
        "embed": cm.embed_params(cfg, r["embed"]),
        "super": cm.stack_layer_params(make_super, r["super"], ns),
        "final_norm": cm.norm_params(cfg),
    }


def param_specs(cfg: ModelConfig, params, ctx: ParallelContext):
    axis = ctx.model_axis
    norm2 = {"scale": P(None, None, None)}  # (ns, nself, d)
    norm1 = {"scale": P(None, None)}

    def attn_specs(stack_dims):
        base = cm.attention_specs(cfg, axis, stacked=False)
        return jax.tree.map(
            lambda s: P(*((None,) * stack_dims), *s), base,
            is_leaf=lambda x: isinstance(x, P))

    sup = params["super"]
    self_mlp = jax.tree.map(
        lambda s: P(None, *s) if isinstance(s, P) else s,
        cm.mlp_specs(cfg, sup["self"]["mlp"], axis),
        is_leaf=lambda x: isinstance(x, P))
    return {
        "embed": cm.embed_specs(cfg, axis, ctx.axis_size(axis)),
        "super": {
            "self": {"ln1": dict(norm2), "attn": attn_specs(2),
                     "ln2": dict(norm2), "mlp": self_mlp},
            "cross": {"ln1": dict(norm1), "xattn": attn_specs(1),
                      "ln2": dict(norm1),
                      "mlp": cm.mlp_specs(cfg, sup["cross"]["mlp"], axis),
                      "gate_attn": P(None), "gate_mlp": P(None)},
        },
        "final_norm": {"scale": P(None)},
    }


def _cross_layer_fwd(cfg, ctx):
    def body(x, lp, patches):
        h = cm.attention_forward(cfg, lp["xattn"],
                                 cm.apply_norm(cfg, lp["ln1"], x), ctx,
                                 kv_x=patches, causal=False)
        x = x + jnp.tanh(lp["gate_attn"]) * h
        h = cm.mlp_forward(cfg, lp["mlp"], cm.apply_norm(cfg, lp["ln2"], x),
                           ctx, path="super.cross.mlp")
        return x + jnp.tanh(lp["gate_mlp"]) * h
    return body


def forward(cfg: ModelConfig, params, batch, ctx: ParallelContext, *,
            window=None, aux=None):
    """batch: {"tokens": (B, S), "patches": (B, vision_tokens, d)}."""
    patches = batch["patches"]
    x = cm.embed_tokens(cfg, params["embed"], batch["tokens"], ctx)
    self_fwd = tfm._layer(cfg, ctx, window,
                          mlp_path="super.self.mlp")
    cross_fwd = _cross_layer_fwd(cfg, ctx)

    def super_body(x, sp, _):
        x = cm.scan_layers(self_fwd, x, sp["self"], ctx)
        return cross_fwd(x, sp["cross"], patches)

    sup = params["super"]
    vo = _self_vo(aux)
    if vo is not None:
        # rides the scans next to the self-layer params; tfm._layer's
        # body picks it up as lp["attn_vo"]
        sup = dict(sup, self=dict(sup["self"], attn_vo=vo))
    x = cm.scan_layers(super_body, x, sup, ctx)
    x = cm.apply_norm(cfg, params["final_norm"], x)
    return cm.lm_head(cfg, params["embed"], x, ctx)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, *, window=None,
               dtype=jnp.bfloat16):
    ns, nself = _n_super(cfg)
    kvh, _, _ = cm.head_grid(cfg)
    hd = cfg.head_dim
    cap = min(seq_len, window) if window else seq_len
    return {
        "self": {"k": jnp.zeros((ns, nself, batch, cap, kvh, hd), dtype),
                 "v": jnp.zeros((ns, nself, batch, cap, kvh, hd), dtype)},
        "cross_k": jnp.zeros((ns, batch, cfg.vision_tokens, kvh, hd), dtype),
        "cross_v": jnp.zeros((ns, batch, cfg.vision_tokens, kvh, hd), dtype),
    }


def init_paged_cache(cfg: ModelConfig, batch: int, n_pages: int,
                     page_size: int, *, bits=None, dtype=jnp.bfloat16):
    """Paged self-attn pool with (n_super, n_self) layer lead dims;
    cross K/V stays dense (vision prefix fixed per slot)."""
    from repro.cache import paged as paged_pool
    ns, nself = _n_super(cfg)
    kvh, _, _ = cm.head_grid(cfg)
    hd = cfg.head_dim
    return {
        "self": paged_pool.init_pool((ns, nself), n_pages, page_size, kvh,
                                     hd, dtype=dtype, bits=bits),
        "cross_k": jnp.zeros((ns, batch, cfg.vision_tokens, kvh, hd), dtype),
        "cross_v": jnp.zeros((ns, batch, cfg.vision_tokens, kvh, hd), dtype),
    }


def cache_specs(cfg: ModelConfig, ctx: ParallelContext):
    xs = P(None, ctx.batch_spec, None, None, None)
    return {"self": cm.kv_cache_specs(cfg, ctx, lead=2),
            "cross_k": xs, "cross_v": xs}


def precompute_cross(cfg: ModelConfig, params, patches, ctx: ParallelContext):
    """Fill cross K/V from patch embeddings (prefill-time, vision fixed)."""
    b, t, _ = patches.shape
    kvh, _, _ = cm.head_grid(cfg)
    hd = cfg.head_dim

    def per_super(sp):
        xa = sp["cross"]["xattn"]
        k = cm.project(patches, xa["wk"]).reshape(b, t, kvh, hd)
        v = cm.project(patches, xa["wv"]).reshape(b, t, kvh, hd)
        return k, v

    return jax.vmap(per_super)(params["super"])


def decode_step(cfg: ModelConfig, params, cache, tokens, pos,
                ctx: ParallelContext, *, window=None, pages=None, aux=None):
    x = cm.embed_tokens(cfg, params["embed"], tokens[:, None], ctx)

    def self_body(x, xs):
        lp, lc = xs
        h, nc = cm.attention_decode(cfg, lp["attn"],
                                    cm.apply_norm(cfg, lp["ln1"], x),
                                    lc, pos, ctx, window=window, pages=pages,
                                    vo=lp.get("attn_vo"))
        x = x + h
        h = cm.mlp_forward(cfg, lp["mlp"], cm.apply_norm(cfg, lp["ln2"], x),
                           ctx, path="super.self.mlp")
        return (x + h).astype(carry_dtype), nc

    def super_body(x, xs):
        sp, (sc, xk, xv) = xs
        x, nsc = jax.lax.scan(self_body, x, (sp["self"], sc))
        cp = sp["cross"]
        b = x.shape[0]
        q = cm.project(cm.apply_norm(cfg, cp["ln1"], x),
                       cp["xattn"]["wq"]).reshape(
            b, 1, cm.head_grid(cfg)[2], cfg.head_dim)
        out = cm._sdpa(cfg, ctx, q, xk.astype(x.dtype), xv.astype(x.dtype),
                       None)
        x = x + jnp.tanh(cp["gate_attn"]) * cm.project(out,
                                                        cp["xattn"]["wo"])
        h = cm.mlp_forward(cfg, cp["mlp"], cm.apply_norm(cfg, cp["ln2"], x),
                           ctx, path="super.cross.mlp")
        x = x + jnp.tanh(cp["gate_mlp"]) * h
        return x.astype(carry_dtype), nsc

    carry_dtype = x.dtype
    sup = params["super"]
    vo = _self_vo(aux)
    if vo is not None:
        sup = dict(sup, self=dict(sup["self"], attn_vo=vo))
    x, nself = jax.lax.scan(
        super_body, x,
        (sup, (cache["self"],
               cache["cross_k"], cache["cross_v"])))
    x = cm.apply_norm(cfg, params["final_norm"], x)
    logits = cm.lm_head(cfg, params["embed"], x, ctx)
    return logits[:, 0], {"self": nself, "cross_k": cache["cross_k"],
                          "cross_v": cache["cross_v"]}
