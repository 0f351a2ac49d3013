"""The dense decoder family: every layer pre-norm GQA attention with RoPE
(and Qwen3's per-head q/k norm where ``qk_norm`` is set) and a gated
SwiGLU MLP, all in one stack under ``"layers"``.

A family module gives the harness everything that depends on the shape
of a model, found by the ``family`` a configuration file states
(``spec.family``):

* ``model_config(conf)``: the program's ``ModelConfig``; the only
  function of a family that imports the program;
* ``layer_kinds(conf)``: one hashable kind per layer index;
* ``top(conf, key)``: the seeded embedding, LM head and final norm, under
  ``embed.embedding``, ``embed.lm_head`` and ``final_norm.scale``, which
  the plain reference reads;
* ``layer(conf, key, index, kind)``: the seeded raw weights of layer
  ``index`` (traced where one program makes every layer of a kind);
* ``abstract_params(cfg, conf, key)`` and ``fill(cfg, conf, key, ctx)``:
  the parameter tree the program's model takes, as shapes and built on
  the device (``build.fill``: one jitted call per layer into each stack);
* ``reference_layer(conf, kind, low, x, w)``: the plain float32 layer, in
  float8 where ``low`` (``reference.Reference`` jits one per kind);
* ``matmul_params(conf)``: the weights one token multiplies through;
  ``attention_layers(conf)``: ``(layers, heads, head_dim, window)`` groups
  whose keys a token attends over (``window`` None: every key before it);
  ``mlp_gemm_shapes(conf, tp)``: ``(k, n, group_size)`` of the dense-MLP
  dequant-GEMMs one layer runs on one chip.  These counts import nothing
  of the program, so a change to it cannot change how its work is
  counted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import build, weights
from perfbench.reference import _mm, _rms, _rope

KIND = "dense"


def model_config(conf: dict):
    from repro.configs.base import ModelConfig, QuantConfig

    q = conf["quantization"]
    return ModelConfig(
        arch_id=conf["name"], family="dense", source=conf["source"],
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qk_norm=conf["qk_norm"], rope_theta=float(conf["rope_theta"]),
        norm_eps=conf["rms_norm_eps"], activation=conf["hidden_act"],
        mlp_gated=True, dtype=q["activation_dtype"],
        quant=QuantConfig(mode="mlp", scheme=q["scheme"],
                          group_size=q["group_size"],
                          act_order=q["act_order"], tp_groups=q["tp_groups"],
                          compute_dtype=q["compute_dtype"],
                          collective=q["collective"]))


def layer_kinds(conf: dict) -> tuple:
    return (KIND,) * conf["num_hidden_layers"]


# ---------------------------------------------------------------------------
# seeded weights and the parameter tree
# ---------------------------------------------------------------------------

def top(conf: dict, key) -> dict:
    """Embedding, LM head and final norm."""
    kt = jax.random.fold_in(key, 0)
    ke, kh, kn = jax.random.split(kt, 3)
    d, v = conf["hidden_size"], conf["vocab_size"]
    emb = weights._normal(ke, (v, d), d ** -0.5)
    head = emb.T if conf["tie_word_embeddings"] else weights._normal(
        kh, (d, v), d ** -0.5)
    return {"embed": {"embedding": emb, "lm_head": head},
            "final_norm": {"scale": weights._norm_scale(kn, (d,))}}


def layer(conf: dict, key, index, kind) -> dict:
    """Layer ``index`` (may be traced): norms, attention, raw MLP."""
    k = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1),
                                            index), 10)
    d, ff, hd = conf["hidden_size"], conf["intermediate_size"], \
        conf["head_dim"]
    q = conf["num_attention_heads"] * hd
    kv = conf["num_key_value_heads"] * hd
    attn = {"wq": weights._normal(k[0], (d, q), d ** -0.5),
            "wk": weights._normal(k[1], (d, kv), d ** -0.5),
            "wv": weights._normal(k[2], (d, kv), d ** -0.5),
            "wo": weights._normal(k[3], (q, d), q ** -0.5)}
    if conf["qk_norm"]:
        attn["q_norm"] = weights._norm_scale(k[4], (hd,))
        attn["k_norm"] = weights._norm_scale(k[5], (hd,))
    return {"ln1": {"scale": weights._norm_scale(k[6], (d,))},
            "attn": attn,
            "ln2": {"scale": weights._norm_scale(k[7], (d,))},
            "mlp": {"w_up": weights.int4_grid(k[8], (d, ff)),
                    "w_gate": weights.int4_grid(jax.random.fold_in(k[8], 1),
                                                (d, ff)),
                    "w_down": weights.int4_grid(k[9], (ff, d))}}


def _planned(cfg, conf, key, index):
    return build.plan_layer(cfg, layer(conf, key, index, KIND), key, index)


def abstract_params(cfg, conf: dict, key) -> dict:
    one = jax.eval_shape(functools.partial(_planned, cfg, conf), key, 0)
    top_shape = jax.eval_shape(functools.partial(top, conf), key)
    return dict(top_shape,
                layers=build.stacked(one, conf["num_hidden_layers"]))


def fill(cfg, conf: dict, key, ctx) -> dict:
    return build.fill(cfg, conf, key, ctx, abstract_params(cfg, conf, key),
                      {"layers": (functools.partial(_planned, cfg, conf),
                                  range(conf["num_hidden_layers"]))})


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def reference_layer(conf: dict, kind, low, x, w):
    b, s, _ = x.shape
    eps, hd = conf["rms_norm_eps"], conf["head_dim"]
    nh, nkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    a = w["attn"]
    h = _rms(x, w["ln1"]["scale"], eps)
    q = _mm("bsd,dn->bsn", h, a["wq"], low).reshape(b, s, nh, hd)
    k = _mm("bsd,dn->bsn", h, a["wk"], low).reshape(b, s, nkv, hd)
    v = _mm("bsd,dn->bsn", h, a["wv"], low).reshape(b, s, nkv, hd)
    if conf["qk_norm"]:
        q = _rms(q, a["q_norm"], eps)
        k = _rms(k, a["k_norm"], eps)
    q, k = _rope(q, conf["rope_theta"]), _rope(k, conf["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=2)
    v = jnp.repeat(v, nh // nkv, axis=2)
    scores = _mm("bshd,bthd->bhst", q, k, low) / hd ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = _mm("bhst,bthd->bshd", p, v, low).reshape(b, s, nh * hd)
    x = x + _mm("bsn,nd->bsd", o, a["wo"], low)
    m = w["mlp"]
    h = _rms(x, w["ln2"]["scale"], eps)
    g = _mm("bsd,df->bsf", h, m["w_gate"], low)
    u = _mm("bsd,df->bsf", h, m["w_up"], low)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"], low)


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------

def matmul_params(conf: dict) -> int:
    """Attention projections, the gated MLP and the LM head, over every
    layer."""
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    hd = conf["head_dim"]
    q = conf["num_attention_heads"] * hd
    kv = conf["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return conf["num_hidden_layers"] * per_layer + d * conf["vocab_size"]


def attention_layers(conf: dict) -> list:
    return [(conf["num_hidden_layers"], conf["num_attention_heads"],
             conf["head_dim"], None)]


def mlp_gemm_shapes(conf: dict, tp: int = 1) -> list:
    """Gate, up and down."""
    d, ff = conf["hidden_size"], conf["intermediate_size"]
    q = conf["quantization"]
    return [(d, ff // tp, q["group_size_up"]),
            (d, ff // tp, q["group_size_up"]),
            (ff // tp, d, q["group_size_down"])]
