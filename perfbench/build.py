"""The system under test, built for one cell.

Weights come from ``weights`` on the device, one layer per call of one
jitted program: the raw layer is made from the seed and goes through the
program's own plan compiler (``plan.compiler.compile_params``: int4
round-to-nearest in act-order groups, then the tp-aware layout), and is
written in place into the stacked buffers.  On a mesh the buffers carry
the shardings of the model's own ``param_specs``.  Nothing is read from
disk and no ``serve prepare`` runs.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from perfbench import weights


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
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


def _planned_layer(cfg, conf, key, index):
    """Layer ``index`` as the program serves it (leading stack dim 1)."""
    from repro.plan import compiler

    raw = jax.tree.map(lambda a: a[None], weights.layer(conf, key, index))
    plan_rng = jax.random.fold_in(jax.random.fold_in(key, 2), index)
    return compiler.compile_params(cfg, {"layers": raw},
                                   rng=plan_rng)["layers"]


def build_params(cfg, conf: dict, key, ctx):
    """The full planned parameter tree on the device(s)."""
    from repro.models.registry import build_model

    n = conf["num_hidden_layers"]
    one = jax.eval_shape(functools.partial(_planned_layer, cfg, conf), key, 0)
    stacked = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n,) + a.shape[1:], a.dtype), one)
    top_shape = jax.eval_shape(functools.partial(weights.top, conf), key)
    abstract = dict(top_shape, layers=stacked)
    if ctx.mesh is None:
        shard = None
        top_shard = layer_shard = None
    else:
        specs = build_model(cfg).param_specs(abstract, ctx)
        shard = jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
        layer_shard = shard["layers"]
        top_shard = {k: v for k, v in shard.items() if k != "layers"}

    top = jax.jit(functools.partial(weights.top, conf),
                  out_shardings=top_shard)(key)
    zeros = jax.jit(lambda: jax.tree.map(
        lambda a: jax.numpy.zeros(a.shape, a.dtype), stacked),
        out_shardings=layer_shard)
    layers = zeros()

    @functools.partial(jax.jit, donate_argnums=0, out_shardings=layer_shard)
    def fill(buf, key, index):
        part = _planned_layer(cfg, conf, key, index)
        return jax.tree.map(
            lambda b, p: jax.lax.dynamic_update_slice_in_dim(b, p, index, 0),
            buf, part)

    for i in range(n):
        layers = fill(layers, key, i)
    return dict(top, layers=layers)


def make_loop(cell, params, cfg, ctx, *, seed: int):
    """Engine, continuous scheduler and its serving loop (started)."""
    from repro.models.registry import build_model
    from repro.runtime.sampling import SamplingConfig
    from repro.runtime.scheduler import Scheduler
    from repro.runtime.serve import Engine
    from repro.serving import EngineLoop

    mix = cell.traffic
    engine = Engine(model=build_model(cfg), params=params, ctx=ctx,
                    max_seq=cell.max_seq)
    sched = Scheduler(engine, max_batch=cell.max_batch,
                      prompt_budget=mix["prompt_len"]["max"],
                      scfg=SamplingConfig(temperature=mix["temperature"],
                                          top_k=mix["top_k"]),
                      seed=seed)
    # the wait line must hold the whole offered load (the backlog is
    # measured, not refused), and the cache stays allocated all run
    loop = EngineLoop(sched, queue_capacity=1 << 16, cache_idle=float("inf"))
    return loop.start()
