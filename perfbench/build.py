"""The system under test, built for one cell.

The configuration's family (``families/<family>.py``) names the program's
config and lays out its parameter tree; the weights are built on the
device, one layer per call of one jitted program (``fill``): the raw
layer is made from the seed and goes through the program's own plan
compiler (``plan.compiler.compile_params``: int4 round-to-nearest in
act-order groups, then the tp-aware layout), and is written in place
into its stacked buffers.  On a mesh the buffers carry the shardings of
the model's own ``param_specs``.  Nothing is read from disk and no
``serve prepare`` runs.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from perfbench import spec


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return spec.family(conf).model_config(conf)


def abstract_params(cfg, conf: dict, key) -> dict:
    """Shapes and dtypes of the planned parameter tree."""
    return spec.family(conf).abstract_params(cfg, conf, key)


def build_params(cfg, conf: dict, key, ctx):
    """The full planned parameter tree on the device(s)."""
    return spec.family(conf).fill(cfg, conf, key, ctx)


def plan_layer(cfg, raw: dict, key, index):
    """A raw layer as the program serves it (leading stack dim 1)."""
    from repro.plan import compiler

    raw = jax.tree.map(lambda a: a[None], raw)
    plan_rng = jax.random.fold_in(jax.random.fold_in(key, 2), index)
    return compiler.compile_params(cfg, {"layers": raw},
                                   rng=plan_rng)["layers"]


def stacked(one, n: int):
    """The shapes of ``n`` layers shaped as ``one`` (leading dim 1)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n,) + a.shape[1:], a.dtype), one)


def fill(cfg, conf: dict, key, ctx, abstract: dict, stacks: dict) -> dict:
    """``abstract`` built on the device(s): the family's ``top`` in one
    jitted call, and each stack ``path -> (planned(key, index), indices)``
    as zeros into which one jitted call per layer writes layer
    ``indices[r]`` at row ``r``.  Every key of ``abstract`` but the stacks
    is ``top``'s."""
    from repro.models.registry import build_model

    shard = None
    if ctx.mesh is not None:
        specs = build_model(cfg).param_specs(abstract, ctx)
        shard = jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))

    def part(keys):
        return None if shard is None else {k: shard[k] for k in keys}

    top = jax.jit(functools.partial(spec.family(conf).top, conf),
                  out_shardings=part(k for k in abstract if k not in stacks)
                  )(key)
    out = dict(top)
    for path, (planned, indices) in stacks.items():
        out[path] = _fill_stack(planned, abstract[path], list(indices), key,
                                None if shard is None else shard[path])
    return out


def _fill_stack(planned, shapes, indices: list, key, shard):
    zeros = jax.jit(lambda: jax.tree.map(
        lambda a: jax.numpy.zeros(a.shape, a.dtype), shapes),
        out_shardings=shard)
    layers = zeros()
    # the row of each layer index, where a stack does not hold 0, 1, ...
    rows = None
    if indices != list(range(len(indices))):
        rows = np.zeros(max(indices) + 1, np.int32)
        rows[indices] = np.arange(len(indices))

    @functools.partial(jax.jit, donate_argnums=0, out_shardings=shard)
    def fill(buf, key, index):
        part = planned(key, index)
        row = index if rows is None else jax.numpy.asarray(rows)[index]
        return jax.tree.map(
            lambda b, p: jax.lax.dynamic_update_slice_in_dim(b, p, row, 0),
            buf, part)

    for i in indices:
        layers = fill(layers, key, i)
    return layers


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
