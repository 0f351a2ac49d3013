"""Compile the served path for a described TPU v5e chip (no chip needed).

The TPU compiler is installed next to the CPU backend, so the kernels of
the main path and the whole decode step compile here for a ``v5e:2x2``
topology that is described, not attached.  That catches what the Pallas
interpreter cannot: blocks off the (8, 128) grid, casts Mosaic lacks, too
much VMEM, a step that does not fit the chip's HBM.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library at a time, and every test worker
imports this file.  Keep these compiles in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: HBM of one v5e chip as its compiler reports it (15.75 GiB).
V5E_HBM_BYTES = int(15.75 * 2**30)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


# qwen3-4b's MLP GEMMs under the tp-aware plan: the up/gate GEMM
# (K=d_model, gs 128) and the down GEMM (K=d_ff, gs 76 = the group size
# that tiles d_ff / tp_groups), whole and as one rank's shard at tp 4.
@pytest.mark.parametrize("k,n,gs", [(2560, 9728, 128), (9728, 2560, 76),
                                    (2432, 2560, 76)],
                         ids=["up", "down", "down-tp4"])
@pytest.mark.parametrize("m", [8, 128])
def test_ordered_gemm_compiles_at_qwen3_4b_widths(one_chip, m, k, n, gs):
    from repro.kernels import dequant_matmul as dk

    g = k // gs
    args = _on(one_chip, (jax.ShapeDtypeStruct((m, k), jnp.float32),
                          jax.ShapeDtypeStruct((k // 8, n), jnp.uint32),
                          jax.ShapeDtypeStruct((g, n), jnp.float32),
                          jax.ShapeDtypeStruct((g, n), jnp.float32)))
    fn = jax.jit(lambda x, q, s, z: dk.dequant_matmul_ordered(
        x, q, s, z, group_size=gs, block_m=m, interpret=False))
    hlo = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_qwen3_4b_decode_step_compiles_for_one_chip(one_chip):
    """The full-width decode step the server runs (batch 8, max_seq 1024,
    int4 tp-aware MLPs on the Pallas backend, compiled) fits one chip."""
    from repro.configs import get_config
    from repro.core.policy import ExecutionPolicy, KernelTiling
    from repro.models.common import ParallelContext
    from repro.models.registry import build_model

    cfg = get_config("qwen3-4b").with_quant(mode="mlp", scheme="tp-aware")
    model = build_model(cfg)
    policy = ExecutionPolicy(scheme="tp-aware", backend="pallas",
                             tiling=KernelTiling(interpret=False))
    ctx = ParallelContext(policy=policy)
    batch, max_seq = 8, 1024
    params = _on(one_chip, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: model.init_cache(batch, max_seq)))
    tokens, pos = _on(one_chip, (
        jax.ShapeDtypeStruct((batch,), jnp.int32),
        jax.ShapeDtypeStruct((batch,), jnp.int32)))

    def decode(p, c, t, q):
        return model.decode_step(p, c, t, q, ctx)

    compiled = jax.jit(decode, donate_argnums=1).lower(
        params, cache, tokens, pos).compile()
    # gate, up and down of the scanned layer body
    assert compiled.as_text().count("tpu_custom_call") >= 3
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


def test_mistral_large_stage_decode_step_compiles_for_four_chips(topo):
    """One tp=4 pipeline stage of Mistral-Large-2407 (22 of 88 layers at
    full width) on a (1, 4) mesh of the described v5e:2x2: the decode step
    fits each chip, and its layer loop holds two all-reduces (wo and the
    MLP epilogue) and no other collective."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.core.policy import ExecutionPolicy, KernelTiling
    from repro.launch.mesh import make_mesh
    from repro.models.common import ParallelContext
    from repro.models.registry import build_model
    from repro.runtime.serve import Engine

    cfg = get_config("mistral-large-123b").with_(num_layers=22).with_quant(
        mode="mlp", scheme="tp-aware")
    model = build_model(cfg)
    mesh = make_mesh((1, 4), ("data", "model"), topo.devices)
    policy = ExecutionPolicy(scheme="tp-aware", backend="pallas",
                             tiling=KernelTiling(interpret=False))
    ctx = ParallelContext(mesh=mesh, policy=policy)
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def placed(tree, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs, is_leaf=lambda x: isinstance(x, P))

    params = placed(abstract, model.param_specs(abstract, ctx))
    engine = Engine(model=model, params=params, ctx=ctx, max_seq=512)
    cache = placed(jax.eval_shape(lambda: model.init_cache(8, 512)),
                   model.cache_specs(ctx))
    lanes = jax.ShapeDtypeStruct((8,), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    compiled = engine._decode.lower(params, cache, lanes, lanes).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
    text = compiled.as_text()
    body = re.search(r"while\(.*?\bbody=(%[\w.\-]+)", text)[1]
    comp = re.search(r"^" + re.escape(body) + r" .*?^}", text,
                     re.M | re.S)[0]
    found = re.findall(r"\s(all-reduce|all-gather|all-to-all|reduce-scatter"
                       r"|collective-permute)(?:-start)?\(", comp)
    assert found == ["all-reduce", "all-reduce"], found
    assert comp.count("tpu_custom_call") >= 3


# the served vocabularies: granite-3-8b's on one chip, Mistral-Large's
# over a (1, 4) mesh with the logits split along it
@pytest.mark.parametrize("vocab,chips", [(49155, 1), (32768, 4)],
                         ids=["granite-one-chip", "mistral-tp4"])
def test_sample_step_compiles_at_the_served_vocab(topo, vocab, chips):
    """The scheduler's per-step sampler compiles for the described chip
    at batch 8; under a mesh its tokens and keys come out replicated."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.runtime import sampling

    mesh = Mesh(np.array(topo.devices[:chips]).reshape(1, chips),
                ("data", "model"))
    rep = NamedSharding(mesh, P())

    def arg(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    step = sampling.compile_sample_step(rep if chips > 1 else None)
    compiled = step.lower(
        arg((8, 2), jnp.uint32), arg((8,), jnp.bool_),
        arg((8, vocab), jnp.float32, P(None, "model")),
        arg((8,), jnp.float32), arg((8,), jnp.float32),
        arg((8,), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**26
    if chips > 1:
        assert all(s.is_fully_replicated for s in compiled.output_shardings)
