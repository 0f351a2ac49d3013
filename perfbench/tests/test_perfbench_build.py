"""The on-device weight builder gives the served path what ``Model.init``
(and so an artifact) would: the same tree, shapes and dtypes, MLP weights
that dequantize back to the generator's grid values, and on a mesh the
shardings of the model's own ``param_specs``."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import build, weights
from smoke_cells import smoke_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: the configuration as it is, and with Qwen3's per-head q/k norm
CONFIGS = ["granite-3-8b", "qk-norm"]


def _conf(name):
    return smoke_cell("granite-3-8b.decode", qk_norm=name == "qk-norm").conf


def _signature(tree):
    return (jax.tree.structure(tree),
            [(a.shape, a.dtype) for a in jax.tree.leaves(tree)])


@pytest.mark.parametrize("name", CONFIGS)
def test_builder_gives_the_tree_model_init_gives(name):
    from repro.models.common import ParallelContext
    from repro.models.registry import build_model

    conf = _conf(name)
    cfg = build.model_config(conf)
    key = weights.jax_key(2**40 + 1)
    built = build.build_params(cfg, conf, key, ParallelContext())
    want = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    assert _signature(built) == _signature(want)


@pytest.mark.parametrize("name", CONFIGS)
def test_served_mlp_weights_dequantize_to_the_generators_values(name):
    from repro.core.quantization import dequantize
    from repro.models.common import ParallelContext

    conf = _conf(name)
    cfg = build.model_config(conf)
    key = weights.jax_key(7)
    params = build.build_params(cfg, conf, key, ParallelContext())
    raw = weights.layer(conf, key, 1)["mlp"]
    pp = jax.tree.map(lambda a: a[1], params["layers"]["mlp"])
    p1, p2 = np.asarray(pp.p1_up), np.asarray(pp.p2)
    for got, want, rows, cols in (
            (pp.up, raw["w_up"], p1, p2), (pp.gate, raw["w_gate"], p1, p2),
            (pp.down, raw["w_down"], p2, np.arange(raw["w_down"].shape[1]))):
        w = np.zeros(want.shape, np.float32)
        w[rows[:, None], cols[None, :]] = np.asarray(dequantize(got))
        np.testing.assert_allclose(w, np.asarray(want), rtol=1e-6,
                                   atol=1e-9)
    # attention and norms go through untouched
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["attn"]["wq"][1]),
        np.asarray(weights.layer(conf, key, 1)["attn"]["wq"]))


def test_int4_grid_has_sixteen_levels_and_both_ends_in_every_group():
    w = np.asarray(weights.int4_grid(jax.random.PRNGKey(3), (1024, 64)))
    step = (w.max(0) - w.min(0)) / 15
    codes = (w - w.min(0)) / step
    np.testing.assert_allclose(codes, np.round(codes), atol=1e-4)
    assert set(np.unique(np.round(codes))) == set(range(16))
    # zero is a level: 8 steps above the bottom, or 7 where the sign is -1
    zero = np.round(-w.min(0) / step)
    assert set(np.unique(zero)) == {7.0, 8.0}
    for g in np.split(np.round(codes), range(76, 1024, 76), axis=0)[:-1]:
        assert (g.min(0) == 0).all() and (g.max(0) == 15).all()
    assert float(jnp.std(w)) == pytest.approx(1024 ** -0.5, rel=0.1)


def test_int4_grid_feeds_no_common_offset_back():
    # an input with the same value in every row reaches the columns with
    # their half-step offsets: their signs differ, so it leaves no common
    # offset in the output for the next layer to amplify
    k, n = 4096, 4096
    w = np.asarray(weights.int4_grid(jax.random.PRNGKey(5), (k, n)))
    out = np.ones(k, np.float32) @ w
    assert abs(out.mean()) < 0.1 * out.std()
    assert 0.4 < (out > 0).mean() < 0.6


def test_builder_shards_over_a_four_device_mesh_as_param_specs_say():
    script = textwrap.dedent(f"""
        import sys
        sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, "src")!r},
                        {os.path.dirname(os.path.abspath(__file__))!r}]
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from perfbench import build, weights
        from smoke_cells import smoke_cell
        from repro.launch.mesh import make_mesh
        from repro.models.common import ParallelContext
        from repro.models.registry import build_model
        assert len(jax.devices()) == 4
        conf = smoke_cell("granite-3-8b.decode", qk_norm=True).conf
        cfg = build.model_config(conf)
        key = weights.jax_key(11)
        ctx = ParallelContext(mesh=make_mesh((1, 4), ("data", "model")))
        sharded = build.build_params(cfg, conf, key, ctx)
        single = build.build_params(cfg, conf, key, ParallelContext())
        specs = build_model(cfg).param_specs(sharded, ctx)
        want = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        got = jax.tree.leaves(sharded)
        assert len(want) == len(got)
        split = 0
        for a, s in zip(got, want):
            assert a.sharding.is_equivalent_to(NamedSharding(ctx.mesh, s),
                                               a.ndim), (a.shape, s)
            split += "model" in [e for e in s if e]
        assert split >= 8, split
        for a, b in zip(got, jax.tree.leaves(single)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK", split)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.startswith("OK")
