"""Tensor-parallel invariance: shard_map TP outputs == single-device ref.

XLA locks the host device count at first init, so multi-device tests run
in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(script: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def test_tp_schemes_match_reference():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import reorder
        from repro.core.policy import ExecutionPolicy

        rng = jax.random.PRNGKey(0)
        k1, n1, n2, m = 128, 256, 128, 16
        r = jax.random.split(rng, 4)
        w_up = jax.random.normal(r[0], (k1, n1))
        w_gate = jax.random.normal(r[1], (k1, n1))
        w_down = jax.random.normal(r[2], (n1, n2))
        x = jax.random.normal(r[3], (m, k1))

        for tp, dp in ((2, 4), (4, 2), (8, 1)):
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((dp, tp), ("data", "model"))
            for scheme in reorder.SCHEMES:
                pp = reorder.plan_pair(
                    w_up, w_down, w_gate=w_gate, scheme=scheme,
                    group_size_up=32, group_size_down=32, rng=rng)
                ref = np.asarray(pp.forward(x, activation="silu"))
                with mesh:
                    for coll in ("psum", "psum_scatter"):
                        pol = ExecutionPolicy(scheme=scheme,
                                              collective=coll)
                        y = np.asarray(pp.forward(
                            x, pol, mesh, batch_axes=("data",),
                            activation="silu"))
                        err = np.abs(y - ref).max() / np.abs(ref).max()
                        assert err < 1e-4, (tp, scheme, coll, err)
                        print("OK", tp, scheme, coll)
    """)
    assert out.count("OK") == 18


def test_tp_model_forward_matches_single_device():
    """Full smoke-model forward under a (2, 4) mesh == replicated run."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models.registry import build_model
        from repro.models.common import ParallelContext, REPLICATED

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        for aid in ("granite-3-8b", "rwkv6-3b"):
            cfg = get_smoke_config(aid)
            m = build_model(cfg)
            params = m.init(jax.random.PRNGKey(0))
            batch = m.make_batch(jax.random.PRNGKey(1), 4, 16)
            y_ref = np.asarray(m.forward(params, batch, REPLICATED))
            ctx = ParallelContext(mesh=mesh, batch_axes=("data",))
            with mesh:
                y_tp = np.asarray(jax.jit(
                    lambda p, b: m.forward(p, b, ctx))(params, batch))
            err = np.abs(y_tp - y_ref).max() / (np.abs(y_ref).max() + 1e-6)
            assert err < 2e-2, (aid, err)   # bf16 activations
            print("OK", aid, err)
    """)


def test_multipod_mesh_constructs():
    _run("""
        import jax
        from repro.launch import mesh as mesh_lib
        # 8 host devices: build a small (2, 2, 2) pod/data/model mesh the
        # same way the production (2, 16, 16) one is built.
        from repro.launch.mesh import make_mesh
        m = make_mesh((2, 2, 2), ("pod", "data", "model"),
                          devices=jax.devices()[:8])
        assert m.axis_names == ("pod", "data", "model")
        assert mesh_lib.batch_axes_for(m, 8) == ("pod", "data")
        assert mesh_lib.batch_axes_for(m, 1) == ()
        print("OK")
    """)
