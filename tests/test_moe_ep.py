"""Explicit-EP MoE dispatch (shard_map + all_to_all) correctness.

The EP path must equal the single-device reference exactly when no token
is capacity-dropped (drop *sets* legitimately differ between global and
per-rank capacity accounting, so the comparison pins capacity high).
"""

import os
import subprocess
import sys
import textwrap

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(script: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def test_moe_ep_matches_reference_no_drops():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models.registry import build_model
        from repro.models.common import ParallelContext, REPLICATED

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        for aid in ("qwen3-moe-235b-a22b", "arctic-480b"):
            cfg = get_smoke_config(aid).with_(capacity_factor=64.0)
            m = build_model(cfg)
            params = m.init(jax.random.PRNGKey(0))
            batch = m.make_batch(jax.random.PRNGKey(1), 4, 16)
            y_ref = np.asarray(
                m.forward(params, batch, REPLICATED).astype(jnp.float32))
            ctx = ParallelContext(mesh=mesh, batch_axes=("data",))
            with mesh:
                y_ep = np.asarray(jax.jit(
                    lambda p, b: m.forward(p, b, ctx))(
                        params, batch).astype(jnp.float32))
            err = np.abs(y_ep - y_ref).max() / (np.abs(y_ref).max() + 1e-6)
            assert err < 5e-3, (aid, err)
            print("OK", aid, err)
    """)
    assert out.count("OK") == 2


def test_moe_ep_emits_all_to_all():
    """The EP path's collective schedule contains the two all_to_alls."""
    out = _run("""
        import jax, jax.numpy as jnp, re
        from repro.configs import get_smoke_config
        from repro.models.registry import build_model
        from repro.models.common import ParallelContext

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke_config("qwen3-moe-235b-a22b")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        batch = m.make_batch(jax.random.PRNGKey(1), 4, 16)
        ctx = ParallelContext(mesh=mesh, batch_axes=("data",))
        with mesh:
            txt = jax.jit(lambda p, b: m.forward(p, b, ctx)).lower(
                params, batch).compile().as_text()
        n = len(re.findall(r" all-to-all(?:-start)?\\(", txt))
        assert n >= 2, f"expected >=2 all-to-alls, found {n}"
        print("OK", n)
    """)
    assert "OK" in out


def test_moe_within_expert_collective_resolves_from_plan():
    """The within-expert epilogue resolves "layers.moe.experts" from a
    per-layer CollectivePlan like every other pair — a compressed
    full-output strategy applies (bounded error), while ``none`` /
    scatter strategies fall back to psum (the EP combine needs every
    rank's complete expert output), bit-identical to the psum plan."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.core.policy import ExecutionPolicy
        from repro.models.registry import build_model
        from repro.models.common import ParallelContext

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke_config("qwen3-moe-235b-a22b").with_(
            capacity_factor=64.0)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        batch = m.make_batch(jax.random.PRNGKey(1), 4, 16)

        def run(coll):
            ctx = ParallelContext(mesh=mesh, batch_axes=("data",),
                                  policy=ExecutionPolicy(collective=coll))
            with mesh:
                return np.asarray(jax.jit(
                    lambda p, b: m.forward(p, b, ctx))(
                        params, batch).astype(jnp.float32))

        y_psum = run("psum")
        y_none = run("per-layer:*.experts=none,*=psum")
        np.testing.assert_array_equal(y_psum, y_none)
        print("OK none-falls-back-to-psum")

        y_q = run("per-layer:*.experts=quant-int8:32,*=psum")
        err = np.abs(y_q - y_psum).max() / (np.abs(y_psum).max() + 1e-6)
        assert 0 < err < 5e-2, err    # compressed wire genuinely applied
        print("OK quantized-within-expert", f"{err:.1e}")
    """)
    assert out.count("OK") == 2
