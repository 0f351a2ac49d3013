"""Distributed runtime subsystem (DESIGN.md §11): MeshPlan topology and
per-rank artifact loading.

Multi-device cases run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (XLA locks the
host device count at first backend use, so the parent process can't
flip it per-test).
"""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core.policy import ExecutionPolicy
from repro.dist import MeshPlan

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(script: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


# ---------------------------------------------------------------------------
# MeshPlan (no devices needed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("short", ["dp1xtp1", "dp2xtp4", "dp4xtp2xep2"])
def test_mesh_plan_shorthand_round_trips(short):
    plan = MeshPlan.parse(short)
    assert plan.shorthand() == short
    assert MeshPlan.parse(plan.shorthand()) == plan
    # parse is idempotent on plans, and None is the single-device default
    assert MeshPlan.parse(plan) is plan
    assert MeshPlan.parse(None) == MeshPlan(dp=1, tp=1)


def test_mesh_plan_parse_is_order_insensitive_print_is_canonical():
    assert MeshPlan.parse("tp4xdp2") == MeshPlan(dp=2, tp=4)
    assert MeshPlan.parse("tp4xdp2").shorthand() == "dp2xtp4"
    assert MeshPlan.parse("ep2xtp2xdp4") == MeshPlan(dp=4, tp=2, ep=2)


@pytest.mark.parametrize("bad,match", [
    ("dp2xdp4", "repeats"),
    ("dp2", "both dp and tp"),
    ("tp0xdp2", "positive int"),
    ("banana", "unknown mesh spec"),
    ("dp2xtp4xep3", "must divide"),
])
def test_mesh_plan_rejects_malformed_specs(bad, match):
    with pytest.raises(ValueError, match=match):
        MeshPlan.parse(bad)


def test_mesh_plan_geometry_and_policy_field():
    plan = MeshPlan(dp=2, tp=4)
    assert plan.size == 8
    pol = ExecutionPolicy(mesh="dp2xtp4")
    assert pol.mesh == plan
    hash(pol)  # stays jit-static-safe with the new field
    assert ExecutionPolicy().mesh == MeshPlan()
    with pytest.raises(ValueError, match="positive int"):
        MeshPlan(dp=0, tp=2)


def test_single_device_mesh_local_ranks():
    from repro.dist import local_model_ranks

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    assert local_model_ranks(mesh) == (0,)
    assert MeshPlan(dp=1, tp=1).local_model_ranks(mesh) == (0,)


# ---------------------------------------------------------------------------
# per-rank loader (subprocess: 8 host devices)
# ---------------------------------------------------------------------------

def test_per_rank_loader_shards_match_rank_files_bit_exact():
    """``load_for_mesh`` on a dp4xtp2 mesh: every addressable device
    shard of every split leaf is byte-identical to that model-rank's
    ``rank_NN.npz`` contents, the byte ledger accounts exactly for the
    files read, and a forward through the per-rank params matches the
    host-reassembled ``DeploymentArtifact.load`` path bit-for-bit."""
    out = _run("""
        import os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.core.policy import ExecutionPolicy
        from repro.dist import MeshPlan
        from repro.models.common import ParallelContext
        from repro.models.registry import build_model
        from repro.plan import DeploymentArtifact, compiler
        from repro.train import checkpoint

        cfg = get_smoke_config("qwen3-4b").with_quant(
            mode="mlp", scheme="tp-aware", backend="jnp",
            collective="quant-int8:32")
        policy = ExecutionPolicy.from_config(cfg).with_(
            mesh=MeshPlan(dp=1, tp=2))
        art = compiler.prepare(cfg, tp=2, seed=0, policy=policy,
                               extra_manifest={"smoke": True})
        d = tempfile.mkdtemp()
        art.save(d)
        assert art.manifest["policy"]["mesh"] == "dp1xtp2"

        mesh = MeshPlan.parse("dp4xtp2").build_mesh()
        art2 = DeploymentArtifact.load_for_mesh(d, mesh)
        st = art2.load_stats
        assert st.ranks == (0, 1)          # single process owns all ranks
        assert st.file_bytes_loaded == st.file_bytes_total > 0
        assert not art2.rank_params        # no host-side rank pytrees

        flats = {r: checkpoint.flatten_keys(checkpoint.load(
                     os.path.join(d, f"rank_{r:02d}.npz")))
                 for r in (0, 1)}
        coord = {dev.id: int(idx[-1]) for idx, dev
                 in np.ndenumerate(np.asarray(mesh.devices, dtype=object))}
        gf = checkpoint.flatten_keys(art2.params())
        shard_dims = art2.manifest["leaf_shards"]
        checked = 0
        for key, arr in gf.items():
            dim = shard_dims.get(key)
            for sh in arr.addressable_shards:
                j = coord[sh.device.id]
                want = flats[j][key] if dim is not None else flats[0][key]
                np.testing.assert_array_equal(np.asarray(sh.data),
                                              np.asarray(want))
                checked += 1
        assert checked == 8 * len(gf)      # every leaf on every device

        # the ledger counts exactly the leaves of the two files read
        want_bytes = sum(int(np.asarray(v).nbytes)
                         for f in flats.values() for v in f.values())
        assert st.bytes_loaded == want_bytes

        # forward bit-identity: per-rank assembled vs host-reassembled
        art3 = DeploymentArtifact.load(d)
        model = build_model(cfg)
        ctx = ParallelContext(mesh=mesh, batch_axes=("data",),
                              policy=art2.policy())
        tok = (np.arange(8, dtype=np.int32).reshape(4, 2)
               % cfg.vocab_size)
        f = jax.jit(lambda pr, t: model.forward(pr, {"tokens": t}, ctx))
        outg = np.asarray(f(art2.params(), tok))
        outh = np.asarray(f(art3.params(), tok))
        assert (outg == outh).all()
        print("LOADER_OK")
    """)
    assert "LOADER_OK" in out


def test_mesh_shell_artifact_guards():
    """A manifest-only artifact (mesh mode) refuses the host-global
    accessors instead of silently serving nothing."""
    from repro.plan import DeploymentArtifact

    shell = DeploymentArtifact(manifest={"tp": 2, "leaf_shards": {}})
    with pytest.raises(ValueError, match="no rank pytrees"):
        shell.params()
    with pytest.raises(ValueError, match="cannot re-save"):
        shell.save("/tmp/should-not-exist")
