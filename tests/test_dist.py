"""Distributed runtime subsystem (DESIGN.md §11): MeshPlan topology,
per-rank artifact loading, and the decomposed compute-overlapped
collective epilogue (``:overlap``).

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
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CollectiveSpec
from repro.core.policy import ExecutionPolicy
from repro.dist import MeshPlan

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(script: str, devices: int = 8):
    env = dict(os.environ)
    # sequential CPU schedule: the overlap-window tests read the printed
    # instruction order as the program order (roofline.parse_overlap_windows)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        "--xla_cpu_enable_concurrency_optimized_scheduler=false")
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
# :overlap spec flag (no devices needed)
# ---------------------------------------------------------------------------

def test_overlap_flag_parse_round_trips():
    spec = CollectiveSpec.parse("quant-int8:32:overlap")
    assert spec.overlap and not spec.fused
    assert spec.shorthand() == "quant-int8:32:overlap"
    # both flag orders parse; canonical print is :fused then :overlap
    for s in ("quant-int4:32:fused:overlap", "quant-int4:32:overlap:fused"):
        spec = CollectiveSpec.parse(s)
        assert spec.fused and spec.overlap
        assert spec.shorthand() == "quant-int4:32:fused:overlap"
    assert CollectiveSpec.parse(spec.shorthand()) == spec


def test_overlap_flag_rejected_on_non_quant_and_duplicates():
    with pytest.raises(ValueError, match="only applies to quant"):
        CollectiveSpec(name="psum", overlap=True)
    with pytest.raises(ValueError, match="repeat"):
        CollectiveSpec.parse("quant-int8:32:overlap:overlap")


def test_wire_support_reasons():
    """``wire_support`` returns the shape-derived reason ``:fused``
    fallback warnings key on."""
    from repro.core import reorder
    from repro.kernels import dispatch as kdispatch

    r = jax.random.split(jax.random.PRNGKey(0), 3)
    pp = reorder.plan_pair(
        jax.random.normal(r[0], (32, 64)) * 0.1,
        jax.random.normal(r[1], (64, 32)) * 0.1,
        scheme="tp-aware", group_size_up=32, group_size_down=32, rng=r[2])
    q8 = CollectiveSpec.parse("quant-int8:32")
    ok, why = kdispatch.wire_support(pp.down, q8, tp=2)
    assert ok and why == ""
    ok, why = kdispatch.wire_support(pp.down, q8, tp=1)
    assert not ok and "tp=1" in why
    ok, why = kdispatch.wire_support(pp.down, CollectiveSpec(), tp=2)
    assert not ok and "no wire payload" in why


def test_unfusable_warning_dedupes_on_site_and_reason():
    """Satellite regression: the ':fused' fallback warning fires once per
    (site path, reason) — scan re-traces of the same site stay silent,
    but a different reason (or site) still surfaces."""
    import warnings

    from repro.core import reorder, schemes

    r = jax.random.split(jax.random.PRNGKey(1), 3)
    pp = reorder.plan_pair(
        jax.random.normal(r[0], (32, 64)) * 0.1,
        jax.random.normal(r[1], (64, 32)) * 0.1,
        scheme="tp-aware", group_size_up=32, group_size_down=32, rng=r[2])
    schemes._UNFUSABLE_WARNED.clear()
    with pytest.warns(UserWarning) as rec:
        schemes._warn_unfusable("layers.mlp", pp, "tp=1 (no ring to feed)")
    assert len(rec) == 1
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # a repeat would raise
        schemes._warn_unfusable("layers.mlp", pp, "tp=1 (no ring to feed)")
    with pytest.warns(UserWarning):      # same site, new reason
        schemes._warn_unfusable("layers.mlp", pp, "K=64 untileable")
    with pytest.warns(UserWarning):      # new site, old reason
        schemes._warn_unfusable("other.mlp", pp, "tp=1 (no ring to feed)")
    schemes._UNFUSABLE_WARNED.clear()


# ---------------------------------------------------------------------------
# roofline async-window verifier (no devices needed)
# ---------------------------------------------------------------------------

_SCHEDULED_HLO = """\
HloModule m, is_scheduled=true

ENTRY %main (p: f32[8,8]) -> f32[8,8] {
  %p = f32[8,8] parameter(0)
  %cp = f32[8,8] collective-permute(%p), source_target_pairs={{0,1},{1,0}}
  %d = f32[8,8] dot(%p, %p), lhs_contracting_dims={1}
  %use = f32[8,8] add(%cp, %d)
  ROOT %r = f32[8,8] add(%use, %d)
}
"""

_SYNC_HLO = _SCHEDULED_HLO.replace(
    "  %cp = f32[8,8] collective-permute(%p), "
    "source_target_pairs={{0,1},{1,0}}\n"
    "  %d = f32[8,8] dot(%p, %p), lhs_contracting_dims={1}\n",
    "  %d = f32[8,8] dot(%p, %p), lhs_contracting_dims={1}\n"
    "  %cp = f32[8,8] collective-permute(%p), "
    "source_target_pairs={{0,1},{1,0}}\n")


def test_parse_overlap_windows_sees_spanned_gemm():
    from repro.launch import roofline

    rep = roofline.parse_overlap_windows(_SCHEDULED_HLO)
    assert rep["collectives"] == 1
    assert rep["spanning"] == 1
    (w,) = rep["windows"]
    assert w["opcode"] == "collective-permute"
    assert w["gemms"] == 1 and w["window_len"] == 1

    rep = roofline.parse_overlap_windows(_SYNC_HLO)
    assert rep["collectives"] == 1 and rep["spanning"] == 0


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


# ---------------------------------------------------------------------------
# overlapped epilogue: bit-identity + real spanned windows (subprocess)
# ---------------------------------------------------------------------------

def test_overlap_epilogue_bit_identical_and_spans_gemm_all_tp():
    """The acceptance gate: at tp in {2,4,8}, for quant-int8 and
    quant-int4, plain and ``:fused``, the ``:overlap`` epilogue is
    BIT-identical to the synchronous two-phase ring, and the compiled
    schedule actually issues ring ppermutes whose in-flight windows span
    a dequant-GEMM (spanning==0 for every synchronous variant)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import reorder
        from repro.core.policy import ExecutionPolicy
        from repro.launch import roofline

        r = jax.random.split(jax.random.PRNGKey(0), 3)
        pp = reorder.plan_pair(
            jax.random.normal(r[0], (64, 256)) * 0.1,
            jax.random.normal(r[1], (256, 96)) * 0.1,
            scheme="tp-aware", group_size_up=32, group_size_down=32,
            rng=r[2])
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))

        for tp in (2, 4, 8):
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((8 // tp, tp), ("data", "model"))
            for base in ("quant-int8:32", "quant-int4:32",
                         "quant-int8:32:fused", "quant-int4:32:fused"):
                outs, spans = {}, {}
                for suffix in ("", ":overlap"):
                    pol = ExecutionPolicy(collective=base + suffix)
                    fn = jax.jit(lambda xx, p, pol=pol, mesh=mesh:
                                 p.forward(xx, pol, mesh, activation=None))
                    c = fn.lower(x, pp).compile()
                    outs[suffix] = np.asarray(fn(x, pp))
                    spans[suffix] = roofline.parse_overlap_windows(
                        c.as_text())["spanning"]
                assert (outs[""] == outs[":overlap"]).all(), (tp, base)
                assert spans[":overlap"] >= 1, (tp, base, spans)
                assert spans[""] == 0, (tp, base, spans)
                print(f"tp={tp} {base}: identical, "
                      f"spanning={spans[':overlap']}")
        print("OVERLAP_OK")
    """)
    assert "OVERLAP_OK" in out


def test_tuner_marks_overlap_opt_in():
    """``prepare(autotune=True, tune_overlap=True)`` marks quantized pair
    choices ':overlap' (never attn_vo sites); default stays unmarked."""
    from repro.comm import CollectivePlan
    from repro.configs import get_smoke_config
    from repro.plan import compiler

    cfg = get_smoke_config("qwen3-4b").with_quant(
        mode="mlp", scheme="tp-aware", backend="jnp", collective="psum")
    art = compiler.prepare(cfg, tp=2, seed=0, autotune=True,
                           tune_overlap=True,
                           extra_manifest={"smoke": True})
    plan = art.manifest["collective_plan"]
    quant_entries = [s for _, s in plan["entries"] if s.startswith("quant")]
    assert quant_entries, plan
    assert all(s.endswith(":overlap") for s in quant_entries), plan
    assert plan["default"] == "psum"
    for site in art.manifest["collective_tuner"]:
        if site["chosen"].startswith("quant") and site["kind"] == "pair":
            assert site["overlap"] is True
    pol = art.policy()
    assert isinstance(pol.collective, CollectivePlan)
    art.validate(cfg=cfg, policy=pol, tp=2)
