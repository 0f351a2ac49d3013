"""repro.analysis: every rule fires on its seeded violation, and the
clean tree / clean artifact produce zero findings.

The seeded fixtures are the contract that the linters CAN detect what
they claim (a linter that never fires passes every clean-tree check);
the clean runs are the contract that the current tree actually holds
the invariants.
"""

import ast
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.analysis import ast_lint, hlo_lint, manifest_lint
from repro.analysis.findings import (Finding, RULES, has_errors, summarize)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# findings plumbing
# ---------------------------------------------------------------------------

def test_unregistered_rule_refused():
    with pytest.raises(ValueError, match="unregistered rule"):
        Finding("ZZ999", "nope")


def test_severity_defaults_from_catalog():
    f = Finding("HL004", "copy")
    assert f.severity == "warn"
    assert not has_errors([f])
    assert has_errors([f, Finding("AS001", "raw")])


def test_summary_shape():
    s = summarize([Finding("AS004", "m")])
    assert s["counts"]["error"] == 1
    assert s["rules_checked"] == sorted(RULES)
    assert s["findings"][0]["layer"] == "ast"


# ---------------------------------------------------------------------------
# AST rules (seeded violations + clean tree)
# ---------------------------------------------------------------------------

RAW_COLLECTIVE_SRC = """\
import jax

def leak(y):
    return jax.lax.psum(y, "model")
"""


def test_as001_raw_collective_fires():
    fs = ast_lint.lint_source(RAW_COLLECTIVE_SRC, "repro/models/foo.py")
    assert _rules(fs) == {"AS001"}
    assert "foo.py:4" in fs[0].location


def test_as001_allowed_inside_comm_and_dist():
    for rel in ("repro/comm/foo.py", "repro/dist/foo.py"):
        assert ast_lint.lint_source(RAW_COLLECTIVE_SRC, rel) == []


def test_as002_kernel_bypass_fires():
    src = ("from repro.kernels import ops\n"
           "def f(x, ql, p):\n"
           "    return ops.pallas_dequant_matmul_ordered(x, ql, p)\n")
    fs = ast_lint.lint_source(src, "repro/models/foo.py")
    assert _rules(fs) == {"AS002"}
    # the dispatch module itself (imported as kdispatch) is the allowed
    # caller, as is anything under kernels/
    assert ast_lint.lint_source(src, "repro/kernels/foo.py") == []
    ok = "import d as kdispatch\nr = kdispatch.dequant_matmul(1)\n"
    assert ast_lint.lint_source(ok, "repro/models/foo.py") == []


def test_as003_unfrozen_spec_dataclass_fires():
    src = ("import dataclasses\n"
           "@dataclasses.dataclass\n"
           "class LooseSpec:\n"
           "    name: str = 'x'\n")
    fs = ast_lint.lint_source(src, "repro/comm/spec.py")
    assert _rules(fs) == {"AS003"}
    # frozen=True passes; non-spec modules are not checked
    frozen = src.replace("@dataclasses.dataclass",
                         "@dataclasses.dataclass(frozen=True)")
    assert ast_lint.lint_source(frozen, "repro/comm/spec.py") == []
    assert ast_lint.lint_source(src, "repro/models/foo.py") == []


def test_as004_mutable_default_fires():
    fs = ast_lint.lint_source("def f(x, acc=[]):\n    return acc\n",
                              "repro/core/foo.py")
    assert _rules(fs) == {"AS004"}
    fs = ast_lint.lint_source("def f(*, acc={}):\n    return acc\n",
                              "repro/core/foo.py")
    assert _rules(fs) == {"AS004"}


def test_clean_tree_has_zero_ast_findings():
    assert ast_lint.run() == []


def _imports(path):
    """Every module ``path`` imports, at module level or deferred inside
    a function: ``import a.b`` and ``from a.b import c`` give ``a.b``
    and ``a.b.c``."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


@pytest.mark.parametrize("layer,above", [
    ("kernels", "comm"), ("kernels", "dist"),
    ("comm", "dist"), ("comm", "kernels")])
def test_layers_import_downward(layer, above):
    """The kernels know nothing of collectives, and the collectives
    nothing of the kernels or the mesh runtime: no module of a layer
    imports one of ``above``'s, deferred imports included."""
    root = os.path.join(REPO, "src", "repro", layer)
    banned = f"repro.{above}"
    found = [(name, mod)
             for name in sorted(os.listdir(root)) if name.endswith(".py")
             for mod in _imports(os.path.join(root, name))
             if mod == banned or mod.startswith(banned + ".")]
    assert found == []


# ---------------------------------------------------------------------------
# HLO rules (seeded dumps + compiled sweep)
# ---------------------------------------------------------------------------

HLO_WIDEN = """\
HloModule w

ENTRY %main (p0: bf16[8,16]) -> f32[8,16] {
  %p0 = bf16[8,16]{1,0} parameter(0)
  %c = f32[8,16]{1,0} convert(bf16[8,16]{1,0} %p0)
  ROOT %r = f32[8,16]{1,0} add(f32[8,16]{1,0} %c, f32[8,16]{1,0} %c)
}
"""

HLO_DONATED = """\
HloModule m, input_output_alias={ {0}: (0, {}, MAY_ALIAS) }

ENTRY %e (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %copy.3 = f32[8]{0} copy(f32[8]{0} %p.1)
}
"""


def test_hl002_widening_convert_fires():
    fs = hlo_lint.lint_hlo_text(HLO_WIDEN)
    assert _rules(fs) == {"HL002"}
    # a matched round trip (intended wire compression) is clean
    rt = HLO_WIDEN.replace(
        "ROOT %r = f32[8,16]{1,0} add(f32[8,16]{1,0} %c, "
        "f32[8,16]{1,0} %c)",
        "%n = bf16[8,16]{1,0} convert(f32[8,16]{1,0} %c)\n"
        "  ROOT %r = bf16[8,16]{1,0} copy(bf16[8,16]{1,0} %n)")
    assert hlo_lint.lint_hlo_text(rt) == []


def test_hl002_root_dtype_fires():
    fs = hlo_lint.lint_hlo_text(HLO_WIDEN, expect_root_dtype="bf16")
    assert [f.rule for f in fs] == ["HL002", "HL002"]
    assert "root dtype" in fs[-1].message


def test_hl001_byte_mismatch_fires():
    # no collective in the module but the plan predicts wire traffic
    fs = hlo_lint.lint_hlo_text("ENTRY %x () -> f32[2] {\n}\n",
                                expected_bytes={"layers.mlp": 1024.0})
    assert _rules(fs) == {"HL001"}
    assert fs[0].detail["analytic"] == 1024.0


def test_hl004_donated_copy_fires():
    fs = hlo_lint.lint_hlo_text(HLO_DONATED)
    assert _rules(fs) == {"HL004"}
    assert fs[0].severity == "warn"
    assert fs[0].detail["param"] == "p.1"
    # same program without the alias: a copy of a plain param is fine
    assert hlo_lint.lint_hlo_text(
        HLO_DONATED.replace(", input_output_alias={ {0}: (0, {}, "
                            "MAY_ALIAS) }", "")) == []


def test_site_sweep_measured_equals_analytic():
    """The acceptance sweep: at tp {2,4,8} the measured HLO collective
    bytes equal the analytic ``bytes_on_wire`` (rel < 1e-6) for psum /
    psum_scatter / quant-int8 / quant-int4 and no dtype rule fires.  Runs
    in a subprocess: the host device count must be set before jax
    imports."""
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n"
        "from repro.analysis import hlo_lint\n"
        "fs = hlo_lint.run_site_sweep(tps=(2, 4, 8),"
        " specs=hlo_lint.SWEEP_SPECS)\n"
        "assert not fs, [str(f) for f in fs]\n"
        "print('SWEEP-CLEAN')\n")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SWEEP-CLEAN" in r.stdout


# ---------------------------------------------------------------------------
# contract rules (seeded via monkeypatch; clean run at tp=1)
# ---------------------------------------------------------------------------

def test_ct002_nonzero_bytes_fires(monkeypatch):
    from repro.analysis import contracts
    from repro.comm.spec import CollectiveSpec

    monkeypatch.setattr(CollectiveSpec, "bytes_on_wire",
                        lambda self, shape, tp: 42.0)
    fs = contracts.lint_collectives(specs=["psum"], tps=(1,))
    assert "CT002" in _rules(fs)
    assert any("42.0" in f.message for f in fs)


def test_ct002_identity_violation_fires(monkeypatch):
    import jax.numpy as jnp

    from repro.analysis import contracts
    from repro.comm import dispatch as comm_dispatch

    orig = comm_dispatch.apply
    monkeypatch.setattr(
        comm_dispatch, "apply",
        lambda y, axis, spec, policy=None:
            orig(y, axis, spec, policy).astype(jnp.bfloat16))
    fs = contracts.lint_collectives(specs=["psum"], tps=(1,))
    # the float32 stream comes back bfloat16 -> tp=1 is not the identity
    assert "CT002" in _rules(fs)


def test_ct001_dtype_leak_fires_at_tp2():
    """CT001 needs a real multi-device trace; seed the leak in a
    2-device subprocess by wrapping comm.dispatch.apply in a cast."""
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=2'\n"
        "import jax.numpy as jnp\n"
        "from repro.comm import dispatch as comm_dispatch\n"
        "orig = comm_dispatch.apply\n"
        "comm_dispatch.apply = (lambda y, axis, spec, policy=None:\n"
        "    orig(y, axis, spec, policy).astype(jnp.bfloat16))\n"
        "from repro.analysis import contracts\n"
        "fs = contracts.lint_collectives(specs=['psum'], tps=(2,))\n"
        "assert any(f.rule == 'CT001' for f in fs), [str(f) for f in fs]\n"
        "print('CT001-FIRES')\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "CT001-FIRES" in r.stdout


def test_ct003_wrong_cache_geometry_fires(monkeypatch):
    import jax.numpy as jnp

    from repro.analysis import contracts
    from repro.configs import get_smoke_config
    from repro.models import transformer

    cfg = get_smoke_config("qwen3-4b")
    monkeypatch.setattr(contracts, "_family_smoke_cfgs",
                        lambda: {"dense": cfg})
    monkeypatch.setattr(
        transformer, "init_paged_cache",
        lambda cfg, b, n, p, bits=None, dtype=jnp.bfloat16:
            {"k": jnp.zeros((1, 1, n, p, 3, 5), dtype)})
    fs = contracts.lint_families()
    assert "CT003" in _rules(fs)


def test_ct004_wrong_logits_dtype_fires(monkeypatch):
    import jax.numpy as jnp

    from repro.analysis import contracts
    from repro.configs import get_smoke_config
    from repro.models import transformer

    cfg = get_smoke_config("qwen3-4b")
    monkeypatch.setattr(contracts, "_family_smoke_cfgs",
                        lambda: {"dense": cfg})
    orig = transformer.forward
    monkeypatch.setattr(
        transformer, "forward",
        lambda *a, **k: orig(*a, **k).astype(jnp.bfloat16))
    fs = contracts.lint_families()
    assert "CT004" in _rules(fs)


def test_contracts_clean_at_tp1():
    from repro.analysis import contracts

    assert contracts.lint_collectives(tps=(1,)) == []


# ---------------------------------------------------------------------------
# manifest rules (seeded manifests + clean artifact)
# ---------------------------------------------------------------------------

def _plan_manifest(entries, default="psum", pairs=("layers.mlp",)):
    short = "per-layer:" + ",".join(
        f"{p}={s}" for p, s in entries) + f",*={default}"
    man = {
        "format_version": 1,
        "tp": 2,
        "policy": {"collective": short},
        "pairs": [{"path": p, "stacked": [2]} for p in pairs],
        "collective_plan": {"entries": [list(e) for e in entries],
                            "default": default},
    }
    return man


def test_mf001_unreachable_glob_fires():
    man = _plan_manifest([("bogus.path", "quant-int8:128"),
                          ("layers.mlp", "psum")])
    fs = manifest_lint.lint_manifest_dict(man)
    assert _rules(fs) == {"MF001"}


def test_mf002_shadowed_glob_fires():
    man = _plan_manifest([("*mlp", "quant-int8:128"),
                          ("layers.mlp", "psum")])
    fs = manifest_lint.lint_manifest_dict(man)
    assert _rules(fs) == {"MF002"}


def test_mf006_shorthand_echo_mismatch_fires():
    man = _plan_manifest([("layers.mlp", "psum")])
    man["collective_plan"]["entries"] = [["layers.mlp", "cast:bfloat16"]]
    fs = manifest_lint.lint_manifest_dict(man)
    assert "MF006" in _rules(fs)


def test_mf006_unparseable_shorthand_fires():
    man = _plan_manifest([("layers.mlp", "psum")])
    man["policy"]["collective"] = "per-layer:*=psum,layers.mlp=cast"
    fs = manifest_lint.lint_manifest_dict(man)
    assert "MF006" in _rules(fs)


def test_mf005_unconsumed_fold_fires():
    fs = manifest_lint._lint_fold_coverage(
        {"arch_id": "qwen3-4b"},
        {"attn_plans": {"bogus.attn": None}}, location="t")
    assert _rules(fs) == {"MF005"}
    assert fs[0].severity == "error"


def test_mf005_waived_fold_is_info():
    fs = manifest_lint._lint_fold_coverage(
        {"arch_id": "whisper-large-v3"},
        {"attn_plans": {"dec_layers.attn": None,
                        "dec_layers.xattn": None,
                        "enc_layers.attn": None}}, location="t")
    # consumed path silent, the two waived paths reported as info
    assert [f.rule for f in fs] == ["MF005", "MF005"]
    assert {f.severity for f in fs} == {"info"}
    assert not has_errors(fs)


def test_bn001_bad_snapshot_fires(tmp_path):
    p = tmp_path / "BENCH_x.json"
    p.write_text(json.dumps({"bench": "x", "git_sha": "abc"}))
    fs = manifest_lint.lint_bench_snapshots(root=str(tmp_path))
    assert _rules(fs) == {"BN001"}
    good = {"bench": "y", "git_sha": "abc", "created": "t",
            "environment": {"jax": "0", "backend": "cpu",
                            "device_count": 1},
            "config": {}, "metrics": {"m": 1}}
    (tmp_path / "BENCH_y.json").write_text(json.dumps(good))
    fs = manifest_lint.lint_bench_snapshots(
        paths=[str(tmp_path / "BENCH_y.json")])
    assert fs == []
    # bench field must match the filename stem
    good["bench"] = "z"
    (tmp_path / "BENCH_y.json").write_text(json.dumps(good))
    fs = manifest_lint.lint_bench_snapshots(
        paths=[str(tmp_path / "BENCH_y.json")])
    assert _rules(fs) == {"BN001"}


def test_committed_snapshots_are_clean():
    assert manifest_lint.lint_bench_snapshots(root=REPO) == []


# ---------------------------------------------------------------------------
# end-to-end: prepared artifact audits clean; seeded disk violations fire
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def artifact_dir(tmp_path_factory):
    from repro.configs import get_smoke_config
    from repro.plan import compiler

    cfg = get_smoke_config("qwen3-4b")
    out = str(tmp_path_factory.mktemp("art") / "plan")
    art = compiler.prepare(cfg, tp=2, seed=0, autotune=True)
    art.save(out)
    return out


def test_clean_artifact_has_zero_findings(artifact_dir):
    assert manifest_lint.lint_artifact(artifact_dir) == []


def test_mf004_missing_and_stray_rank_files_fire(artifact_dir, tmp_path):
    broken = str(tmp_path / "broken")
    shutil.copytree(artifact_dir, broken)
    os.rename(os.path.join(broken, "rank_01.npz"),
              os.path.join(broken, "rank_05.npz"))
    fs = manifest_lint.lint_artifact(broken)
    msgs = [f.message for f in fs if f.rule == "MF004"]
    assert any("missing rank shard" in m for m in msgs)
    assert any("stray rank shard" in m for m in msgs)


def test_serve_verify_subcommand(artifact_dir, tmp_path):
    """``serve verify --artifact`` exits 0 on a clean artifact and
    writes the findings JSON."""
    out = str(tmp_path / "findings.json")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "verify",
         "--artifact", artifact_dir, "--json", out],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        report = json.load(f)
    assert report["counts"]["error"] == 0
    assert report["rules_checked"] == sorted(RULES)


def test_cli_gate_exits_nonzero_on_violation(tmp_path):
    """The CLI is the CI gate: a tree with a seeded raw collective makes
    ``python -m repro.analysis --ast`` exit 1 with the finding JSON."""
    bad_root = tmp_path / "src" / "repro" / "models"
    bad_root.mkdir(parents=True)
    (bad_root / "bad.py").write_text(RAW_COLLECTIVE_SRC)
    code = (
        "import sys\n"
        "from repro.analysis import ast_lint\n"
        f"fs = ast_lint.run(src_root={str(tmp_path / 'src')!r})\n"
        "assert any(f.rule == 'AS001' for f in fs)\n"
        "sys.exit(1 if fs else 0)\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 1, r.stdout + r.stderr
