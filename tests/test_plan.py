"""Plan compiler + DeploymentArtifact: the prepare-once/serve-many path.

Acceptance criteria of the PlanCompiler refactor:

* ``prepare`` (compile_plan -> save) then serve-from-artifact runs WITHOUT
  invoking GPTQ quantization or the layout planner at load time, and its
  logits are bit-identical to the in-memory path for the same
  config/policy/seed,
* checkpoint round-trip of quantized pytrees: ``save`` -> ``load`` ->
  bit-identical ``PlannedPair.forward`` outputs, statics preserved,
* manifest-mismatch rejection: wrong TP degree / policy / config hash.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.policy import ExecutionPolicy
from repro.core.reorder import PlannedPair
from repro.models.common import REPLICATED
from repro.models.registry import build_model
from repro.plan import (DeploymentArtifact, PlanMismatchError, compiler)
from repro.train import checkpoint


def _smoke_cfg(arch="qwen3-4b"):
    return get_smoke_config(arch)


def _prepare(cfg, tp=2, seed=0):
    """The exact pipeline ``launch.serve prepare`` runs."""
    return compiler.prepare(cfg, tp=tp, seed=seed,
                            extra_manifest={"smoke": True})


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.asarray(x).dtype == np.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# checkpoint round-trip of quantized pytrees
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("host", [False, True])
def test_checkpoint_quantized_roundtrip(tmp_path, host):
    """save -> template-free load -> bit-identical PlannedPair.forward,
    statics (scheme / group_size / kind) included; ``host=True`` keeps
    the leaves numpy arrays in host memory."""
    from repro.core import reorder

    rng = jax.random.PRNGKey(0)
    r = jax.random.split(rng, 3)
    pp = reorder.plan_pair(
        jax.random.normal(r[0], (64, 128)),
        jax.random.normal(r[1], (128, 64)),
        w_gate=jax.random.normal(r[2], (64, 128)),
        scheme="tp-aware", group_size_up=32, group_size_down=32, rng=rng)
    tree = {"layers": {"mlp": pp}, "scale": jnp.ones((4,))}
    path = checkpoint.save(str(tmp_path / "plan"), tree)
    loaded = checkpoint.load(path, host=host)
    leaf_type = np.ndarray if host else jax.Array
    assert all(isinstance(x, leaf_type) for x in jax.tree.leaves(loaded))

    lpp = loaded["layers"]["mlp"]
    assert isinstance(lpp, PlannedPair)
    assert lpp.scheme == "tp-aware"
    assert lpp.up.kind == "ordered" and lpp.up.group_size == 32
    assert lpp.up.qweight.dtype == jnp.uint32
    _assert_trees_equal(tree, loaded)

    x = jax.random.normal(r[0], (4, 64))
    np.testing.assert_array_equal(
        np.asarray(pp.forward(x, activation="silu")),
        np.asarray(lpp.forward(x, activation="silu")))


def test_checkpoint_naive_layout_roundtrip(tmp_path):
    """The g_idx (naive) layout keeps its unordered metadata through disk."""
    from repro.core import reorder

    rng = jax.random.PRNGKey(1)
    r = jax.random.split(rng, 2)
    pp = reorder.plan_pair(
        jax.random.normal(r[0], (64, 128)),
        jax.random.normal(r[1], (128, 64)),
        scheme="naive-actorder", group_size_up=32, group_size_down=32,
        rng=rng)
    path = checkpoint.save(str(tmp_path / "naive"), pp)
    lpp = checkpoint.load(path)
    assert lpp.scheme == "naive-actorder"
    assert lpp.up.kind == "naive" and lpp.up.g_idx is not None
    assert lpp.p2 is None
    _assert_trees_equal(pp, lpp)


def test_checkpoint_load_rejects_legacy_files(tmp_path):
    """npz files without the embedded schema demand the template path."""
    p = tmp_path / "legacy.npz"
    np.savez(p, **{"a": np.ones(3)})
    with pytest.raises(ValueError, match="no embedded tree schema"):
        checkpoint.load(str(p))
    # restore() still works on them
    out = checkpoint.restore(str(p), {"a": jnp.zeros(3)})
    np.testing.assert_array_equal(np.asarray(out["a"]), np.ones(3))


# ---------------------------------------------------------------------------
# compiler stages
# ---------------------------------------------------------------------------

def test_model_init_is_the_compiler():
    """``Model.init`` == raw init + compile_params — one pipeline."""
    cfg = _smoke_cfg()
    key = jax.random.PRNGKey(0)
    m = build_model(cfg)
    planned = m.init(key)
    by_hand = compiler.compile_params(
        cfg, m.init_raw(key),
        rng=jax.random.fold_in(key, compiler.PLAN_RNG_STREAM))
    _assert_trees_equal(planned, by_hand)
    pairs = [x for x in jax.tree_util.tree_leaves(
        planned, is_leaf=lambda x: isinstance(x, PlannedPair))
        if isinstance(x, PlannedPair)]
    assert pairs and all(p.scheme == "tp-aware" for p in pairs)


def test_shard_assemble_identity():
    """stage_shard slices, artifact.params() concatenates: identity."""
    cfg = _smoke_cfg()
    art = _prepare(cfg, tp=2)
    assert len(art.rank_params) == 2
    planned = build_model(cfg).init(jax.random.PRNGKey(0))
    _assert_trees_equal(planned, art.params())
    # sharded leaves really are split (not everything replicated)
    shards = art.manifest["leaf_shards"]
    assert sum(v is not None for v in shards.values()) > 0
    # and a sharded leaf's rank slice is 1/tp of the global extent
    key = next(k for k, v in shards.items() if v is not None)
    flat0 = checkpoint.flatten_keys(art.rank_params[0])
    flatg = checkpoint.flatten_keys(art.params())
    dim = shards[key]
    assert flat0[key].shape[dim] * 2 == flatg[key].shape[dim]


def test_attention_fold_stage():
    """cfg.quant.attn_tp_aware compiles V->O folds into the aux tree."""
    cfg = _smoke_cfg().with_quant(attn_tp_aware=True)
    art = _prepare(cfg, tp=2)
    assert art.aux is not None and art.aux["attn_plans"]
    (path, plans), = art.aux["attn_plans"].items()
    assert "attn" in path
    assert isinstance(plans, PlannedPair) and plans.scheme == "tp-aware"
    # stacked over layers
    assert plans.up.qweight.ndim == 3


def _attn_dicts(tree, path=()):
    """(dotted path, dict) of every attention dict in a param tree."""
    if isinstance(tree, dict):
        if compiler._is_attn_dict(tree):
            yield ".".join(path), tree
            return
        for k, v in tree.items():
            yield from _attn_dicts(v, path + (k,))


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


@pytest.mark.parametrize("arch", ["qwen3-4b", "llama-3.2-vision-90b",
                                  "whisper-large-v3"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_attention_projections_stored_in_activation_dtype(arch, dtype):
    """compile_params stores every attention dict's wq/wk/wv/wo (cross-
    attention included) in a 16-bit ``cfg.dtype``, each leaf the raw
    one's round-to-nearest-even bit for bit; an f32 ``cfg.dtype`` leaves
    them as they were."""
    cfg = _smoke_cfg(arch).with_(dtype=dtype)
    key = jax.random.PRNGKey(0)
    raw = build_model(cfg).init_raw(key)
    planned = compiler.compile_params(cfg, raw, rng=key)
    raw_attn = dict(_attn_dicts(raw))
    got_attn = dict(_attn_dicts(planned))
    assert raw_attn and got_attn.keys() == raw_attn.keys()
    for path, node in got_attn.items():
        for k, leaf in node.items():
            want = raw_attn[path][k]
            if k in compiler.ATTN_PROJ:
                want = want.astype(dtype)
            assert leaf.dtype == want.dtype, (path, k)
            np.testing.assert_array_equal(_bits(leaf), _bits(want))


def test_float32_config_plans_and_computes_as_before():
    """With ``cfg.dtype`` float32 the attention-dtype stage is the
    identity (the plan is what quantize + layout alone give), and the
    projection helper computes what ``x @ w`` computes."""
    from repro.models.common import project

    cfg = _smoke_cfg().with_(dtype="float32")
    key = jax.random.PRNGKey(0)
    raw = build_model(cfg).init_raw(key)
    state = compiler.PlanState(cfg=cfg,
                               policy=ExecutionPolicy.from_config(cfg),
                               params=raw, rng=key)
    before = compiler.run_stages(
        state, (compiler.stage_quantize, compiler.stage_layout)).params
    planned = compiler.compile_params(cfg, raw, rng=key)
    _assert_trees_equal(planned, before)
    w = planned["layers"]["attn"]["wq"][0]
    for dt in (jnp.bfloat16, jnp.float32):
        x = jax.random.normal(key, (3, w.shape[0])).astype(dt)
        np.testing.assert_array_equal(np.asarray(project(x, w)),
                                      np.asarray(x @ w))


def test_attention_fold_plans_from_f32_weights():
    """The V->O fold runs before the projections go to bf16: its plans are
    those the f32 weights give, bit for bit."""
    cfg = _smoke_cfg().with_quant(attn_tp_aware=True)
    key = jax.random.PRNGKey(0)
    raw = build_model(cfg).init_raw(key)
    state = compiler.PlanState(cfg=cfg,
                               policy=ExecutionPolicy.from_config(cfg),
                               params=raw, rng=key)
    f32 = compiler.run_stages(state, (compiler.stage_quantize,
                                      compiler.stage_layout,
                                      compiler.stage_fold_attention))
    art = compiler.compile_plan(cfg, raw, tp=1, rng=key)
    _assert_trees_equal(art.aux["attn_plans"], f32.attn_plans)
    (_, attn), = _attn_dicts(art.params())
    assert attn["wv"].dtype == jnp.bfloat16


def test_artifact_keeps_bf16_attention(tmp_path):
    """Written and loaded back (to the device, and to host memory as the
    per-rank loader reads it), every rank's attention projections are
    still bf16, and the assembled tree is the in-memory plan's."""
    cfg = _smoke_cfg()
    art_dir = str(tmp_path / "artifact")
    _prepare(cfg, tp=2).save(art_dir)
    loaded = DeploymentArtifact.load(art_dir)
    host = checkpoint.load(f"{art_dir}/rank_01.npz", host=True)
    for tree in (*loaded.rank_params, host):
        (_, attn), = _attn_dicts(tree)
        assert {str(attn[k].dtype) for k in compiler.ATTN_PROJ} == \
            {"bfloat16"}
    _assert_trees_equal(build_model(cfg).init(jax.random.PRNGKey(0)),
                        loaded.params())


# ---------------------------------------------------------------------------
# artifact round-trip: no quantization at load, bit-identical serving
# ---------------------------------------------------------------------------

def _forbid_requantize(monkeypatch):
    """Loading an artifact must never re-run the offline pipeline."""
    from repro.core import quantization, reorder

    def boom(*a, **k):
        raise AssertionError("offline pipeline invoked at load time")

    monkeypatch.setattr(quantization, "quantize", boom)
    monkeypatch.setattr(reorder, "quantize_pair", boom)
    monkeypatch.setattr(reorder, "plan_pair", boom)
    monkeypatch.setattr(compiler, "stage_quantize", boom)


def test_artifact_serves_bit_identical_logits(tmp_path, monkeypatch):
    """The acceptance criterion: prepare -> save -> load -> serve produces
    logits bit-identical to the in-memory path for the same
    config/policy/seed, without invoking GPTQ or plan_pair at load."""
    from repro.runtime.serve import make_engine

    cfg = _smoke_cfg()
    art_dir = str(tmp_path / "artifact")
    _prepare(cfg, tp=1, seed=0).save(art_dir)

    eng_mem = make_engine(cfg, jax.random.PRNGKey(0), max_seq=16)

    _forbid_requantize(monkeypatch)
    eng_art = make_engine(cfg, jax.random.PRNGKey(0), max_seq=16,
                          artifact=art_dir)
    _assert_trees_equal(eng_mem.params, eng_art.params)

    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0,
                              cfg.vocab_size)
    y_mem = eng_mem.model.forward(eng_mem.params, {"tokens": toks},
                                  REPLICATED)
    y_art = eng_art.model.forward(eng_art.params, {"tokens": toks},
                                  REPLICATED)
    np.testing.assert_array_equal(np.asarray(y_mem), np.asarray(y_art))

    # and through a decode step (the serving hot path)
    cache = eng_art.init_cache(2)
    l_art, _ = eng_art._decode(eng_art.params, cache, toks[:, 0],
                               jnp.int32(0))
    cache = eng_mem.init_cache(2)
    l_mem, _ = eng_mem._decode(eng_mem.params, cache, toks[:, 0],
                               jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(l_mem), np.asarray(l_art))


def test_artifact_rejects_mismatched_plan(tmp_path):
    cfg = _smoke_cfg()
    art_dir = str(tmp_path / "artifact")
    _prepare(cfg, tp=2).save(art_dir)
    art = DeploymentArtifact.load(art_dir)
    pol = ExecutionPolicy.from_config(cfg)

    art.validate(cfg=cfg, policy=pol, tp=2)          # the matching plan
    with pytest.raises(PlanMismatchError, match="model-axis degree"):
        art.validate(tp=4)
    with pytest.raises(PlanMismatchError, match="policy"):
        art.validate(policy=pol.with_(collective="quant-int8"))
    with pytest.raises(PlanMismatchError, match="scheme|policy"):
        art.validate(policy=pol.with_(scheme="exllama"))
    with pytest.raises(PlanMismatchError, match="config hash"):
        art.validate(cfg=cfg.with_(d_ff=cfg.d_ff * 2))
    with pytest.raises(PlanMismatchError, match="compiled for"):
        art.validate(cfg=dataclasses.replace(cfg, arch_id="other"))


def test_artifact_backend_is_chosen_where_served(tmp_path, monkeypatch):
    """``backend=auto`` resolves on the serving platform, not where the
    plan was prepared: an artifact prepared on this CPU host (jnp) loads
    under a TPU's Pallas policy, and its own policy picks Pallas there."""
    from repro.core import policy as policy_mod

    cfg = _smoke_cfg()
    assert cfg.quant.backend == "auto"
    art_dir = str(tmp_path / "artifact")
    _prepare(cfg, tp=2).save(art_dir)
    art = DeploymentArtifact.load(art_dir)
    assert art.manifest["policy"]["backend"] == "jnp"
    monkeypatch.setattr(policy_mod, "platform_is_tpu", lambda: True)
    served = ExecutionPolicy.from_config(cfg)
    assert served.backend == "pallas"
    art.validate(cfg=cfg, policy=served, tp=2)
    assert art.policy() == served


def test_prepare_cli_num_layers_cut(tmp_path):
    """``serve prepare --num-layers N`` plans only the first N layers; the
    manifest records the cut and serving rebuilds that config, so the
    config hash still matches."""
    from repro.launch import serve

    art_dir = str(tmp_path / "artifact")
    serve.prepare(["--arch", "qwen3-4b", "--smoke", "--num-layers", "1",
                   "--out", art_dir])
    man = DeploymentArtifact.load_manifest(art_dir)
    assert man["num_layers"] == 1
    (pair,) = man["pairs"]
    assert pair["stacked"] == [1]
    cfg = serve.config_from_manifest(man)
    assert cfg.num_layers == 1
    assert cfg.d_model == _smoke_cfg().d_model
    DeploymentArtifact.load(art_dir).validate(cfg=cfg)


def test_engine_refuses_mismatched_artifact(tmp_path):
    from repro.runtime.serve import make_engine

    cfg = _smoke_cfg()
    art_dir = str(tmp_path / "artifact")
    _prepare(cfg, tp=2).save(art_dir)      # pre-sharded for TP=2
    with pytest.raises(PlanMismatchError, match="model-axis degree"):
        # single-device ctx (tp=1) != the artifact's TP=2 plan
        make_engine(cfg, max_seq=16, artifact=art_dir)


def test_artifact_manifest_contents(tmp_path):
    cfg = _smoke_cfg()
    art_dir = str(tmp_path / "artifact")
    _prepare(cfg, tp=2, seed=5).save(art_dir)
    man = DeploymentArtifact.load(art_dir).manifest
    assert man["arch_id"] == cfg.arch_id
    assert man["tp"] == 2 and man["seed"] == 5
    assert man["policy"]["scheme"] == "tp-aware"
    assert man["policy"]["collective"] == "psum"
    (pair,) = man["pairs"]
    assert pair["scheme"] == "tp-aware"
    assert pair["k1"] == cfg.d_model and pair["n1"] == cfg.d_ff
    assert pair["gate"] is True and pair["stacked"] == [cfg.num_layers]


# ---------------------------------------------------------------------------
# per-layer CollectivePlan through the artifact lifecycle
# ---------------------------------------------------------------------------

def test_artifact_roundtrip_heterogeneous_collective_plan(tmp_path):
    """A per-layer plan with distinct collectives survives
    prepare -> save -> load: the manifest echoes it both as the policy
    shorthand and structurally, ``art.policy()`` reconstructs the same
    frozen plan, and ``validate`` refuses a mismatched plan/policy."""
    from repro.comm import CollectivePlan

    short = "per-layer:*.mlp=quant-int8:64,attn*=cast:float16,*=psum"
    cfg = _smoke_cfg().with_quant(collective=short)
    art_dir = str(tmp_path / "het")
    _prepare(cfg, tp=2).save(art_dir)
    art = DeploymentArtifact.load(art_dir)

    man = art.manifest
    assert man["policy"]["collective"] == short
    assert man["collective_plan"] == {
        "entries": [["*.mlp", "quant-int8:64"],
                    ["attn*", "cast:float16"]],
        "default": "psum",
    }
    shorts = {s for _, s in man["collective_plan"]["entries"]}
    shorts.add(man["collective_plan"]["default"])
    assert len(shorts) >= 2         # genuinely heterogeneous

    pol = art.policy()
    assert isinstance(pol.collective, CollectivePlan)
    assert pol.collective == CollectivePlan.parse(short)
    assert pol.collective.resolve("layers.mlp").block_size == 64
    art.validate(cfg=cfg, policy=pol, tp=2)
    # a bare-spec policy is NOT the per-layer plan it was compiled for
    with pytest.raises(PlanMismatchError, match="policy"):
        art.validate(policy=pol.with_(collective="psum"))
    with pytest.raises(PlanMismatchError, match="policy"):
        art.validate(policy=pol.with_(
            collective="per-layer:*.mlp=quant-int8:128,*=psum"))


@pytest.mark.parametrize("mark", ["fused", "overlap"])
def test_artifact_with_a_retired_epilogue_mark_is_refused(tmp_path, mark):
    """A manifest whose collective plan carries a ':fused' or ':overlap'
    mark (epilogue modes the program no longer has) is refused where the
    artifact is served, naming the mark, instead of serving a plain
    epilogue under the old name."""
    from repro.launch import serve

    cfg = _smoke_cfg().with_quant(
        collective="per-layer:*.mlp=quant-int8:128,*=psum")
    art_dir = tmp_path / "marked"
    _prepare(cfg, tp=1).save(str(art_dir))
    manifest = art_dir / "manifest.json"
    text = manifest.read_text()
    assert text.count("quant-int8:128") >= 2    # the policy and the echo
    manifest.write_text(text.replace("quant-int8:128",
                                     f"quant-int8:128:{mark}"))

    with pytest.raises(ValueError, match=f"'{mark}'"):
        DeploymentArtifact.load(str(art_dir)).policy()
    with pytest.raises(ValueError, match=f"'{mark}'"):
        serve.main(["--artifact", str(art_dir), "--requests", "1",
                    "--max-new", "1"])


def test_autotune_compiles_collective_plan(tmp_path):
    """``prepare(autotune=True)`` scores every full-output strategy per
    pair site and freezes a per-layer ``CollectivePlan`` into the
    artifact: the manifest carries >=2 distinct collectives (the tuned
    site + the psum default), the tuner report names every candidate's
    bytes/error, and the served policy round-trips the plan."""
    from repro.comm import CollectivePlan

    cfg = _smoke_cfg()
    art = compiler.prepare(cfg, tp=2, seed=0, autotune=True,
                           extra_manifest={"smoke": True})
    man = art.manifest
    plan = man["collective_plan"]
    assert plan["default"] == "psum"
    assert [p for p, _ in plan["entries"]] == [m["path"]
                                               for m in man["pairs"]]
    distinct = {s for _, s in plan["entries"]} | {plan["default"]}
    assert len(distinct) >= 2, plan

    (site,) = man["collective_tuner"]
    assert site["path"] == "layers.mlp" and site["status"] == "tuned"
    assert site["chosen"] in dict(
        (s, None) for _, s in plan["entries"]).keys()
    # every candidate was scored with both axes of the trade-off
    assert {"psum"} <= set(site["candidates"])
    for v in site["candidates"].values():
        assert v["rel_err"] >= 0 and v["bytes_per_token"] >= 0
    # the chosen collective actually compresses vs the psum baseline
    cand = site["candidates"]
    assert cand[site["chosen"]]["bytes_per_token"] < \
        cand["psum"]["bytes_per_token"]

    # round-trip through disk, then validate against the tuned policy
    art_dir = str(tmp_path / "tuned")
    art.save(art_dir)
    loaded = DeploymentArtifact.load(art_dir)
    pol = loaded.policy()
    assert isinstance(pol.collective, CollectivePlan)
    loaded.validate(cfg=cfg, policy=pol, tp=2)
    with pytest.raises(PlanMismatchError, match="policy"):
        # the pre-tune (global psum) policy is not the compiled plan
        loaded.validate(policy=ExecutionPolicy.from_config(cfg))


def test_autotune_respects_budget():
    """budget=0 forbids every lossy collective -> psum everywhere;
    a huge budget picks the cheapest wire (int4) for the mlp site."""
    cfg = _smoke_cfg()
    tight = compiler.prepare(cfg, tp=2, seed=0, autotune=True,
                             tune_budget=0.0)
    assert all(s == "psum" for _, s in
               tight.manifest["collective_plan"]["entries"])
    loose = compiler.prepare(cfg, tp=2, seed=0, autotune=True,
                             tune_budget=10.0)
    chosen = dict(loose.manifest["collective_plan"]["entries"])
    assert chosen["layers.mlp"].startswith("quant-int4")
