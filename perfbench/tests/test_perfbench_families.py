"""The harness dispatches on a configuration's family: ``spec.family``
loads ``families/<family>.py`` by path; the dense family gives what the
harness gave before it had families; a family of another shape is new
files only; and the families' counts import nothing of the program."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from perfbench import arith, build, reference, spec, weights

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIGS = ["granite-3-8b", "mistral-large-123b"]
#: widths at which the CPU builds and runs the reference in seconds
SMOKE = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
             num_attention_heads=8, num_key_value_heads=4, head_dim=32,
             vocab_size=512)
KEY = 2**40 + 3

#: what the harness gave for each configuration before the families:
#: sha-256 of the ``repr`` of the program's config, of the tree's
#: structure with every leaf's shape and dtype, and of the bytes of
#: planned layers and of the reference's gaps (``_digest``); recorded on
#: the commit before ``families/`` existed, on a CPU
BEFORE = {
    "granite-3-8b": {
        "matmul_params": 8170516480,
        "span_flops_7_50": 818116608000,
        "mlp_gemm_shapes_1": [(4096, 12800, 128), (4096, 12800, 128),
                              (12800, 4096, 100)],
        "mlp_gemm_shapes_4": [(4096, 3200, 128), (4096, 3200, 128),
                              (3200, 4096, 100)],
        "published_abstract": "4708aa132a41576af01ba89e5dbc145d"
                              "a583dd8803c4a91706091ddd1d4b1522",
        "model_config": "96cdd118b2e99aad900cb0209b072e25"
                        "c204e26acb317c8a985b453e1c074f0d",
        "smoke_abstract": "e61250f881524034a81150b8d6d6cbb5"
                          "0e75b648cd678dfcdd1977359d5229fc",
        "layer0": "987babadb86fad80354cc05ad795e568"
                  "5078e53066457e56582ff64240d176f0",
        "layer1": "226c93c69a1ea51866144412b844d303"
                  "bc9da78c1f521e34671002ffcedcd571",
        "top": "3582d3fbe22497c0f46bf78926b6aec2"
               "042069dcc3811f928e6391243ef0aa5c",
        "gaps": "69c0080b87884839143a6c718f87ebc5"
                "506069638d561cd56215449dc286c1a1",
        "gaps_control": "8e59510e04c460c2e2277ec92da3a1f1"
                        "faa00f216541569dbc7aee25eb2db3d9",
    },
    "mistral-large-123b": {
        "matmul_params": 30853300224,
        "span_flops_7_50": 3087087206400,
        "mlp_gemm_shapes_1": [(12288, 28672, 128), (12288, 28672, 128),
                              (28672, 12288, 128)],
        "mlp_gemm_shapes_4": [(12288, 7168, 128), (12288, 7168, 128),
                              (7168, 12288, 128)],
        "published_abstract": "75bdcbc028891eeb7c790735464852fa"
                              "4aa9cde914476531e5305f7510a136a6",
        "model_config": "71ff0cd6335c3eb60b7bb04badee9937"
                        "973515180212c10e0b8135cf87de925f",
        "smoke_abstract": "e61250f881524034a81150b8d6d6cbb5"
                          "0e75b648cd678dfcdd1977359d5229fc",
        "layer0": "987babadb86fad80354cc05ad795e568"
                  "5078e53066457e56582ff64240d176f0",
        "layer1": "226c93c69a1ea51866144412b844d303"
                  "bc9da78c1f521e34671002ffcedcd571",
        "top": "e5fcefd659560e156f532085a1d7bfb8"
               "402c8f354b7ee21fc349992892f1be6c",
        "gaps": "f96d4297dc0618aacf4d4cd3c7d97b99"
                "eb0bc0ca56f7f62129382794384cb668",
        "gaps_control": "5f2045eb1987919bc8f501be11391143"
                        "4879e5c57a622165e9b2774248c7e0a8",
    },
}


def _conf(name):
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) \
            as f:
        return json.load(f)


def _smoke(conf):
    out = dict(conf, **SMOKE)
    out["quantization"] = dict(conf["quantization"], tp_groups=4,
                               group_size_up=128, group_size_down=128)
    return out


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _signature(tree):
    text = repr((str(jax.tree.structure(tree)),
                 [(tuple(a.shape), str(a.dtype))
                  for a in jax.tree.leaves(tree)]))
    return hashlib.sha256(text.encode()).hexdigest()


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()


# ----------------------------------------------------------------------
# spec.family
# ----------------------------------------------------------------------

def test_a_configuration_names_its_family_module():
    for name in CONFIGS:
        assert _conf(name)["family"] == "dense"
        fam = spec.family(_conf(name))
        assert fam.__file__ == os.path.join(ROOT, "perfbench", "families",
                                            "dense.py")
        assert spec.family(_conf(name)) is fam


@pytest.mark.parametrize("family", ["moe", None, "../configs/granite-3-8b"])
def test_an_unknown_family_is_an_error_that_names_the_known(family):
    conf = dict(_conf("granite-3-8b"), family=family)
    if family is None:
        del conf["family"]
    with pytest.raises(KeyError, match=r"known: \['dense'\]"):
        spec.family(conf)


def test_the_root_points_the_lookup_at_a_copy(tmp_path):
    fams = tmp_path / "perfbench" / "families"
    fams.mkdir(parents=True)
    (fams / "probe.py").write_text("WHERE = 'copy'\n")
    conf = {"name": "p", "family": "probe"}
    assert spec.family(conf, root=str(tmp_path)).WHERE == "copy"
    with pytest.raises(KeyError, match="known"):
        spec.family(conf)


# ----------------------------------------------------------------------
# the dense family gives what the harness gave before
# ----------------------------------------------------------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_counts_are_those_of_before(name):
    conf, want = _conf(name), BEFORE[name]
    assert arith.matmul_params(conf) == want["matmul_params"]
    assert arith.span_flops(conf, 7, 50) == want["span_flops_7_50"]
    assert arith.mlp_gemm_shapes(conf, 1) == want["mlp_gemm_shapes_1"]
    assert arith.mlp_gemm_shapes(conf, 4) == want["mlp_gemm_shapes_4"]


@pytest.mark.parametrize("name", CONFIGS)
def test_the_published_tree_is_that_of_before(name):
    conf = _conf(name)
    cfg = build.model_config(conf)
    tree = build.abstract_params(cfg, conf, weights.jax_key(KEY))
    assert _signature(tree) == BEFORE[name]["published_abstract"]


@pytest.mark.parametrize("name", CONFIGS)
def test_config_weights_and_reference_are_bitwise_those_of_before(name):
    from repro.models.common import ParallelContext

    conf, want = _smoke(_conf(name)), BEFORE[name]
    key = weights.jax_key(KEY)
    cfg = build.model_config(conf)
    assert _sha(repr(cfg)) == want["model_config"]
    assert _signature(build.abstract_params(cfg, conf, key)) == \
        want["smoke_abstract"]
    params = build.build_params(cfg, conf, key, ParallelContext())
    assert _signature(params) == want["smoke_abstract"]
    for i in (0, 1):
        assert _digest(jax.tree.leaves(jax.tree.map(
            lambda a: a[i], params["layers"]))) == want[f"layer{i}"]
    assert _digest(jax.tree.leaves(
        {k: v for k, v in params.items() if k != "layers"})) == want["top"]
    rng = np.random.default_rng(19)
    seqs = [rng.integers(0, 512, n).astype(np.int32) for n in (37, 20)]
    ref = reference.Reference(conf, key)
    assert _digest([g for g, _ in ref.gaps(seqs, [5, 9])]) == want["gaps"]
    assert _digest([a for g, c in ref.gaps(seqs, [5, 9], control=True)
                    for a in (g, c)]) == want["gaps_control"]


# ----------------------------------------------------------------------
# a family of another shape is new files only
# ----------------------------------------------------------------------

#: a family whose layer 0 has an MLP twice as wide as the others', in a
#: stack of its own (``lead``); the rest as the dense family makes them
LEAD_FAMILY = '''
import dataclasses
import functools

import jax

from perfbench import build, spec

dense = spec.family({"name": "lead-wide", "family": "dense"})
model_config = dense.model_config
top = dense.top
attention_layers = dense.attention_layers
mlp_gemm_shapes = dense.mlp_gemm_shapes


def _wide(conf):
    return dict(conf, intermediate_size=2 * conf["intermediate_size"])


def layer_kinds(conf):
    return ("lead",) + ("dense",) * (conf["num_hidden_layers"] - 1)


def layer(conf, key, index, kind):
    return dense.layer(_wide(conf) if kind == "lead" else conf, key, index,
                       "dense")


def _planned(cfg, conf, kind, key, index):
    if kind == "lead":
        cfg = dataclasses.replace(cfg, d_ff=2 * cfg.d_ff)
    return build.plan_layer(cfg, layer(conf, key, index, kind), key, index)


def _stacks(cfg, conf):
    n = conf["num_hidden_layers"]
    return {"lead": (functools.partial(_planned, cfg, conf, "lead"), [0]),
            "layers": (functools.partial(_planned, cfg, conf, "dense"),
                       range(1, n))}


def abstract_params(cfg, conf, key):
    out = dict(jax.eval_shape(functools.partial(top, conf), key))
    for path, (planned, indices) in _stacks(cfg, conf).items():
        one = jax.eval_shape(planned, key, indices[0])
        out[path] = build.stacked(one, len(indices))
    return out


def fill(cfg, conf, key, ctx):
    return build.fill(cfg, conf, key, ctx, abstract_params(cfg, conf, key),
                      _stacks(cfg, conf))


def matmul_params(conf):
    return dense.matmul_params(conf) + 3 * conf["hidden_size"] \\
        * conf["intermediate_size"]


#: (kind, low) of every trace of the reference layer
TRACES = []


def reference_layer(conf, kind, low, x, w):
    TRACES.append([kind, low])
    return dense.reference_layer(conf, kind, low, x, w)
'''

LEAD_SCRIPT = r'''
import dataclasses, json, sys
import jax, numpy as np
from perfbench import arith, build, reference, spec, weights
from repro.models.common import ParallelContext

assert spec.ROOT == sys.argv[1], (spec.ROOT, sys.argv[1])
cell = spec.load("lead-wide-8m.decode")
conf = cell.conf
key = weights.jax_key(5)
cfg = build.model_config(conf)
params = build.build_params(cfg, conf, key, ParallelContext())
out = {"shapes": {p: [list(a.shape) for a in jax.tree.leaves(params[p])]
                  for p in ("lead", "layers")}}
dense = spec.family(dict(conf, family="dense"))
wide = dataclasses.replace(cfg, d_ff=2 * cfg.d_ff)


def planned(cfg, fam, kind, index):
    # as one jitted call of the fill makes it
    return jax.jit(lambda k, i: build.plan_layer(
        cfg, fam.layer(conf, k, i, kind), k, i))(key, index)


def same(stack, row, one):
    return all(np.array_equal(a[row], b[0]) for a, b in
               zip(jax.tree.leaves(stack), jax.tree.leaves(one)))


out["lead_is_layer_0"] = same(params["lead"], 0,
                              planned(wide, spec.family(conf), "lead", 0))
out["rows_are_layers_1_up"] = all(
    same(params["layers"], i - 1, planned(cfg, dense, "dense", i))
    for i in (1, 2, 3))
rng = np.random.default_rng(0)
seqs = [rng.integers(0, conf["vocab_size"], 30).astype(np.int32)
        for _ in range(2)]
gaps = reference.Reference(conf, key).gaps(seqs, [4, 6], control=True)
out["gaps"] = [float(g.max()) for g, _ in gaps]
out["traces"] = spec.family(conf).TRACES
out["span_flops"] = arith.span_flops(conf, 7, 50)
out["dense_span_flops"] = arith.span_flops(dict(conf, family="dense"), 7, 50)
print(json.dumps(out))
'''


def test_a_new_family_is_new_files_only(tmp_path):
    ignore = shutil.ignore_patterns("__pycache__", "data")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=ignore)

    def files():
        return {str(p.relative_to(tmp_path)): p.read_bytes()
                for p in tmp_path.rglob("*")
                if p.is_file() and "__pycache__" not in p.parts}

    before = files()
    here = tmp_path / "perfbench"
    (here / "families" / "lead_wide.py").write_text(LEAD_FAMILY)
    conf = dict(_smoke(_conf("granite-3-8b")), name="lead-wide-8m",
                family="lead_wide")
    (here / "configs" / "lead-wide-8m.json").write_text(json.dumps(conf))
    (here / "cells" / "lead-wide-8m.decode.json").write_text(json.dumps(
        {"max_batch": 4, "max_seq": 64, "sample": 2,
         "limits": {"max_logit_gap": 0.4, "missing_answers": 0}}))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "lead-wide-8m", "source": "-",
                             "file": "perfbench/configs/lead-wide-8m.json",
                             "reduced": [], "why": "-"})
    bench["workloads"].append({"name": "lead-wide-8m.decode",
                               "config": "lead-wide-8m", "traffic": "decode",
                               "chips": 1, "why": "-"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    after = files()
    # every file that was there is as it was, but BENCHMARK.json, which
    # only gained entries
    changed = {p for p in before if after[p] != before[p]}
    assert changed == {"BENCHMARK.json"}
    old = json.loads(before["BENCHMARK.json"])
    assert all(bench[k][:len(v)] == v if isinstance(v, list)
               else bench[k] == v for k, v in old.items())

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(tmp_path),
                                           os.path.join(ROOT, "src")]))
    out = subprocess.run([sys.executable, "-c", LEAD_SCRIPT, str(tmp_path)],
                         env=env, cwd=tmp_path, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.splitlines()[-1])
    d, ff = SMOKE["hidden_size"], SMOKE["intermediate_size"]
    lead, rest = got["shapes"]["lead"], got["shapes"]["layers"]
    assert {s[0] for s in lead} == {1} and {s[0] for s in rest} == {3}
    assert max(max(s[1:]) for s in lead) == 2 * max(max(s[1:]) for s in rest)
    assert got["lead_is_layer_0"] and got["rows_are_layers_1_up"]
    assert all(np.isfinite(g) for g in got["gaps"])
    # one layer program per kind, for the program's forward and the
    # control's: four traces for two kinds of four layers
    assert sorted(got["traces"]) == [["dense", False], ["dense", True],
                                     ["lead", False], ["lead", True]]
    assert got["span_flops"] - got["dense_span_flops"] == \
        2 * 50 * 3 * d * ff


# ----------------------------------------------------------------------
# the counts import nothing of the program
# ----------------------------------------------------------------------

def test_the_families_counts_run_without_the_program():
    script = textwrap.dedent(f"""
        import json, sys
        sys.modules["repro"] = None
        sys.path[:0] = [{ROOT!r}]
        from perfbench import arith, spec
        for name in {CONFIGS!r}:
            conf = json.load(open({ROOT!r} + "/perfbench/configs/"
                                  + name + ".json"))
            fam = spec.family(conf)
            assert len(fam.layer_kinds(conf)) == conf["num_hidden_layers"]
            print(name, arith.span_flops(conf, 7, 50),
                  arith.mlp_gemm_shapes(conf, 4), fam.attention_layers(conf))
        try:
            import repro  # noqa: F401
        except ImportError:
            print("no program")
    """)
    out = subprocess.run([sys.executable, "-c", script],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.splitlines()
    assert lines[-1] == "no program"
    for name, line in zip(CONFIGS, lines):
        assert line.startswith(
            f"{name} {BEFORE[name]['span_flops_7_50']} ")
