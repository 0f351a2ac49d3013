"""Fused wire-epilogue subsystem (DESIGN.md §10).

Acceptance criteria of the fused-epilogue PR:

* the Pallas wire kernel's ``(payload, scales[, zeros])`` is BIT-identical
  to running the dense dequant-GEMM and then the collective's own
  ``_blockwise_quantize`` helpers — int8 and int4, dividing and
  non-dividing N, across wire block sizes,
* a ``:fused`` spec round-trips through parse/shorthand and refuses
  non-quant strategies,
* ``supports_wire`` gates on exactly (quant spec, tp > 1, ordered layout,
  tileable K); ineligible sites fall back to the plain epilogue with a
  one-line warning instead of erroring at forward time,
* the pallas backends degrade to jnp (warn-once) when K cannot tile the
  grid (the ``ExecutionPolicy.auto`` contract),
* under a real multi-device shard_map, fused vs unfused quant epilogues
  produce bit-identical outputs AND identical measured HLO wire bytes,
* the autotuner marks eligible winning quant sites ``:fused`` and probes
  aux attention V->O folds as (never-fused) sites.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CollectiveSpec, dispatch as comm_dispatch
from repro.comm.wire import wire_params
from repro.core import quantization as qz
from repro.core.policy import ExecutionPolicy
from repro.kernels import dispatch as kdispatch, ops

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(script: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, env=env, timeout=560)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def _ordered_ql(k, n, gs, seed=0):
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n)) * 0.1
    return qz.quantize(w, gs, act_order=True).ordered


def _ragged_ql(n=32):
    """An ordered layout with a ragged final group (K=24, gs=16, G=2):
    valid for ``qz.dequantize`` (g_idx gather) but NOT pallas-tileable —
    lcm(16, 8)=16 does not divide 24."""
    r = jax.random.split(jax.random.PRNGKey(9), 3)
    return qz.QuantizedLinear(
        qweight=jax.random.randint(r[0], (3, n), 0, 2**31 - 1,
                                   jnp.int32).astype(jnp.uint32),
        scales=jax.random.uniform(r[1], (2, n), jnp.float32, 0.01, 0.1),
        zeros=jnp.round(jax.random.uniform(r[2], (2, n), jnp.float32,
                                           0.0, 15.0)),
        g_idx=None, group_size=16, kind="ordered")


# ---------------------------------------------------------------------------
# kernel bit-identity vs quantize-after-GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,n,gs,tp,bits,blk", [
    (128, 96, 32, 4, 8, 32),     # int8, N % (tp*blk) != 0 -> odd wire block
    (64, 128, 8, 8, 8, 128),     # int8, block clamped to the chunk
    (128, 96, 32, 2, 4, 32),     # int4, asymmetric + packing
    (256, 256, 64, 2, 4, 16),    # int4, small preferred block
])
def test_fused_payload_bit_identical(k, n, gs, tp, bits, blk):
    """Fused kernel output == blockwise-quantize of the padded dense
    Pallas GEMM output, bit for bit (payload, scales, zeros)."""
    ql = _ordered_ql(k, n, gs)
    m = 16
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k))

    n_pad, _, bs = wire_params(n, tp, bits, blk)
    y = ops.dequant_matmul(x, ql)                       # dense pallas GEMM
    y32 = jnp.pad(y.astype(jnp.float32), [(0, 0), (0, n_pad - n)])

    p, s, z = ops.dequant_matmul_wire(x, ql, tp=tp, wire_bits=bits,
                                      wire_block=blk)
    if bits == 8:
        q_ref, s_ref = comm_dispatch._blockwise_quantize(y32, bs)
        assert z is None
        np.testing.assert_array_equal(np.asarray(p), np.asarray(q_ref))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    else:
        q_ref, s_ref, z_ref = comm_dispatch._blockwise_quantize_int4(y32, bs)
        p_ref = comm_dispatch._pack4_last(q_ref)
        np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
        np.testing.assert_array_equal(np.asarray(z), np.asarray(z_ref))


def test_fused_payload_batched_lead_dims():
    """Leading batch dims flatten/reshape through the wire kernel."""
    ql = _ordered_ql(64, 64, 32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 64))
    p, s, z = ops.dequant_matmul_wire(x, ql, tp=2, wire_bits=8,
                                      wire_block=32)
    assert p.shape == (2, 3, 64) and p.dtype == jnp.int8
    assert s.shape == (2, 3, 2) and s.dtype == jnp.float16
    p2, s2, _ = ops.dequant_matmul_wire(x.reshape(6, 64), ql, tp=2,
                                        wire_bits=8, wire_block=32)
    np.testing.assert_array_equal(np.asarray(p).reshape(6, 64),
                                  np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(s).reshape(6, 2),
                                  np.asarray(s2))


# ---------------------------------------------------------------------------
# spec: ':fused' shorthand
# ---------------------------------------------------------------------------

def test_fused_spec_parse_round_trip():
    for short in ("quant-int8:128:fused", "quant-int4:32:fused",
                  "quant-int8:fused", "quant-int4:fused"):
        spec = CollectiveSpec.parse(short)
        assert spec.fused
        assert CollectiveSpec.parse(spec.shorthand()) == spec
    assert CollectiveSpec.parse("quant-int8:fused").block_size == 128
    assert not CollectiveSpec.parse("quant-int8:128").fused


def test_fused_spec_rejects_non_quant():
    with pytest.raises(ValueError, match="only applies to quant"):
        CollectiveSpec(name="psum", fused=True)
    with pytest.raises(ValueError, match="takes no ':' argument"):
        CollectiveSpec.parse("psum:fused")
    with pytest.raises(ValueError, match="too many ':'"):
        CollectiveSpec.parse("quant-int8:128:64:fused")


# ---------------------------------------------------------------------------
# eligibility gate + graceful fallbacks (S1)
# ---------------------------------------------------------------------------

def test_supports_wire_gating():
    ql = _ordered_ql(64, 32, 32)
    q8 = CollectiveSpec.parse("quant-int8:128")
    assert kdispatch.supports_wire(ql, q8, 2)
    assert kdispatch.supports_wire(ql, CollectiveSpec.parse("quant-int4"), 4)
    # tp=1: no ring to feed
    assert not kdispatch.supports_wire(ql, q8, 1)
    # non-quant collective has no wire payload
    assert not kdispatch.supports_wire(ql, CollectiveSpec(name="psum"), 2)
    # naive layout: only the ordered kernel has a wire variant
    naive = qz.quantize(jax.random.normal(jax.random.PRNGKey(0), (64, 32)),
                        32, act_order=True).naive
    assert not kdispatch.supports_wire(naive, q8, 2)
    # untileable K (ragged final group: lcm(16, 8) does not divide 24)
    assert not kdispatch.supports_wire(_ragged_ql(), q8, 2)


def test_wire_backend_rejected_as_policy_backend():
    ql = _ordered_ql(64, 32, 32)
    x = jnp.zeros((2, 64))
    with pytest.raises(ValueError, match="wire payload"):
        kdispatch.qmatmul(x, ql, ExecutionPolicy(backend="pallas-fused"))


def test_fused_spec_unfusable_site_warns_and_matches_plain():
    """A hand-written ':fused' plan on an ineligible site (tp=1 mesh)
    falls back to the dense GEMM + plain collective, same numbers."""
    from repro.core import reorder, schemes

    r = jax.random.split(jax.random.PRNGKey(4), 3)
    pp = reorder.plan_pair(
        jax.random.normal(r[0], (32, 64)) * 0.1,
        jax.random.normal(r[1], (64, 32)) * 0.1,
        scheme="tp-aware", group_size_up=32, group_size_down=32, rng=r[2])
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"),
                         devices=jax.devices()[:1])
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 32))
    fused_pol = ExecutionPolicy(collective="quant-int8:128:fused")
    plain_pol = ExecutionPolicy(collective="quant-int8:128")
    schemes._UNFUSABLE_WARNED.clear()
    with pytest.warns(UserWarning, match="cannot serve pair"):
        y_f = schemes.pair_forward_tp(x, pp, mesh, fused_pol)
    y_p = schemes.pair_forward_tp(x, pp, mesh, plain_pol)
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_p))


# ---------------------------------------------------------------------------
# multi-device: bit-identity + wire bytes (subprocess, 8 host devices)
# ---------------------------------------------------------------------------

def test_fused_epilogue_tp_bit_identical_and_same_wire_bytes():
    """Under a real shard_map ring, a ':fused' quant spec produces
    BIT-identical outputs to the unfused spec (same pallas dense GEMM +
    quantize-after), and the lowered HLO moves the same collective
    bytes — the fusion saves HBM traffic, never wire traffic."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import reorder, schemes
        from repro.core.policy import ExecutionPolicy
        from repro.launch import roofline

        r = jax.random.split(jax.random.PRNGKey(0), 3)
        pp = reorder.plan_pair(
            jax.random.normal(r[0], (64, 256)) * 0.1,
            jax.random.normal(r[1], (256, 96)) * 0.1,
            scheme="tp-aware", group_size_up=32, group_size_down=32,
            rng=r[2])
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))

        for tp, short in ((4, "quant-int8:32"), (2, "quant-int4:32")):
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((1, tp), ("data", "model"),
                                 devices=jax.devices()[:tp])
            outs, bytes_ = {}, {}
            for tag, coll in (("plain", short),
                              ("fused", short + ":fused")):
                pol = ExecutionPolicy(backend="pallas", collective=coll)
                fn = lambda xx, pol=pol: schemes.pair_forward_tp(
                    xx, pp, mesh, pol)
                outs[tag] = np.asarray(jax.jit(fn)(x))
                txt = jax.jit(fn).lower(x).compile().as_text()
                bytes_[tag] = roofline.parse_collective_bytes(
                    txt, chips=tp)["total_per_device"]
            np.testing.assert_array_equal(outs["plain"], outs["fused"])
            assert bytes_["plain"] == bytes_["fused"], (short, bytes_)
            assert bytes_["plain"] > 0
            print(f"OK {short} tp={tp} wire_B={bytes_['plain']:.0f}")
    """)
    assert out.count("OK") == 2


# ---------------------------------------------------------------------------
# tuner integration
# ---------------------------------------------------------------------------

def test_tuner_marks_eligible_quant_sites_fused():
    """autotune marks the winning quant spec ':fused' where the wire
    kernel can serve the site, probes aux V->O folds as attn_vo sites
    (never fused), and the artifact round-trips the plan."""
    from repro.configs import get_smoke_config
    from repro.plan import DeploymentArtifact, compiler

    cfg = get_smoke_config("qwen3-4b").with_quant(attn_tp_aware=True)
    art = compiler.prepare(cfg, tp=2, seed=0, autotune=True,
                           tune_budget=10.0)
    sites = {s["path"]: s for s in art.manifest["collective_tuner"]}
    mlp = sites["layers.mlp"]
    assert mlp["kind"] == "pair" and mlp["status"] == "tuned"
    assert mlp["chosen"].endswith(":fused") and mlp["fused"]
    spec = CollectiveSpec.parse(mlp["chosen"])
    assert spec.fused and spec.name.startswith("quant-")
    # the fused shorthand scores as an alias of the unfused winner
    base = spec.with_(fused=False).shorthand()
    cand = mlp["candidates"]
    assert cand[mlp["chosen"]] == cand[base]

    attn = sites["layers.attn"]
    assert attn["kind"] == "attn_vo" and attn["status"] == "tuned"
    assert not attn["fused"] and not attn["chosen"].endswith(":fused")

    # plan entries carry both sites; policy shorthand round-trips
    plan_paths = [p for p, _ in art.manifest["collective_plan"]["entries"]]
    assert plan_paths == ["layers.mlp", "layers.attn"]
    art.validate(cfg=cfg, policy=art.policy(), tp=2)
