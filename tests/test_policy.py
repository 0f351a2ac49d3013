"""ExecutionPolicy plumbing: config round-trip, dispatch resolution, and
context/engine policy threading.

Covers the acceptance criteria of the policy redesign:
* ``ExecutionPolicy.from_config`` works for every arch config and parses
  the ``QuantConfig.collective`` shorthand into a ``CollectiveSpec``,
* ``kernels/dispatch.py`` resolves every seeded (kind, backend) pair and
  errors helpfully on unknown backends,
* the policy is the only spelling — there are no legacy loose kwargs and
  no ``reduce``/``reduce_dtype`` string fields anywhere.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CollectiveSpec
from repro.configs import ARCH_IDS, QuantConfig, get_config
from repro.core import reorder, schemes
from repro.core.policy import (DEFAULT_POLICY, ExecutionPolicy,
                               KernelTiling, resolve_policy)
from repro.kernels import dispatch


def _mk_pair(seed, k1, n1, n2, gs, scheme, gate=True):
    rng = jax.random.PRNGKey(seed)
    r = jax.random.split(rng, 4)
    w_up = jax.random.normal(r[0], (k1, n1))
    w_gate = jax.random.normal(r[1], (k1, n1)) if gate else None
    w_down = jax.random.normal(r[2], (n1, n2))
    pp = reorder.plan_pair(w_up, w_down, w_gate=w_gate, scheme=scheme,
                           group_size_up=gs, group_size_down=gs, rng=rng)
    x = jax.random.normal(r[3], (8, k1))
    return pp, x


# ---------------------------------------------------------------------------
# from_config / auto
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCH_IDS)
def test_from_config_every_arch(arch):
    cfg = get_config(arch)
    pol = ExecutionPolicy.from_config(cfg)
    assert pol.scheme == cfg.quant.scheme
    assert pol.backend in dispatch.backends()
    assert pol.collective == CollectiveSpec.parse(cfg.quant.collective)
    # ModelConfig and its QuantConfig describe the same plan
    assert ExecutionPolicy.from_config(cfg.quant) == pol


def test_from_config_explicit_fields():
    qc = QuantConfig(scheme="exllama", backend="pallas",
                     compute_dtype="bfloat16", collective="quant-int8:64")
    pol = ExecutionPolicy.from_config(qc)
    assert pol.backend == "pallas"
    assert pol.compute_dtype == jnp.dtype(jnp.bfloat16)
    assert pol.collective == CollectiveSpec(name="quant-int8", block_size=64)


def test_from_config_bad_values_error():
    with pytest.raises(ValueError, match="unknown compute_dtype 'float64'"):
        ExecutionPolicy.from_config(QuantConfig(compute_dtype="float64"))
    with pytest.raises(ValueError, match="registered strategies"):
        ExecutionPolicy.from_config(QuantConfig(collective="allgather"))


def test_auto_heuristic():
    # pallas only when the layout is ordered AND we are on a real TPU
    assert ExecutionPolicy.auto("tp-aware", on_tpu=True).backend == "pallas"
    assert ExecutionPolicy.auto("exllama", on_tpu=True).backend == "pallas"
    assert ExecutionPolicy.auto("naive-actorder",
                                on_tpu=True).backend == "jnp"
    assert ExecutionPolicy.auto("tp-aware", on_tpu=False).backend == "jnp"


def test_policy_validates_and_hashes():
    with pytest.raises(ValueError, match="unknown scheme"):
        ExecutionPolicy(scheme="nope")
    with pytest.raises(ValueError, match="unknown collective"):
        ExecutionPolicy(collective="allgather")
    # hashable + stable under dtype spelling (static-arg requirement)
    a = ExecutionPolicy(compute_dtype=jnp.float32)
    b = ExecutionPolicy(compute_dtype=np.float32)
    assert a == b and hash(a) == hash(b)
    assert hash(ExecutionPolicy().with_tiling(block_m=64)) != hash(
        ExecutionPolicy())
    # string shorthands normalize to the same spec (hash-stable)
    c = ExecutionPolicy(collective="cast:bfloat16")
    d = ExecutionPolicy(collective=CollectiveSpec.parse("cast"))
    assert c == d and hash(c) == hash(d)


def test_policy_has_no_stringly_reduce_fields():
    """The redesign's contract: the collective plan is a CollectiveSpec,
    not loose strings."""
    fields = {f.name for f in dataclasses.fields(ExecutionPolicy)}
    assert "reduce" not in fields and "reduce_dtype" not in fields
    assert isinstance(DEFAULT_POLICY.collective, CollectiveSpec)
    qfields = {f.name for f in dataclasses.fields(QuantConfig)}
    assert "reduce" not in qfields and "reduce_dtype" not in qfields


# ---------------------------------------------------------------------------
# dispatch registry
# ---------------------------------------------------------------------------

def test_dispatch_resolves_all_seeded_pairs():
    pp, x = _mk_pair(0, 64, 64, 32, 32, "tp-aware")
    layouts = {"ordered": pp.up, "naive": None}
    res_naive, _ = _mk_pair(0, 64, 64, 32, 32, "naive-actorder")
    layouts["naive"] = res_naive.up
    for kind, ql in layouts.items():
        y_ref = None
        for backend in ("ref", "jnp", "pallas"):
            assert backend in dispatch.backends(kind)
            fn = dispatch.resolve(kind, backend)
            pol = ExecutionPolicy(backend=backend)
            y = np.asarray(fn(x, ql, pol))
            assert y.shape == (8, 64)
            if y_ref is None:
                y_ref = y
            else:
                np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-3)


def test_dispatch_unknown_backend_errors():
    with pytest.raises(ValueError, match="no kernel registered"):
        dispatch.resolve("ordered", "cuda")
    with pytest.raises(ValueError, match="registered backends"):
        dispatch.resolve("naive", "not-a-backend")
    with pytest.raises(ValueError, match="unknown layout kind"):
        dispatch.register("diagonal", "jnp")


def test_dispatch_extensible():
    """New backends register themselves and immediately become valid
    policy values — the redesign's extensibility contract."""
    @dispatch.register("ordered", "_test_double")
    def _double(x, ql, policy):
        jnp_fn = dispatch.resolve("ordered", "jnp")
        return 2.0 * jnp_fn(x, ql, policy)

    try:
        pp, x = _mk_pair(1, 64, 64, 32, 32, "tp-aware", gate=False)
        y1 = pp.forward(x, ExecutionPolicy(backend="jnp"))
        y2 = pp.forward(x, ExecutionPolicy(backend="_test_double"))
        # both GEMMs of the (gateless) pair double -> output is 4x
        np.testing.assert_allclose(np.asarray(y2), 4.0 * np.asarray(y1),
                                   rtol=1e-6)
    finally:
        del dispatch._REGISTRY[("ordered", "_test_double")]


# ---------------------------------------------------------------------------
# default policy == explicit spelling
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", reorder.SCHEMES)
@pytest.mark.parametrize("gate", [True, False])
def test_forward_default_policy_is_explicit_policy(scheme, gate):
    """Omitting the policy, DEFAULT_POLICY, and the fully-spelled-out
    equivalent are bit-identical (the historical default plan)."""
    pp, x = _mk_pair(7, 128, 256, 128, 32, scheme, gate)
    y_default = np.asarray(pp.forward(x, activation="silu"))
    y_explicit = np.asarray(schemes.pair_forward_reference(
        x, pp, ExecutionPolicy(scheme=scheme, backend="jnp",
                               compute_dtype=jnp.float32,
                               collective="psum"),
        activation="silu"))
    np.testing.assert_array_equal(y_default, y_explicit)
    np.testing.assert_array_equal(
        np.asarray(pp.forward(x, DEFAULT_POLICY, activation="silu")),
        y_default)
    assert resolve_policy(None) is DEFAULT_POLICY
    assert resolve_policy(y_pol := ExecutionPolicy(backend="ref")) is y_pol


# ---------------------------------------------------------------------------
# context / engine plumbing
# ---------------------------------------------------------------------------

def test_parallel_context_policy_threading():
    from repro.models.common import ParallelContext, REPLICATED

    assert REPLICATED.execution_policy == DEFAULT_POLICY
    explicit = ParallelContext(policy=ExecutionPolicy(collective="none"))
    assert explicit.execution_policy.collective == CollectiveSpec("none")
    quant = ParallelContext(policy=ExecutionPolicy(
        collective="quant-int8"))
    assert quant.execution_policy.collective.name == "quant-int8"
    # the deprecated per-field spelling is gone for good
    with pytest.raises(TypeError):
        ParallelContext(mlp_reduce="psum_scatter")


def test_engine_injects_policy_into_ctx():
    from repro.configs import get_smoke_config
    from repro.runtime.serve import make_engine

    cfg = get_smoke_config("granite-3-8b").with_quant(mode="mlp")
    eng = make_engine(cfg, jax.random.PRNGKey(0), max_seq=16)
    assert eng.policy == ExecutionPolicy.from_config(cfg)
    assert eng.ctx.policy == eng.policy
    # an explicit policy wins
    pol = ExecutionPolicy(backend="ref")
    eng2 = make_engine(cfg, jax.random.PRNGKey(0), max_seq=16, policy=pol)
    assert eng2.ctx.policy == pol


def test_tiling_flows_to_kernel():
    """KernelTiling is part of the policy and reaches the pallas wrapper."""
    pp, x = _mk_pair(5, 512, 512, 64, 32, "tp-aware", gate=False)
    pol = ExecutionPolicy(backend="pallas", tiling=KernelTiling(
        block_m=32, block_n=128, block_k=256, interpret=True))
    y = pp.forward(x, pol)
    y_ref = pp.forward(x, ExecutionPolicy(backend="ref"))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)
    # block_k is honored, not silently dropped: an un-tileable K errors
    bad = ExecutionPolicy(backend="pallas", tiling=KernelTiling(
        block_m=32, block_n=128, block_k=48, interpret=True))
    with pytest.raises(ValueError, match="bad tiling"):
        jax.block_until_ready(pp.forward(x, bad))


def test_engine_conflicting_policies_error():
    from repro.configs import get_smoke_config
    from repro.models.common import ParallelContext
    from repro.runtime.serve import make_engine

    cfg = get_smoke_config("granite-3-8b").with_quant(mode="mlp")
    ctx = ParallelContext(policy=ExecutionPolicy(backend="ref"))
    with pytest.raises(ValueError, match="conflicting deployment plans"):
        make_engine(cfg, jax.random.PRNGKey(0), ctx=ctx, max_seq=16,
                    policy=ExecutionPolicy(backend="jnp"))
    # matching policies are fine
    eng = make_engine(cfg, jax.random.PRNGKey(0), ctx=ctx, max_seq=16,
                      policy=ExecutionPolicy(backend="ref"))
    assert eng.ctx.policy.backend == "ref"


def test_policy_replace_helpers():
    pol = DEFAULT_POLICY.with_(backend="ref").with_tiling(block_m=8)
    assert pol.backend == "ref" and pol.tiling.block_m == 8
    assert DEFAULT_POLICY.tiling.block_m == 128   # frozen originals
    quant = DEFAULT_POLICY.with_(collective="quant-int8")
    assert quant.collective == CollectiveSpec.parse("quant-int8")
    assert DEFAULT_POLICY.collective == CollectiveSpec("psum")
