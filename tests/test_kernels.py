"""Pallas kernel sweeps vs the pure-jnp oracles (interpret=True on CPU),
and the tiling rules that keep every block legal for the compiled kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantization as qz
from repro.kernels import dequant_matmul as dk, ops, ref


def _mk(seed, k, n, gs, act_order=True):
    r1, r2 = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(r1, (k, n))
    return qz.quantize(w, gs, act_order=act_order, rng=r2)


@pytest.mark.parametrize("m,k,n,gs,bk", [
    (8, 128, 128, 32, None),
    (16, 256, 384, 64, None),
    (128, 512, 256, 128, None),
    (1, 256, 128, 64, None),      # decode-like M=1
    (4, 1024, 128, 128, None),    # deep K
    (8, 1024, 128, 32, 256),      # four K-steps through the accumulator
    (8, 4864, 128, 76, None),     # down-GEMM group size: bk=2432, two steps
])
def test_ordered_kernel_sweep(m, k, n, gs, bk):
    res = _mk(m * 3 + k, k, n, gs)
    x = jax.random.normal(jax.random.PRNGKey(9), (m, k))
    ql = res.ordered
    y = dk.dequant_matmul_ordered(x, ql.qweight, ql.scales, ql.zeros,
                                  group_size=gs, block_k=bk,
                                  interpret=True)
    y_ref = ref.dequant_matmul_ordered(x, ql.qweight, ql.scales, ql.zeros,
                                       group_size=gs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


def _stack(seed, k, n, gs, layers=3, kind="ordered"):
    """``layers`` quantized (k, n) linears stacked along a leading dim."""
    qls = [getattr(_mk(seed + i, k, n, gs), kind) for i in range(layers)]
    return jax.tree.map(lambda *a: jnp.stack(a), *qls)


@pytest.mark.parametrize("m", [8, 64], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n,gs", [(256, 256, 128), (800, 128, 100)],
                         ids=["gs128", "gs100-whole-k"])
def test_ordered_kernel_reads_a_stacked_layer_in_place(m, k, n, gs):
    """Addressed at layer ``l`` of a stack, the kernel gives bitwise what
    it gives on ``stack[l]``: the index steers the weight-side blocks and
    nothing else."""
    st = _stack(m + k, k, n, gs)
    x = jax.random.normal(jax.random.PRNGKey(13), (m, k))
    at = jax.jit(lambda layer: dk.dequant_matmul_ordered(
        x, st.qweight, st.scales, st.zeros, group_size=gs, layer=layer,
        interpret=True))
    for layer in range(3):
        want = dk.dequant_matmul_ordered(
            x, st.qweight[layer], st.scales[layer], st.zeros[layer],
            group_size=gs, interpret=True)
        np.testing.assert_array_equal(np.asarray(at(jnp.int32(layer))),
                                      np.asarray(want))


@pytest.mark.parametrize("kind", ["ordered", "naive"])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_stacked_linear_at_a_layer_equals_its_slice(backend, kind):
    """A ``QuantizedLinear`` with ``layer`` set computes bitwise what its
    slice computes, on every backend; only the ordered Pallas kernel reads
    the stack in place, the others slice it."""
    import dataclasses

    from repro.core.policy import ExecutionPolicy
    from repro.kernels import dispatch

    st = _stack(7, 256, 128, 64, kind=kind)
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 4, 256))
    policy = ExecutionPolicy(backend=backend)
    at = jax.jit(lambda layer: dispatch.qmatmul(
        x, dataclasses.replace(st, layer=layer), policy))
    for layer in range(3):
        sliced = jax.tree.map(lambda a: a[layer], st)
        assert dataclasses.replace(st, layer=layer).k == sliced.k == 256
        np.testing.assert_array_equal(
            np.asarray(at(jnp.int32(layer))),
            np.asarray(dispatch.qmatmul(x, sliced, policy)))
    assert dispatch.reads_in_place(kind, backend) == (
        (kind, backend) == ("ordered", "pallas"))


@pytest.mark.parametrize("m,k,n,gs", [
    (8, 128, 128, 32),
    (16, 256, 384, 64),
    (32, 512, 256, 128),
])
def test_gidx_kernel_sweep(m, k, n, gs):
    res = _mk(m * 5 + n, k, n, gs)
    x = jax.random.normal(jax.random.PRNGKey(10), (m, k))
    ql = res.naive
    y = dk.dequant_matmul_gidx(x, ql.qweight, ql.scales, ql.zeros, ql.g_idx,
                               interpret=True)
    y_ref = ref.dequant_matmul_gidx(x, ql.qweight, ql.scales, ql.zeros,
                                    ql.g_idx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("k,n,gs", [(128, 128, 32), (512, 384, 128)])
def test_dequantize_kernel(k, n, gs):
    res = _mk(k + n, k, n, gs)
    ql = res.ordered
    y = dk.dequantize_ordered(ql.qweight, ql.scales, ql.zeros, group_size=gs,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref.dequantize(ql)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ops_wrapper_dtypes_and_padding(dtype):
    """ops.dequant_matmul handles leading batch dims + non-tile N/M."""
    res = _mk(42, 128, 96, 32)   # N=96 not a multiple of 128 -> padded
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 3, 128)).astype(dtype)
    for ql in (res.ordered, res.naive):
        y = ops.dequant_matmul(x, ql, compute_dtype=jnp.float32)
        y_ref = ref.dequant_matmul(x.astype(jnp.float32), ql)
        assert y.shape == (2, 3, 96)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-2, atol=2e-2)


def test_kernel_matches_scheme_forward():
    """backend='pallas' pair forward == backend='jnp' (policy-selected)."""
    from repro.core import reorder
    from repro.core.policy import ExecutionPolicy

    rng = jax.random.PRNGKey(12)
    r = jax.random.split(rng, 3)
    pp = reorder.plan_pair(
        jax.random.normal(r[0], (128, 256)),
        jax.random.normal(r[1], (256, 128)),
        scheme="tp-aware", group_size_up=32, group_size_down=32, rng=rng)
    x = jax.random.normal(r[2], (8, 128))
    y_jnp = pp.forward(x, ExecutionPolicy(backend="jnp"), activation="silu")
    y_pal = pp.forward(x, ExecutionPolicy(backend="pallas"),
                       activation="silu")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_jnp),
                               rtol=1e-4, atol=1e-3)


def test_pick_block_k():
    assert dk.pick_block_k(1024, 128) % 128 == 0
    assert 1024 % dk.pick_block_k(1024, 128) == 0
    assert dk.pick_block_k(608, 76) % 76 == 0


@pytest.mark.parametrize("k,gs,want", [
    (2560, 128, 2560),    # qwen3-4b up: no multiple of 1024 divides K
    (9728, 76, 2432),     # qwen3-4b down: lcm(8*76, 128) = 2432
    (2432, 76, 2432),     # the down GEMM's per-rank shard at tp 4
    (4096, 128, 2048),    # the largest aligned tile up to the target
    (8192, 64, 2048),
    (608, 76, 608),       # below lcm(8*gs, 128): one whole-K tile
    (256, 64, 256),
])
def test_pick_block_k_obeys_block_rule(k, gs, want):
    """Each chosen K-tile keeps x, the packed weights and the metadata on
    the (8, 128) grid, or spans the whole K."""
    bk = dk.pick_block_k(k, gs)
    assert bk == want
    dk.check_tiling(8, k, 128, gs, 8, 128, bk)
    if bk != k:
        assert bk % 128 == 0 and (bk // 8) % 8 == 0 and (bk // gs) % 8 == 0


@pytest.mark.parametrize("m,k,n,gs,bm,bn,bk,why", [
    (8, 9728, 256, 76, 8, 128, 152, "lcm"),           # the old K-tile
    (8, 2560, 256, 128, 8, 128, 256, "lcm"),          # 2 metadata rows
    (8, 512, 256, 32, 8, 64, 512, "multiple of 128"),
    (16, 512, 128, 32, 4, 128, 512, "multiple of 8"),
    (8, 512, 128, 32, 8, 128, 48, "divide"),
    (8, 24, 128, 16, 8, 128, 24, "group_size"),       # ragged final group
    (8, 16384, 128, 128, 8, 128, 16384, "VMEM"),
])
def test_check_tiling_rejects(m, k, n, gs, bm, bn, bk, why):
    with pytest.raises(ValueError, match=why):
        dk.check_tiling(m, k, n, gs, bm, bn, bk)


def _ragged_ordered_ql():
    """K=24 at group_size 16: no K-tile holds whole groups."""
    qw = jnp.zeros((3, 128), jnp.uint32)
    meta = jnp.ones((2, 128), jnp.float32)
    return qz.QuantizedLinear(qweight=qw, scales=meta, zeros=meta,
                              g_idx=None, group_size=16, kind="ordered")


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_pallas_untileable_site_raises(platform, monkeypatch):
    """The pallas backend raises on a site it cannot tile, on a TPU as
    everywhere: no silent switch to another kernel."""
    from repro.core import policy as policy_mod
    from repro.core.policy import ExecutionPolicy
    from repro.kernels import dispatch

    monkeypatch.setattr(policy_mod, "platform_is_tpu",
                        lambda: platform == "tpu")
    ql = _ragged_ordered_ql()
    ok, why = dispatch._tileable(ql)
    assert not ok and "group_size" in why
    with pytest.raises(ValueError, match="cannot tile K=24"):
        dispatch.qmatmul(jnp.zeros((8, 24)), ql,
                         ExecutionPolicy(backend="pallas"))


@pytest.mark.parametrize("platform,requested,want", [
    ("cpu", None, True), ("tpu", None, False),
    ("cpu", False, False), ("tpu", False, False), ("cpu", True, True),
])
def test_interpret_mode_follows_platform(platform, requested, want,
                                         monkeypatch):
    from repro.core import policy as policy_mod

    monkeypatch.setattr(policy_mod, "platform_is_tpu",
                        lambda: platform == "tpu")
    assert policy_mod.interpret_mode(requested) is want


def test_interpret_mode_refused_on_tpu(monkeypatch):
    from repro.core import policy as policy_mod

    monkeypatch.setattr(policy_mod, "platform_is_tpu", lambda: True)
    with pytest.raises(ValueError, match="run compiled"):
        policy_mod.interpret_mode(True)


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,s,d,causal,window,bq,bk", [
    (1, 2, 128, 32, True, None, 64, 64),
    (2, 2, 256, 64, True, None, 128, 128),
    (1, 1, 128, 32, False, None, 64, 64),
    (1, 2, 256, 32, True, 64, 64, 64),
    (1, 2, 128, 32, True, None, 128, 32),   # uneven q/k blocks
])
def test_flash_attention_sweep(b, h, s, d, causal, window, bq, bk):
    from repro.kernels import ops

    r1, r2, r3 = jax.random.split(jax.random.PRNGKey(b * s + d), 3)
    q = jax.random.normal(r1, (b, h, s, d), jnp.float32)
    k = jax.random.normal(r2, (b, h, s, d), jnp.float32)
    v = jax.random.normal(r3, (b, h, s, d), jnp.float32)
    y = ops.flash_attention(q, k, v, causal=causal, window=window,
                            block_q=bq, block_k=bk)
    y_ref = ref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_dtypes(dtype):
    from repro.kernels import ops

    r = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(rr, (1, 2, 128, 32)).astype(dtype)
               for rr in r)
    y = ops.flash_attention(q, k, v, block_q=64, block_k=64)
    y_ref = ref.flash_attention(q, k, v)
    assert y.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
        rtol=2e-2, atol=2e-2)
