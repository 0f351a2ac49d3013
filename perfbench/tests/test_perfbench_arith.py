"""The yardstick's arithmetic against values worked out by hand at the
cells' widths."""

import json
import os

import numpy as np
import pytest

from perfbench import arith

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


#: Qwen3-4B's widths (Qwen/Qwen3-4B config.json), a second shape check
QWEN3_4B = {"hidden_size": 2560, "intermediate_size": 9728,
            "num_hidden_layers": 36, "num_attention_heads": 32,
            "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 151936}


def _conf(name):
    if name == "qwen3-4b":
        return QWEN3_4B
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_peaks_table_holds_the_published_v5e_numbers():
    for kind in ("TPU v5 lite", "TPU v5e"):
        p = arith.peaks(kind)
        assert p["flops_per_s"] == 197e12
        assert p["hbm_bytes_per_s"] == 819e9
        assert p["hbm_bytes"] == 16e9
        assert "Google Cloud" in p["source"]


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        arith.peaks("cpu")


@pytest.mark.parametrize("m,k,n,gs,flops,nbytes", [
    # granite-3-8b gate/up: 4096x12800 int4 = 26,214,400 B; 32 groups x
    # 12800 cols x (scale + zero) x 2 B = 1,638,400 B; (8x4096 + 8x12800)
    # x 2 B of activations = 270,336 B
    (8, 4096, 12800, 128, 838_860_800, 28_123_136),
    # granite-3-8b down: 128 groups of 100 rows -> 2,097,152 B of metadata
    (8, 12800, 4096, 100, 838_860_800, 28_581_888),
    # qwen3-4b at 16 slots: up 2560x9728 (20 groups), down 9728x2560 (128
    # groups of 76)
    (16, 2560, 9728, 128, 796_917_760, 12_451_840 + 778_240 + 393_216),
    (16, 9728, 2560, 76, 796_917_760, 12_451_840 + 1_310_720 + 393_216),
], ids=["granite-up", "granite-down", "qwen3-up", "qwen3-down"])
def test_dequant_gemm_flops_and_bytes(m, k, n, gs, flops, nbytes):
    assert arith.dequant_gemm_flops(m, k, n) == flops
    assert arith.dequant_gemm_bytes(m, k, n, gs) == nbytes


def test_dequant_gemm_bytes_refuses_a_group_size_that_does_not_tile_k():
    with pytest.raises(ValueError):
        arith.dequant_gemm_bytes(8, 4096, 128, 100)


def test_roofline_takes_the_larger_bound():
    peak = arith.peaks("TPU v5e")
    # 28,123,136 B at 819 GB/s = 34.34 us > 838,860,800 FLOP at 197 TF/s
    t = arith.roofline_seconds(838_860_800, 28_123_136, peak)
    assert t == pytest.approx(28_123_136 / 819e9)
    t = arith.roofline_seconds(197e12, 1.0, peak)
    assert t == pytest.approx(1.0)


def test_mlp_gemm_shapes_follow_the_configuration_and_the_tp_split():
    g = _conf("granite-3-8b")
    assert arith.mlp_gemm_shapes(g) == [(4096, 12800, 128), (4096, 12800, 128),
                                       (12800, 4096, 100)]
    assert arith.mlp_gemm_shapes(g, tp=4)[2] == (3200, 4096, 100)


@pytest.mark.parametrize("name,params", [
    # 40 x (4096*4096 + 2*4096*1024 + 4096*4096 + 3*4096*12800)
    # + 4096 * 49155
    ("granite-3-8b", 40 * 199_229_440 + 201_338_880),
    # 36 x (2560*4096 + 2*2560*1024 + 4096*2560 + 3*2560*9728)
    # + 2560 * 151936
    ("qwen3-4b", 36 * 100_925_440 + 388_956_160),
])
def test_model_flops_per_token(name, params):
    conf = _conf(name)
    assert arith.matmul_params(conf) == params
    attn = 4 * conf["num_hidden_layers"] * 32 * 128
    assert arith.token_flops(conf, 0) == 2 * params + attn
    assert arith.token_flops(conf, 99) == 2 * params + 100 * attn
    assert arith.span_flops(conf, 7, 50) == sum(
        arith.token_flops(conf, p) for p in range(7, 57))
    assert arith.span_flops(conf, 7, 0) == 0


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_matches_numpys_linear_rule(q):
    xs = list(np.random.default_rng(3).lognormal(0, 1, 137))
    assert arith.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_over_all_samples_and_missing_ones_last():
    assert arith.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
    # 40 samples: rank 37.05 lies between the 38th (1.0) and 39th (5.0)
    assert arith.percentile([5.0, float("inf")] + [1.0] * 38, 95) \
        == pytest.approx(1.2)
    assert arith.percentile([float("inf")] * 3 + [1.0] * 17, 95) \
        == float("inf")
    with pytest.raises(ValueError):
        arith.percentile([], 95)


def test_rate_is_count_over_the_whole_window():
    assert arith.rate(510, 51) == 10.0
    with pytest.raises(ValueError):
        arith.rate(1, 0)
