"""The benchmark's cells shrunk to a size the CPU runs in seconds: same
family, mix and limits, small widths, two slots' worth of traffic.  An
open-loop variant of the mix and a per-head q/k-norm variant of the
configuration cover the generator's and the builder's other paths."""

from __future__ import annotations

import json
import os

from perfbench import spec

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: cell -> (configuration, traffic mix), as BENCHMARK.json pairs them
CELLS = {"granite-3-8b.decode": ("granite-3-8b", "decode")}


def _json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


SMOKE_SIZES = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=64,
                   vocab_size=512)


def smoke_conf(conf: dict, qk_norm: bool = False) -> dict:
    out = dict(conf, **SMOKE_SIZES, qk_norm=qk_norm)
    # whole-K groups of 128 rows: both grid ends in every group
    out["quantization"] = dict(conf["quantization"], tp_groups=1,
                               group_size_up=128, group_size_down=128)
    return out


def smoke_cell(name: str, *, chips: int = 1, open_loop: bool = False,
               qk_norm: bool = False) -> spec.Cell:
    conf, mix_name = CELLS[name]
    own = _json("cells", name + ".json")
    mix = dict(_json("traffic", mix_name + ".json"),
               prompt_len={"dist": "uniform", "min": 4, "max": 24},
               output_len={"dist": "uniform", "min": 8, "max": 24})
    if open_loop:
        mix.update(loop="open", rate_per_s=3.0)
        e2e = ["itl_p95_ms", "ttft_p95_ms", "setup_s"]
    else:
        mix["pool"] = 8
        e2e = ["tokens_per_s", "itl_p95_ms", "setup_s"]
    return spec.Cell(name=name, chips=chips,
                     conf=smoke_conf(_json("configs", conf + ".json"),
                                     qk_norm),
                     traffic=mix, max_batch=4, max_seq=64,
                     limits=own["limits"], sample=own["sample"],
                     end_to_end=[{"name": n, "unit": "-"} for n in e2e],
                     per_layer=[])
