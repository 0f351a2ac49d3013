"""The yardstick's arithmetic: peaks, operations and bytes from shapes,
and statistics over all samples of a window.

Nothing here imports the program, nor do the families' counts this takes
the model's shape from: a change to the program cannot change how its
work is counted.
"""

from __future__ import annotations

import json
import math
import os

from perfbench import spec

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")

#: bytes of one activation element (the configurations state bfloat16)
ACT_BYTES = 2
#: bytes of one group scale or zero, as a GPTQ checkpoint stores them (fp16)
META_BYTES = 2


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """Published peaks of one chip; an unknown device is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def percentile(values, q: float) -> float:
    """``q``-th percentile of all values, linear between closest ranks
    (numpy's default).  ``inf`` entries (a request that never answered)
    sort last."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == xs[lo]:
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError(f"rate over a window of {seconds} s")
    return count / seconds


# ---------------------------------------------------------------------------
# one dequant-GEMM call
# ---------------------------------------------------------------------------

def dequant_gemm_flops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def dequant_gemm_bytes(m: int, k: int, n: int, group_size: int) -> int:
    """Least bytes one ``(m, k) @ int4 (k, n)`` call moves: packed int4
    weights, one fp16 scale and zero per group and column, the bf16
    input and output."""
    if k % group_size:
        raise ValueError(f"K={k} is not a multiple of group size {group_size}")
    weights = k * n // 2
    meta = 2 * (k // group_size) * n * META_BYTES
    return weights + meta + (m * k + m * n) * ACT_BYTES


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def mlp_gemm_shapes(conf: dict, tp: int = 1) -> list[tuple[int, int, int]]:
    """``(k, n, group_size)`` of the dense-MLP GEMMs one layer runs on one
    chip of a ``tp``-way model axis, as the configuration's family counts
    them."""
    return spec.family(conf).mlp_gemm_shapes(conf, tp)


# ---------------------------------------------------------------------------
# the model per fed token
# ---------------------------------------------------------------------------

def matmul_params(conf: dict) -> int:
    """Weights one token multiplies through, over every layer and the LM
    head (with experts, the active ones), as the configuration's family
    counts them."""
    return spec.family(conf).matmul_params(conf)


def _keys(first: int, count: int, window) -> int:
    """Keys attended by ``count`` consecutive tokens from 0-based position
    ``first``: ``p + 1`` at position ``p``, at most ``window``."""
    lo, hi = first + 1, first + count
    cap = hi if window is None else min(hi, window)
    ramp = (cap * (cap + 1) - (lo - 1) * lo) // 2 if cap >= lo else 0
    flat = 0 if window is None else window * max(0, hi - max(lo - 1, window))
    return ramp + flat


def token_flops(conf: dict, position: int) -> int:
    """Model FLOPs of one token at 0-based ``position``: every weight
    once (multiply and add) and attention over the keys before it
    (scores and the weighted sum)."""
    return span_flops(conf, position, 1)


def span_flops(conf: dict, first: int, count: int) -> int:
    """FLOPs of ``count`` consecutive tokens from position ``first``."""
    if count <= 0:
        return 0
    attn = sum(4 * layers * heads * head_dim * _keys(first, count, window)
               for layers, heads, head_dim, window
               in spec.family(conf).attention_layers(conf))
    return 2 * matmul_params(conf) * count + attn
