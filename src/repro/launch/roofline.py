"""Roofline analysis from compiled dry-run artifacts.

Three terms, per (arch × shape × mesh), all in seconds (TPU v5e targets):

* compute    = HLO_FLOPs / (chips × 197 TFLOP/s bf16)
* memory     = HLO_bytes / (chips × 819 GB/s HBM)
* collective = collective_bytes / (chips × 50 GB/s ICI link)

``cost_analysis()`` reports whole-program FLOPs/bytes (already summed over
the SPMD program = per-device value × chips).  collective_bytes is parsed
from the compiled HLO text: for every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute instruction we model the
per-device ICI traffic of a ring/bidirectional implementation from the
instruction's result shape and replica-group size, then multiply by chips
to get the global number the formula above divides back down.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Optional

# --- hardware constants (TPU v5e) -----------------------------------------
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 0.5, "u4": 0.5, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# matches e.g.:  %all-gather.3 = bf16[4,1792]{1,0} all-gather(%x), ...
# ('-done' lines never match; an async '-start' is counted once here)
_INSTR_RE = re.compile(
    r"=\s*([a-z0-9_]+)\[([\d,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
# tuple-result form that *synchronous* multi-operand collectives lower to:
#   %all-to-all.4 = (s8[8,4096]{...}, /*index=1*/ f16[8,32]{...}) all-to-all(...)
# the result bytes are the sum of every tuple entry.  Deliberately does
# NOT accept '-start' here: async tuple results alias their operands
# ((in, out) pairs), so summing the entries would double-count — those
# keep the old behavior (simple form counted, tuple form skipped).
_TUPLE_INSTR_RE = re.compile(
    r"=\s*\((.*?)\)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"\(")
_SHAPE_RE = re.compile(r"([a-z0-9_]+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+)\s*=")


def _shape_bytes(dtype: str, dims: str) -> float:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _ring_bytes(kind: str, r: float, g: int) -> float:
    """Ring cost model (g = replica-group size, R = result bytes/device):
      all-gather       : R × (g-1)/g      (result is the gathered tensor)
      all-reduce       : R × 2(g-1)/g     (reduce-scatter + all-gather)
      reduce-scatter   : R × (g-1)        (input = R×g, moves (g-1)/g of it)
      all-to-all       : R × (g-1)/g
      collective-permute: R               (point-to-point)
    """
    if kind == "all-gather":
        return r * (g - 1) / g
    if kind == "all-reduce":
        return r * 2 * (g - 1) / g
    if kind == "reduce-scatter":
        return r * (g - 1)
    if kind == "all-to-all":
        return r * (g - 1) / g
    return r  # collective-permute


def iter_collectives(hlo_text: str, *, chips: int):
    """Structured per-instruction view of a module's collectives.

    Yields one dict per matched collective instruction — the substrate
    ``repro.analysis.hlo_lint``'s rule engine and the byte accounting
    below share: ``{"kind", "name", "line", "dtype", "result_bytes",
    "group", "bytes"}`` where ``bytes`` applies the ring cost model and
    ``group`` is the replica-group size (``chips`` when the instruction
    names none).  ``dtype`` is None for tuple-result forms (mixed
    payload/scale dtypes).
    """
    for lineno, line in enumerate(hlo_text.splitlines(), 1):
        m = _INSTR_RE.search(line)
        if m:
            dtype, dims, kind = m.groups()
            r = _shape_bytes(dtype, dims)
        else:
            m = _TUPLE_INSTR_RE.search(line)
            if not m:
                continue
            shapes, kind = m.groups()
            dtype = None
            r = sum(_shape_bytes(dt, dims)
                    for dt, dims in _SHAPE_RE.findall(shapes))
        g = chips
        mg = _GROUPS_RE.search(line)
        if mg:
            g = len(mg.group(1).split(","))
        else:
            mi = _GROUPS_IOTA_RE.search(line)
            if mi:
                g = int(mi.group(2))
        g = max(g, 1)
        mn = _NAME_RE.match(line)
        yield {
            "kind": kind,
            "name": mn.group(1) if mn else "",
            "line": lineno,
            "dtype": dtype,
            "result_bytes": r,
            "group": g,
            "bytes": _ring_bytes(kind, r, g),
        }


def parse_collective_bytes(hlo_text: str, *, chips: int) -> dict:
    """Per-device ICI bytes by collective kind, modeled from compiled HLO
    (ring cost model — see ``_ring_bytes``)."""
    out = {k: 0.0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for instr in iter_collectives(hlo_text, chips=chips):
        out[instr["kind"]] += instr["bytes"]
        counts[instr["kind"]] += 1
    out["total_per_device"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float       # global (per-device × chips)
    model_flops: float            # 6·N·D (train) or 2·N_active·D (serve)
    per_device_hbm: Optional[float] = None   # memory_analysis total
    collective_detail: Optional[dict] = None

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (self.chips * ICI_BW)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_frac(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    def to_json(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "model_flops": self.model_flops,
            "per_device_hbm": self.per_device_hbm,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_frac": self.useful_flops_frac,
            "collective_detail": self.collective_detail,
        }


def from_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                  chips: int, model_flops: float) -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # older jax returns [dict]
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo, chips=chips)
    mem = compiled.memory_analysis()
    per_dev = None
    if mem is not None:
        per_dev = float(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0))
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=nbytes,
        collective_bytes=coll["total_per_device"] * chips,
        model_flops=model_flops, per_device_hbm=per_dev,
        collective_detail=coll)


def fmt_seconds(s: float) -> str:
    if s <= 0:
        return "0"
    if s < 1e-3:
        return f"{s * 1e6:.1f}us"
    if s < 1:
        return f"{s * 1e3:.2f}ms"
    return f"{s:.2f}s"


def fmt_bytes(b: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB", "PB"):
        if b < 1024:
            return f"{b:.1f}{unit}"
        b /= 1024
    return f"{b:.1f}EB"
