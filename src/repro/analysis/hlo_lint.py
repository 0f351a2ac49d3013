"""HLO lint — comm-schedule rules decided from compiled HLO text.

Xu et al. 2025 (PAPERS.md) shows the properties this repo's past bugs
violated are fully decidable from the compiled module: wire bytes and
dtype round trips are both in the text.  This module grows
``launch/roofline.py``'s parser (``iter_collectives``) into a rule
engine with two surfaces:

* ``lint_hlo_text`` — rules over one module's text (a dump on disk, a
  CI artifact, a freshly lowered program):

  - HL001 when the caller supplies per-site analytic expectations
    (measured ring-model bytes must match ``bytes_on_wire``),
  - HL002 always: no *asymmetric* dtype-widening float ``convert`` (a
    narrow->wide convert whose wide->narrow partner never appears means
    the value entered the stream already narrowed — exactly how the old
    ``cast`` bf16 leak surfaces in multi-layer HLO), plus an optional
    root-dtype check against the activation input dtype,
  - HL004 always: no ``copy`` of a donated (input/output aliased)
    parameter.

* ``run_site_sweep`` — the self-contained deployment check: for every
  (collective spec × TP degree) site it compiles the paper's pair
  program under ``schemes.pair_forward_tp`` exactly like
  ``benchmarks/bench_comm.py`` does and asserts measured == analytic
  (rel diff < 1e-6) per site, and the dtype rules over every lowered
  module.
"""

from __future__ import annotations

import functools
import re
from typing import Iterable, Optional, Sequence

from repro.analysis.findings import Finding
from repro.launch import roofline

#: HL001 tolerance — the byte model and the implementation are the same
#: padded two-phase ring, so agreement is exact up to float accounting
BYTE_RTOL = 1e-6

#: float dtypes (HLO names) ordered by width, for the widening check
_FLOAT_BYTES = {"f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
                "f32": 4, "f64": 8}

# "%c = f32[8,16]{1,0} convert(bf16[8,16]{1,0} %x)" -> (f32, bf16)
_CONVERT_RE = re.compile(
    r"=\s*([a-z0-9]+)\[[\d,]*\]\S*\s+convert\(([a-z0-9]+)\[")
# ENTRY signature result dtype: "... -> f32[8,256] {" / tuple forms skipped
_ENTRY_ROOT_RE = re.compile(r"^ENTRY\s[^\n]*->\s*([a-z0-9]+)\[", re.M)
# donated params: input_output_alias={ {0}: (1, {}, MAY_ALIAS), ... } —
# the first element of each (param_number, param_index, kind) tuple
_ALIAS_PARAM_RE = re.compile(r"\(\s*(\d+)\s*,")
# "%p.1 = f32[8]{0} parameter(0)" -> (name, number)
_PARAM_RE = re.compile(
    r"%?([A-Za-z0-9_.\-]+)\s*=\s*\S+\s+parameter\((\d+)\)")
# "%copy.3 = f32[8]{0} copy(f32[8]{0} %p.1)" -> operand name
_COPY_RE = re.compile(
    r"%?([A-Za-z0-9_.\-]+)\s*=\s*\S+\s+copy\((?:\S+\s+)?%([A-Za-z0-9_.\-]+)\)")


def _widening_converts(hlo_text: str) -> list[Finding]:
    """HL002: asymmetric narrow->wide float converts.

    A well-formed wire round trip narrows before the collective and
    widens after — both directions appear, the pair cancels.  A widening
    convert with no matching narrowing convert anywhere in the module
    means the residual stream was already narrow when it arrived:
    information was lost upstream of the widen.
    """
    pairs: dict[tuple, int] = {}
    lines: dict[tuple, int] = {}
    for lineno, line in enumerate(hlo_text.splitlines(), 1):
        m = _CONVERT_RE.search(line)
        if not m:
            continue
        to_dt, from_dt = m.groups()
        if to_dt not in _FLOAT_BYTES or from_dt not in _FLOAT_BYTES:
            continue  # int<->float converts are quantization, not leaks
        key = (from_dt, to_dt)
        pairs[key] = pairs.get(key, 0) + 1
        lines.setdefault(key, lineno)
    out = []
    for (from_dt, to_dt), n in sorted(pairs.items()):
        if _FLOAT_BYTES[to_dt] <= _FLOAT_BYTES[from_dt]:
            continue  # narrowing or same-width: never a leak by itself
        if (to_dt, from_dt) in pairs:
            continue  # matched round trip (intended wire compression)
        out.append(Finding(
            "HL002",
            f"{n} widening convert(s) {from_dt}->{to_dt} with no "
            f"matching {to_dt}->{from_dt} narrowing — the residual "
            f"stream entered {from_dt} upstream",
            location=f"hlo:{lines[(from_dt, to_dt)]}",
            detail={"from": from_dt, "to": to_dt, "count": n}))
    return out


def _root_dtype(hlo_text: str) -> Optional[str]:
    m = _ENTRY_ROOT_RE.search(hlo_text)
    return m.group(1) if m else None


def _alias_block(hlo_text: str) -> Optional[str]:
    """The brace-balanced body of ``input_output_alias={...}`` (the
    nested ``{0}: (1, {}, ...)`` tuples make a regex fragile)."""
    tag = "input_output_alias={"
    start = hlo_text.find(tag)
    if start < 0:
        return None
    depth, i = 1, start + len(tag)
    while i < len(hlo_text) and depth:
        depth += {"{": 1, "}": -1}.get(hlo_text[i], 0)
        i += 1
    return hlo_text[start + len(tag):i - 1]


def _donated_copies(hlo_text: str) -> list[Finding]:
    """HL004: copy instructions whose operand is an aliased parameter."""
    block = _alias_block(hlo_text)
    if block is None:
        return []
    donated_nums = set(_ALIAS_PARAM_RE.findall(block))
    if not donated_nums:
        return []
    donated_names = {name for name, num in _PARAM_RE.findall(hlo_text)
                     if num in donated_nums}
    out = []
    for lineno, line in enumerate(hlo_text.splitlines(), 1):
        mc = _COPY_RE.search(line)
        if mc and mc.group(2) in donated_names:
            out.append(Finding(
                "HL004",
                f"copy of donated parameter %{mc.group(2)} — the "
                f"donation buys nothing if XLA duplicates the buffer",
                location=f"hlo:{lineno}",
                detail={"copy": mc.group(1), "param": mc.group(2)}))
    return out


def lint_hlo_text(hlo_text: str, *, chips: int = 1,
                  expected_bytes: Optional[dict] = None,
                  expect_root_dtype: Optional[str] = None,
                  location: str = "hlo") -> list[Finding]:
    """Apply every text-decidable rule to one compiled module.

    ``expected_bytes``: ``{site_label: analytic_bytes}`` — the module's
    measured per-device collective total must match the summed analytic
    prediction within ``BYTE_RTOL`` (HL001).  ``expect_root_dtype``:
    the activation input dtype (HLO name, e.g. ``"f32"``) the ENTRY
    root must preserve (HL002).
    """
    out: list[Finding] = []
    if expected_bytes:
        measured = roofline.parse_collective_bytes(
            hlo_text, chips=chips)["total_per_device"]
        analytic = sum(expected_bytes.values())
        rel = abs(measured - analytic) / max(analytic, 1.0)
        if rel > BYTE_RTOL:
            out.append(Finding(
                "HL001",
                f"measured collective bytes {measured:.1f} != analytic "
                f"{analytic:.1f} (rel diff {rel:.2e} > {BYTE_RTOL})",
                location=location,
                detail={"measured": measured, "analytic": analytic,
                        "rel": rel, "sites": dict(expected_bytes)}))
    out.extend(_widening_converts(hlo_text))
    if expect_root_dtype is not None:
        root = _root_dtype(hlo_text)
        if root is not None and root != expect_root_dtype:
            out.append(Finding(
                "HL002",
                f"ENTRY root dtype {root} != activation input dtype "
                f"{expect_root_dtype} — a wire dtype leaked out of the "
                f"residual stream",
                location=location,
                detail={"root": root, "expect": expect_root_dtype}))
    out.extend(_donated_copies(hlo_text))
    return out


# ---------------------------------------------------------------------------
# self-contained site sweep (compiled pair programs, bench_comm's setup)
# ---------------------------------------------------------------------------

#: specs whose measured==analytic equality PR 5 established exactly;
#: ``cast`` is excluded on CPU — XLA promotes the bf16 all-reduce to f32
#: (the wire stays bf16 on TPU), a backend artifact, not a plan bug
SWEEP_SPECS = ("psum", "psum_scatter", "quant-int8", "quant-int4")

_SWEEP_SHAPE = (256, 512, 256)   # (k1, n1, n2): shards to tp 8, gs 32
_SWEEP_M = 8


@functools.lru_cache(maxsize=None)
def _sweep_pair():
    import jax
    import jax.numpy as jnp

    from repro.core import reorder

    k1, n1, n2 = _SWEEP_SHAPE
    rng = jax.random.PRNGKey(0)
    r = jax.random.split(rng, 2)
    w_up = jax.random.normal(r[0], (k1, n1), jnp.float32) * 0.02
    w_down = jax.random.normal(r[1], (n1, n2), jnp.float32) * 0.02
    return reorder.plan_pair(w_up, w_down, scheme="tp-aware",
                             group_size_up=32, group_size_down=32, rng=rng)


def _lowered_pair_hlo(spec, tp: int) -> str:
    import jax
    import jax.numpy as jnp

    from repro.core.policy import ExecutionPolicy
    from repro.launch.mesh import make_mesh

    pp = _sweep_pair()
    mesh = make_mesh((1, tp), ("data", "model"))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (_SWEEP_M, _SWEEP_SHAPE[0]), jnp.float32)
    pol = ExecutionPolicy(scheme="tp-aware", backend="jnp",
                          compute_dtype=jnp.float32, collective=spec)
    with mesh:
        fn = lambda xx, p: p.forward(xx, pol, mesh, activation=None)
        return jax.jit(fn).lower(x, pp).compile().as_text()


def run_site_sweep(tps: Iterable[int] = (2, 4, 8),
                   specs: Optional[Sequence] = None) -> list[Finding]:
    """Compile one pair program per (spec × tp) and lint every rule.

    TP degrees beyond the host's device count are skipped (the CLI
    forces 8 host devices; under CI's 2-device job only tp=2 runs).
    """
    import jax

    from repro.comm.spec import CollectiveSpec

    specs = [CollectiveSpec.parse(s)
             for s in (SWEEP_SPECS if specs is None else specs)]

    out: list[Finding] = []
    n2 = _SWEEP_SHAPE[2]
    for tp in tps:
        if tp > len(jax.devices()):
            continue
        for spec in specs:
            label = f"pair@tp={tp}:{spec.shorthand()}"
            txt = _lowered_pair_hlo(spec, tp)
            out.extend(lint_hlo_text(
                txt, chips=tp,
                expected_bytes={label: spec.bytes_on_wire(
                    (_SWEEP_M, n2), tp)},
                expect_root_dtype="f32", location=label))
    return out


def run(tps: Iterable[int] = (2, 4, 8)) -> list[Finding]:
    """Entry point the CLI calls."""
    return run_site_sweep(tps=tps)
