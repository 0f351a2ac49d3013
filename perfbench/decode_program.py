"""The decode program of a cell on its mesh, for readers that sort the
ops of a traced window by their HLO instruction.

A chip trace's op events carry the op's ``%name`` and nothing else, so a
reader that needs an op's kind (a collective) or its scope (``epilogue``)
compiles the cell's decode program again and looks the name up in the
compiled HLO text.  The program is built as ``run.start`` and
``build.make_loop`` build it: abstract weights in the shardings of the
model's ``param_specs`` on a ``(1, chips)`` data x model mesh, the cache
in the sharding ``Engine.init_cache`` makes it in, and the lanes as the
engine feeds them.  An engine whose decode step hands its cache back in
another sharding compiles a second program, which this text is not.

Where an op that ran inside a ``jit_decode`` program has no line in the
text, the text is not of the program that ran, and the readers read
nothing.
"""

from __future__ import annotations

import functools
import json
import re
from bisect import bisect_right

from perfbench import trace

PROGRAM = r"^jit_decode\b"
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*)$", re.M)
OP_NAME = re.compile(r'\bop_name="([^"]*)"')
#: the opcode of an instruction: the first ``word(`` after its type
OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
CALLS = re.compile(r"\bcalls=(%[\w.\-]+)")
COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) ", re.M)
#: opcodes of the collectives, whole or as the halves of an async pair
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all"
                        r"|collective-permute|collective-broadcast"
                        r"|ragged-all-to-all)(-start|-done)?$")
_HLO: dict = {}


def instructions(hlo: str) -> dict:
    """``%name -> (opcode, rest of the line)`` of every instruction."""
    out = {}
    for name, rest in INSTRUCTION.findall(hlo):
        m = OPCODE.search(rest)
        out[name] = (m[1] if m else "", rest)
    return out


def _computations(hlo: str) -> dict:
    """``%computation -> its text`` (the lines up to the next header)."""
    heads = list(COMPUTATION.finditer(hlo))
    return {m[1]: hlo[m.end():heads[i + 1].start() if i + 1 < len(heads)
                      else len(hlo)]
            for i, m in enumerate(heads)}


def collectives(hlo: str) -> dict:
    """``%name -> True`` for a collective that starts one exchange (a
    whole collective or the ``-start`` of an async pair), ``False`` for
    the ``-done`` that ends one, over every instruction that is a
    collective or a fusion that calls one."""
    ins = instructions(hlo)
    comps = _computations(hlo)

    def kind(opcode):
        m = COLLECTIVE.match(opcode)
        return None if m is None else m[2] != "-done"

    def in_fusion(rest):
        m = CALLS.search(rest)
        if not m or m[1] not in comps:
            return None
        found = [kind(op) for op, _ in instructions(comps[m[1]]).values()]
        return True if True in found else (False if False in found else None)

    out = {}
    for name, (opcode, rest) in ins.items():
        k = kind(opcode) if opcode != "fusion" else in_fusion(rest)
        if k is not None:
            out[name] = k
    return out


def scoped(hlo: str, scope: str) -> set:
    """``%name`` of every instruction whose ``op_name`` holds ``scope``."""
    rx = re.compile(rf"(^|/){re.escape(scope)}/")
    return {name for name, (_, rest) in instructions(hlo).items()
            if (m := OP_NAME.search(rest)) and rx.search(m[1])}


def decode_ops(summary):
    """``(op events, programs)``: the first chip's op events inside the
    ``jit_decode`` programs that lie wholly in the traced window, and the
    number of those programs; None without a trace, a chip or a program."""
    if summary is None or not summary.devices:
        return None
    dev = summary.devices[0]
    progs = sorted((m.start_ns, m.end_ns)
                   for m in summary.module_events(PROGRAM, dev)
                   if m.end_ns <= summary.hi)
    if not progs:
        return None
    starts = [p[0] for p in progs]
    ran = []
    for e in summary.ops[dev]:
        i = bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.end_ns <= progs[i][1]:
            ran.append(e)
    return (ran, len(progs)) if ran else None


def compile_hlo(cell):
    """HLO text of the cell's decode program on its chips; None
    for a cell whose engine takes a page table."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from perfbench import build, weights
    from repro.launch.mesh import make_mesh
    from repro.models.common import ParallelContext
    from repro.models.registry import build_model
    from repro.runtime.serve import Engine

    conf = cell.conf
    cfg = build.model_config(conf)
    model = build_model(cfg)
    key = weights.jax_key(0)
    one = jax.eval_shape(functools.partial(build._planned_layer, cfg, conf),
                         key, 0)
    layers = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (conf["num_hidden_layers"],) + a.shape[1:], a.dtype), one)
    top = jax.eval_shape(functools.partial(weights.top, conf), key)
    params = dict(top, layers=layers)
    ctx = ParallelContext()
    if cell.chips > 1:
        mesh = make_mesh((1, cell.chips), ("data", "model"),
                         jax.devices()[:cell.chips])
        ctx = ParallelContext(mesh=mesh)
        specs = model.param_specs(params, ctx)
        params = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            params, specs, is_leaf=lambda x: isinstance(x, P))
    engine = Engine(model=model, params=params, ctx=ctx,
                    max_seq=cell.max_seq)
    if engine.uses_page_table:
        return None
    cache = jax.eval_shape(lambda: engine.init_cache(cell.max_batch))
    if cell.chips > 1:
        # the sharding the engine makes its cache in, where that spans the
        # mesh (a cache made on one device is left to the program to place)
        made = jax.jit(lambda: engine.init_cache(cell.max_batch)).lower() \
            .compile().output_shardings
        cache = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=s if len(s.device_set) == cell.chips else None),
            cache, made)
    lanes = jax.ShapeDtypeStruct((cell.max_batch,), jnp.int32)
    return engine._decode.lower(params, cache, lanes, lanes).compile() \
        .as_text()


def decode_hlo(cell):
    """``compile_hlo(cell)``, once per cell in this process; None for a
    cell whose engine takes a page table."""
    key = json.dumps([cell.name, cell.chips, cell.max_batch, cell.max_seq,
                      cell.conf], sort_keys=True)
    if key not in _HLO:
        _HLO[key] = compile_hlo(cell)
    return _HLO[key]


def ops_of_the_program(run):
    """``(op events, programs, hlo)`` where every op that ran in the
    decode programs has a line in the recompiled text; else None."""
    found = decode_ops(run.trace)
    if found is None:
        return None
    ran, n = found
    hlo = decode_hlo(run.cell)
    if not hlo:
        return None
    lines = instructions(hlo)
    if any(trace.op_name(e.name) not in lines for e in ran):
        return None
    return ran, n, hlo
