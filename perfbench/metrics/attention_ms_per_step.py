"""Attention: device time, on the first chip, of the ops that the decode
step's ``jax.named_scope("attention")`` names (``models/transformer.py``),
summed over the ``jit_decode`` programs that lie wholly in the traced
window, per program.  The trace's op events carry no scope, so the
reader compiles the cell's decode program again, for arguments of the
shapes it ran with (a hit in the compile cache), and looks
each op's ``%name`` up in its HLO text, whose ``op_name`` metadata holds
the scope path.  Ops the compiler inserts carry no metadata and count
nowhere.  Where an op that ran in a ``jit_decode`` program has no line
in that text, the text is not of the program that ran, and the reader
reads nothing."""

import functools
import re
from bisect import bisect_right

from perfbench import trace

PROGRAM = r"^jit_decode\b"
SCOPE = re.compile(r"(^|/)attention/")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.*)$", re.M)
OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def scoped(hlo: str) -> set:
    """``%name`` of every instruction of ``hlo`` in the attention scope."""
    return {name for name, rest in INSTRUCTION.findall(hlo)
            if (m := OP_NAME.search(rest)) and SCOPE.search(m[1])}


def decode_hlo(cell):
    """HLO text of the cell's compiled decode program, built as
    ``build.make_loop`` builds it, from abstract weights, cache and
    lanes; None for a cell on a mesh or with a page table."""
    import jax
    import jax.numpy as jnp

    from perfbench import build, weights
    from repro.models.common import ParallelContext
    from repro.models.registry import build_model
    from repro.runtime.serve import Engine

    if cell.chips > 1:
        return None
    conf = cell.conf
    cfg = build.model_config(conf)
    key = weights.jax_key(0)
    one = jax.eval_shape(functools.partial(build._planned_layer, cfg, conf),
                         key, 0)
    layers = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (conf["num_hidden_layers"],) + a.shape[1:], a.dtype), one)
    top = jax.eval_shape(functools.partial(weights.top, conf), key)
    params = dict(top, layers=layers)
    engine = Engine(model=build_model(cfg), params=params,
                    ctx=ParallelContext(), max_seq=cell.max_seq)
    if engine.uses_page_table:
        return None
    cache = jax.eval_shape(lambda: engine.init_cache(cell.max_batch))
    lanes = jax.ShapeDtypeStruct((cell.max_batch,), jnp.int32)
    return engine._decode.lower(params, cache, lanes, lanes).compile() \
        .as_text()


def read(run):
    s = run.trace
    if s is None or not s.devices:
        return None
    dev = s.devices[0]
    progs = sorted((m.start_ns, m.end_ns)
                   for m in s.module_events(PROGRAM, dev) if m.end_ns <= s.hi)
    if not progs:
        return None
    starts = [p[0] for p in progs]
    ran = []
    for e in s.ops[dev]:
        i = bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.end_ns <= progs[i][1]:
            ran.append(e)
    hlo = decode_hlo(run.cell)
    if not hlo or not ran:
        return None
    lines = {name for name, _ in INSTRUCTION.findall(hlo)}
    if any(trace.op_name(e.name) not in lines for e in ran):
        return None
    names = scoped(hlo)
    if not names:
        return None
    ns = sum(e.dur_ns for e in ran if trace.op_name(e.name) in names)
    return ns / 1e6 / len(progs)
