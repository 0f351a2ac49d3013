"""Collective epilogue: device time, on the first chip, of the ops that
the MLP pairs' ``jax.named_scope("epilogue")`` names
(``core/schemes._pair_local_forward``: the trailing collective of the
row-parallel down GEMM) inside the ``jit_decode`` programs wholly in the
traced window, per program.  The scope is read from the op_name metadata
of the cell's decode program, compiled again for its mesh
(``perfbench.decode_program``); a program without the scope reads
nothing."""

from perfbench import decode_program, trace

SCOPE = "epilogue"


def read(run):
    found = decode_program.ops_of_the_program(run)
    if found is None:
        return None
    ran, programs, hlo = found
    names = decode_program.scoped(hlo, SCOPE)
    if not names:
        return None
    ns = sum(e.dur_ns for e in ran if trace.op_name(e.name) in names)
    return ns / 1e6 / programs
