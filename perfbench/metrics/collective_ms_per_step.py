"""Collectives: device time, on the first chip, of the collective ops
(all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute,
whole or as both halves of an async pair, or a fusion that calls one)
inside the ``jit_decode`` programs that lie wholly in the traced window,
per program.  An op's kind is read from the cell's decode program,
compiled again for its mesh (``perfbench.decode_program``)."""

from perfbench import decode_program, trace


def read(run):
    found = decode_program.ops_of_the_program(run)
    if found is None:
        return None
    ran, programs, hlo = found
    names = decode_program.collectives(hlo)
    ns = sum(e.dur_ns for e in ran if trace.op_name(e.name) in names)
    return ns / 1e6 / programs
