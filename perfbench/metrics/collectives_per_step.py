"""Collectives: the number of collective exchanges on the first chip per
``jit_decode`` program wholly in the traced window: op events of the
collectives ``collective_ms_per_step`` times, less the ``-done`` halves
of async pairs (each exchange counts once, at its start)."""

from perfbench import decode_program, trace


def read(run):
    found = decode_program.ops_of_the_program(run)
    if found is None:
        return None
    ran, programs, hlo = found
    names = decode_program.collectives(hlo)
    return sum(1 for e in ran if names.get(trace.op_name(e.name))) \
        / programs
