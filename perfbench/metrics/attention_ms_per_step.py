"""Attention: device time, on the first chip, of the ops that the decode
step's ``jax.named_scope("attention")`` names (``models/transformer.py``),
summed over the ``jit_decode`` programs that lie wholly in the traced
window, per program.  The trace's op events carry no scope, so the scope
is read from the op_name metadata of the cell's decode program, compiled
again on its chips for arguments of the shapes it ran with
(``perfbench.decode_program``; a hit in the compile cache).  Ops the
compiler inserts carry no metadata and count nowhere.  Where an op that
ran in a ``jit_decode`` program has no line in that text, the text is
not of the program that ran, and the reader reads nothing."""

from perfbench import decode_program

SCOPE = "attention"


def read(run):
    return decode_program.scoped_ms_per_step(run, SCOPE)
