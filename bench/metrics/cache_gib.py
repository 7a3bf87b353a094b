"""Bytes of the matrix the selection re-reads every step, on the fullest
chip, from the traced kernel operand's shape and dtype."""
from bench.lib.readings import streamed


def read(r):
    b = streamed(r)
    return None if b is None else b.nbytes / 2 ** 30
