"""Bytes of the largest operand a selection kernel streams, as padded,
from the kernel wrapper's record."""
from bench.lib.counters import streamed


def read(r):
    s = streamed(r)
    return None if s is None else s["bytes"] / 2 ** 30
