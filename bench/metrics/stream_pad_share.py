"""Padding share of the largest operand a selection kernel streams, from
the shapes the kernel wrapper recorded: logical against padded."""
from bench.lib.counters import pad_share, streamed


def read(r):
    s = streamed(r)
    return None if s is None else pad_share(s)
