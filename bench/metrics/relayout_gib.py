"""Bytes per selection that the kernel wrappers' pads write to give each
kernel its padded operands, as the wrappers count them while traced."""
from bench.lib.counters import greedy_record


def read(r):
    rec = greedy_record(r)
    return None if rec is None else rec["relayout_bytes"] / 2 ** 30
