"""HBM bytes the pairwise build of one selection moves under the tiles
the planner chose, as the kernel wrapper counts them while traced
(`build_bytes`). A program that keeps no such count reads nothing."""
from bench.lib.counters import greedy_record


def read(r):
    rec = greedy_record(r)
    if rec is None or "build_bytes" not in rec:
        return None
    return rec["build_bytes"] / 2 ** 30
