"""Device time of collective ops per selection, averaged over the chips."""
from bench.lib.trace import is_collective


def read(r):
    s = r.summary
    if s is None or not r.selections or not any(
            is_collective(o.name) for d in s.devices for o in s.ops[d]):
        return None
    return 1e3 * s.collective_s() / r.selections
