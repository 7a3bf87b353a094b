"""Share of the streamed selection matrix that is padding: the traced
kernel operand's shape against the cell's logical (rows, candidates)."""
from bench.lib.readings import pad_share


def read(r):
    return pad_share(r)
