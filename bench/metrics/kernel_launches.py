"""Pallas kernel launches per selection, as the greedy driver counts them
while it is traced (scan bodies times their trip count, one lane under
vmap or shard_map)."""
from bench.lib.counters import greedy_record


def read(r):
    rec = greedy_record(r)
    return None if rec is None else rec["launches"]
