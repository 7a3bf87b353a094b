"""Roofline share of the whole-greedy kernels (`greedy_loop.py`, streaming
and resident tiers)."""
from bench.lib.readings import roofline_share


def read(r):
    return roofline_share(r, ("greedy_loop_pallas",
                              "greedy_loop_resident_pallas"))
