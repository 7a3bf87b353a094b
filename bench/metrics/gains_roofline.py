"""Roofline share of the per-step gains kernel (`pairwise.gains_pallas`),
which the planner picks when no cached tier fits."""
from bench.lib.readings import roofline_share


def read(r):
    return roofline_share(r, ("gains_pallas",))
