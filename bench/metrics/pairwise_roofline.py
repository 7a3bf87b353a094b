"""Roofline share of the pairwise matrix build (`pairwise.py`). It is
compute-bound; its float32 operations are held against the bf16 peak, the
only one the v5e publishes."""
from bench.lib.readings import roofline_share


def read(r):
    return roofline_share(r, ("pairwise_pallas",))
