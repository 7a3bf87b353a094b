"""Lowerings of a jaxpr to MLIR inside the window, whether or not the
compile then came from the persistent cache (`jax.monitoring`)."""


def read(r):
    return float(r.lowerings)
