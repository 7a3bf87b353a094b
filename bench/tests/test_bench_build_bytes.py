"""The program's own count of the pairwise build's HBM bytes
(`build_bytes`, read as `build_gib`) against the harness's outside count
of the traced kernel (`bench/lib/counts.py`), and the tiles the plan
record names against the traced kernel's blocks. Tracing needs no chip:
the selection is traced for the Pallas backend and never lowered."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from bench.lib import counts

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _traced(n, d, k=8):
    from repro.core.greedy import greedy
    from repro.core.objective import make_objective
    from repro.runtime import telemetry
    obj = make_objective("kmedoid", backend="pallas")
    args = (jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n, d), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.bool_))
    jx = jax.make_jaxpr(lambda i, p, v: greedy(obj, i, p, v, k))(*args)
    ks = {kk.name: kk for kk in counts.kernels(jx)}
    return (ks, telemetry.records("greedy")[-1],
            telemetry.records("plan")[-1])


# one feature tile; Tiny ImageNet's pixel width over a small pool (the
# features tiled); and a width that leaves a zero-padded last tile
@pytest.mark.parametrize("n,d", [(2048, 128), (1024, 12_288),
                                 (1024, 12_300)])
def test_build_bytes_equal_the_outside_count(n, d):
    ks, rec, plan = _traced(n, d)
    pw = ks["pairwise_pallas"]
    assert rec["logical"] == [n, n]
    assert rec["build_bytes"] == pw.nbytes
    tiles = plan["tiles"]
    assert plan["engine"] == "mega_stream" and tiles["kernel"] == "pairwise"
    assert pw.grid == (n // tiles["tn"], n // tiles["tc"],
                       tiles["d_pad"] // tiles["td"])
    assert pw.inputs[0].block == (tiles["tn"], tiles["td"])
    assert pw.inputs[1].block == (tiles["tc"], tiles["td"])
    assert tiles["hbm_bytes"] == pw.nbytes
    assert tiles["limit"] >= 2 * tiles["need"]
    assert pw.ops == 2 * n * n * tiles["d_pad"] + 3 * n * n


def _reader():
    path = os.path.join(ROOT, "bench", "metrics", "build_gib.py")
    spec = importlib.util.spec_from_file_location("bench_metric_build_gib",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _R:
    def __init__(self, logical):
        self.logical = logical


def test_build_gib_reads_the_greedy_record():
    n, d = 2048, 128
    ks, rec, _ = _traced(n, d)
    got = _reader().read(_R((n, n)))
    assert got == ks["pairwise_pallas"].nbytes / 2 ** 30


def test_build_gib_reads_nothing_without_a_record():
    assert _reader().read(_R((3, 5))) is None
