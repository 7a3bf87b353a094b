"""The readers of the program's own counters (`bench/lib/counters.py`) on
the tiny k-cover cell, traced on the CPU: they agree with the readings
the harness works out from the jaxpr, and fall silent, without an error,
on a program that keeps no telemetry."""
import sys
import time

import jax
import pytest

from bench import run
from bench.lib import spec
from bench.tests.tiny import TINY, tiny_root

CELL = "kcover_retail.greedy"
NEW = ("kernel_launches", "relayout_gib", "stream_pad_share", "stream_gib")


def _traced(root):
    c = spec.load_cell(root, CELL)
    return run.run_cell(c, 2 ** 33 + 11, 0.3, True,
                        devices=jax.devices()[:1], t0=time.perf_counter(),
                        on_chip=False)


@pytest.fixture
def step_engine(monkeypatch):
    """Kernels in interpret mode, and a cache budget under the tiny
    cell's bitmap matrix, so the planner falls to the per-step engine as
    it does at the cell's own size."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    monkeypatch.setenv("REPRO_FUSED_CACHE_MB", "0.5")


def test_program_counters_match_the_outside_readings(tmp_path, step_engine):
    res = _traced(tiny_root(str(tmp_path)))
    assert res.correct and not res.errors
    m = {name: v["value"] for name, v in res.metrics.items()}
    assert abs(m["stream_pad_share"] - m["pad_share"]) <= 1e-9
    assert abs(m["stream_gib"] - m["cache_gib"]) <= 1e-9
    k, n = TINY["kcover_retail"]["k"], TINY["kcover_retail"]["n"]
    # per step: the (n, words) bitmaps padded to (n, 512) words, and the
    # covered-words row padded to 512
    bits, row = n * 512 * 4, 512 * 4
    assert m["kernel_launches"] == k
    assert m["relayout_gib"] == k * (bits + row) / 2 ** 30
    assert m["stream_gib"] == bits / 2 ** 30


def test_readers_are_silent_without_program_telemetry(tmp_path, step_engine,
                                                      monkeypatch):
    import repro.runtime
    monkeypatch.setitem(sys.modules, "repro.runtime.telemetry", None)
    monkeypatch.delattr(repro.runtime, "telemetry")
    res = _traced(tiny_root(str(tmp_path)))
    assert res.correct and not res.errors
    assert not set(NEW) & set(res.metrics)
    assert "pad_share" in res.metrics
