"""The trace reduction, on a trace recorded on a TPU v5e: three greedy
selections (k-medoid on the streaming tier, k-cover, a 400-pool k-medoid
on the resident tier) inside a host span named `probe_window`."""
import os

import pytest

from bench.lib import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_small.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return tr.summarize(FIXTURE, 1, span="probe_window")


def test_window_is_the_host_span(summary):
    assert summary.devices == ["/device:TPU:0"]
    assert summary.window == (41678489.0, 46200469.0)
    assert summary.window_s == pytest.approx(4.52198e-3)


def test_busy_is_the_union_of_ops_within_the_window(summary):
    busy = summary.busy(summary.devices[0])
    assert all(b[1] <= c[0] for b, c in zip(busy, busy[1:]))
    assert summary.busy_s == pytest.approx(
        sum(e - s for s, e in busy) * 1e-9)
    assert 0 < summary.busy_s < summary.window_s
    idle = sum(e - s for s, e in summary.gaps(summary.devices[0])) * 1e-9
    assert summary.busy_s + idle == pytest.approx(summary.window_s)


def test_kernels_are_found_by_their_wrapper_name():
    s = tr.summarize(FIXTURE, 1)            # whole trace: every op
    assert [len(s.kernel(n)) for n in ("pairwise_pallas",
                                       "greedy_loop_pallas",
                                       "greedy_loop_resident_pallas",
                                       "gains_pallas")] == [1, 2, 1, 0]
    (pw,) = s.kernel("pairwise_pallas")
    assert pw.end - pw.start == 220610.0
    assert tr.result_shapes(pw.name) == (("f32", (2048, 2048)),)
    loop = s.kernel("greedy_loop_pallas")[0]
    assert tr.result_shapes(loop.name) == (
        ("f32", (8, 256)), ("s32", (1, 128)), ("f32", (1, 128)))
    assert s.top_ops(1)[0][0] == "greedy_loop_pallas"
    assert s.collective_s() == 0.0


def test_idle_gaps_are_named_by_host_spans(summary):
    gaps = summary.idle_gaps(10)
    idle = sum(e - s for s, e in summary.gaps(summary.devices[0])) * 1e-9
    assert sum(sec for _, sec in gaps) == pytest.approx(idle)
    assert all(name != "probe_window" for name, _ in gaps)


@pytest.mark.parametrize("name,op", [
    ("%fusion.3 = (f32[]{:T(128)}, f32[2048]{0:T(1024)}) fusion(pred[2048] "
     "%a), kind=kLoop", "fusion"),
    ("%pad.31 = u32[131072,1024]{1,0:T(8,128)} pad(u32[88162,515]{1,0} %x,"
     " u32[] %c), padding=0_42910x0_509", "pad"),
    ("%all-gather-start.1 = (f32[200,768]{1,0:T(8,128)}, f32[400,768]) "
     "all-gather-start(f32[200,768] %x), channel_id=1", "all-gather-start"),
    ("jit__lambda(5791888833088582002)", ""),
])
def test_opcode(name, op):
    assert tr.opcode(name) == op
    assert tr.is_collective(name) == op.startswith("all-gather")


def test_union_and_gaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    ops = {"d": [tr.Op(1, 3, "a"), tr.Op(2, 4, "b"), tr.Op(6, 7, "c")]}
    host = [tr.Op(0, 10, "outer"), tr.Op(4, 6, "inner"), tr.Op(7, 9, "x")]
    s = tr.Summary((0.0, 10.0), ["d"], ops, host, span="none")
    assert s.gaps("d") == [(0.0, 1), (4, 6), (7, 10.0)]
    assert s.busy_s == pytest.approx(4e-9)
    # the shortest span covering half a gap names it
    assert dict(s.idle_gaps()) == pytest.approx(
        {"outer": 1e-9, "inner": 2e-9, "x": 3e-9})
